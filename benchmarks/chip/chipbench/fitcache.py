"""The fitted oracle as set-up: fit once per program version, then load.

The fit is deterministic from the configuration (its ``fit_seed`` and
``data_seed``). Its artifact is cached at a fixed path inside the checkout,
keyed by a hash of the configuration file, the fit seed and the bytes of
every ``*.py`` under ``src/repro``: a change to the program fits afresh in
its first run.

Beside the artifact the cache keeps the fitted parameters as plain arrays
(``<key>.ref.npz``) and the dataset as plain JSON (``<key>.data.json``),
written once right after the fit, before anything is served. The
benchmark's reference and its traffic generator read only those, never the
program's objects.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import time

import numpy as np

from chipbench import spec

SRC = spec.ROOT / "src" / "repro"


def source_digest(src: pathlib.Path) -> str:
    """sha256 over the relative path and bytes of every ``*.py`` under
    ``src`` (sorted, so the digest is independent of directory order)."""
    h = hashlib.sha256()
    for path in sorted(src.rglob("*.py")):
        h.update(str(path.relative_to(src)).encode() + b"\0")
        h.update(path.read_bytes() + b"\0")
    return h.hexdigest()


def cache_key(config_bytes: bytes, fit_seed: int, src_digest: str) -> str:
    h = hashlib.sha256()
    for part in (config_bytes, str(int(fit_seed)).encode(),
                 src_digest.encode()):
        h.update(hashlib.sha256(part).digest())
    return h.hexdigest()[:24]


def profet_config(cfg: dict):
    from repro.core.predictor import ProfetConfig
    from repro.core.regressors import DNNRegressor
    if tuple(cfg["dnn_layers"]) != tuple(DNNRegressor.LAYERS):
        raise ValueError(f"configuration asks for DNN layers "
                         f"{cfg['dnn_layers']}, the program builds "
                         f"{list(DNNRegressor.LAYERS)}")
    return ProfetConfig(clustering=cfg["clustering"],
                        max_height=cfg["max_height"],
                        poly_order=cfg["poly_order"],
                        dnn_epochs=cfg["dnn_epochs"],
                        n_trees=cfg["n_trees"], seed=cfg["fit_seed"],
                        members=tuple(cfg["members"]))


def dataset(cfg: dict):
    from repro.core import workloads
    kw = {}
    if "models" in cfg:
        kw["models"] = tuple(cfg["models"])
    return workloads.generate(devices=tuple(cfg["devices"]),
                              batches=tuple(cfg["batches"]),
                              pixels=tuple(cfg["pixels"]),
                              seed=cfg["data_seed"], **kw)


def cache_paths(cfg: dict, config_bytes: bytes, cache_dir: pathlib.Path,
                src: pathlib.Path = SRC) -> dict:
    """The configuration's cache files (``artifact``, ``ref``, ``data``)
    for the program as it stands."""
    key = cache_key(config_bytes, cfg["fit_seed"], source_digest(src))
    stem = cache_dir / f"{cfg['name']}-{key}"
    return {"artifact": stem.with_suffix(".pkl"),
            "ref": pathlib.Path(str(stem) + ".ref.npz"),
            "data": pathlib.Path(str(stem) + ".data.json")}


def cached(paths: dict) -> bool:
    return all(p.exists() for p in paths.values())


def load_or_fit(cfg: dict, config_bytes: bytes, cache_dir: pathlib.Path,
                src: pathlib.Path = SRC):
    """Returns ``(oracle, paths, hit, seconds)``: the loaded or freshly
    fitted oracle, the cache paths (``artifact``, ``ref``, ``data``),
    whether the cache held them, and the seconds the fit or load took."""
    from repro import api
    paths = cache_paths(cfg, config_bytes, cache_dir, src)
    t0 = time.monotonic()
    if cached(paths):
        return api.load(paths["artifact"]), paths, True, \
            time.monotonic() - t0
    cache_dir.mkdir(parents=True, exist_ok=True)
    for old in cache_dir.glob(f"{cfg['name']}-*"):
        old.unlink()                      # one program version at a time
    oracle = api.LatencyOracle.fit(dataset(cfg), profet_config(cfg))
    export(oracle, cfg, paths)
    api.save(oracle, paths["artifact"])
    return oracle, paths, False, time.monotonic() - t0


def export(oracle, cfg: dict, paths: dict) -> None:
    """Write the fitted parameters and the dataset as plain arrays and
    JSON, in the form the reference reads."""
    profet, ds = oracle.profet, oracle.dataset
    if profet.cfg.extra_knob_features:
        raise ValueError("the reference knows no knob features")
    feats = profet.features
    pairs = sorted(profet.cross)
    devices = list(cfg["devices"])
    arrays = {}
    G = len(pairs)
    if "linear" in cfg["members"]:
        arrays["lin_coef"] = np.stack([
            np.asarray(profet.cross[p].models["linear"].coef_, np.float64)
            for p in pairs])
    if "forest" in cfg["members"]:
        packed = [profet.cross[p].models["forest"].forest_ for p in pairs]
        T = packed[0].n_trees
        N = max(f.feat.shape[1] for f in packed)
        arrays["feat"] = np.full((G, T, N), -1, np.int32)
        arrays["left"] = np.zeros((G, T, N), np.int32)
        arrays["right"] = np.zeros((G, T, N), np.int32)
        arrays["thr"] = np.zeros((G, T, N), np.float64)
        arrays["value"] = np.zeros((G, T, N), np.float64)
        for g, f in enumerate(packed):
            n = f.feat.shape[1]
            for k in ("feat", "left", "right", "thr", "value"):
                arrays[k][g, :, :n] = getattr(f, k)
        arrays["n_nodes"] = np.stack([np.asarray(f.n_nodes, np.int64)
                                      for f in packed])
        arrays["depth"] = np.array([f.depth for f in packed], np.int64)
    if "dnn" in cfg["members"]:
        heads = [profet.cross[p].models["dnn"] for p in pairs]
        for i in range(len(heads[0].params)):
            arrays[f"w{i}"] = np.stack([np.asarray(h.params[i]["w"])
                                        for h in heads])
            arrays[f"b{i}"] = np.stack([np.asarray(h.params[i]["b"])
                                        for h in heads])
        arrays["mu"] = np.stack([np.asarray(h._stats[0], np.float64)
                                 for h in heads])
        arrays["sd"] = np.stack([np.asarray(h._stats[1], np.float64)
                                 for h in heads])
        arrays["ys"] = np.array([h._stats[2] for h in heads], np.float64)
    for kind, scalers in (("batch", profet.batch_scalers),
                          ("pixel", profet.pixel_scalers)):
        arrays[f"{kind}_coef"] = np.stack(
            [np.asarray(scalers[d].coef, np.float64) for d in devices])
        arrays[f"{kind}_lo"] = np.array([scalers[d].min_knob
                                         for d in devices], np.float64)
        arrays[f"{kind}_hi"] = np.array([scalers[d].max_knob
                                         for d in devices], np.float64)
    np.savez(paths["ref"], **arrays)
    data = {
        "devices": devices,
        "pairs": [list(p) for p in pairs],
        "batches": list(cfg["batches"]),
        "pixels": list(cfg["pixels"]),
        "cases": [list(c) for c in ds.cases],
        "n_features": len(feats.clusters),
        "cluster_of": {feats.names[i]: ci
                       for ci, c in enumerate(feats.clusters) for i in c},
        # each profile keeps its op order: the feature sums follow it
        "profiles": {d: [[[op, float(v)] for op, v in
                          ds.profile(d, c).items()] for c in ds.cases]
                     for d in devices},
        "latency_ms": {d: [float(ds.latency(d, c)) for c in ds.cases]
                       for d in devices},
    }
    with open(paths["data"], "w") as f:
        json.dump(data, f)
