#!/usr/bin/env python3
"""Chip smoke test: PROFET's fit -> bank -> HTTP serve path, once, on one
TPU chip, in one process.

    python3 chip_smoke.py                                # on a TPU host
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # tiny CPU run

Phases, in order; any failure exits non-zero and prints no result:

1. device — JAX must run on a TPU. JAX falls back to the CPU quietly when
   the TPU fails to initialise, so this check comes first.
2. fit — the paper configuration (``serve_http --full``): linear, 60-tree
   forest and 128-64-32-16-1 DNN members over the paper's four-device
   grid, fitted fresh on the chip, saved as an artifact under
   ``chiprun_out/chip_smoke/`` and loaded back.
3. serve — ``LatencyService`` and ``BackgroundServer`` in this process
   (no shard plane); a ``Client`` sends /predict in measured, cross and
   two-phase modes, one /grid per anchor and one /advise.
4. checks — every request answered, the service not degraded, no bank
   build error, every wave answered through the bank, and the bank's
   forest backend the compiled Pallas kernel.
5. correctness — the Pallas forest member equals the numpy traversal
   exactly on float32-quantized rows; the DNN member the chip computed for
   every served row is within ``DNN_RTOL`` of a float64 numpy forward of
   the same weights; and every served answer is within ``SERVED_RTOL`` of
   the same oracle with the float64 host forest.

Lines tagged "smoke, not a benchmark" carry timings and memory; the last
stdout line is ``{"ok": true, "device": {...}}``. ``--rehearse`` skips the
device check and runs a tiny fit with the kernel in interpret mode.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"

# Served answers vs the float64 host forest. The chip's forest member
# routes in float32 and averages float32 leaf values (relative rounding
# 2**-24 ~ 6e-8 per value); the DNN member runs the same program in both;
# the median and the phase-2 interpolation are float64 on the host and
# move an error by at most about 2x. 1e-5 leaves two orders of magnitude
# over that rounding and is far below a routing flip, which moves an
# answer by a tree's leaf difference over 60 trees (~1e-3 and up).
SERVED_RTOL = 1e-5
# The chip's DNN member vs a float64 forward, as the largest difference
# over the largest output. float32 matmuls over at most 128 terms round to
# ~1e-6 of the output scale; a matmul that rounds its inputs to bfloat16
# (2**-9 ~ 2e-3 each, the TPU's DEFAULT float32 precision) lands near
# 1e-3. 1e-4 sits an order of magnitude from each.
DNN_RTOL = 1e-4
SEED = 0                 # fit, weights and request stream
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def _check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def _info(msg):
    print(f"[smoke, not a benchmark] {msg}", flush=True)


def _max_rel(got, want):
    import numpy as np
    got, want = np.asarray(got, float), np.asarray(want, float)
    _check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    _check(np.array_equal(np.isnan(got), np.isnan(want)),
           "NaN cells differ from the reference")
    ok = ~np.isnan(want)
    return float((np.abs(got[ok] - want[ok]) / np.abs(want[ok])).max())


def _fit(args, artifact):
    from repro import api
    from repro.core import workloads
    from repro.core.predictor import ProfetConfig
    from repro.launch import serve_http

    artifact.unlink(missing_ok=True)           # always a fresh fit
    if args.rehearse:
        cfg = ProfetConfig(n_trees=10, dnn_epochs=5, seed=SEED)
        ds = workloads.generate(devices=("T4", "V100"),
                                models=("LeNet5", "AlexNet"))
        api.fit_or_load(artifact, cfg,
                        fit_fn=lambda: api.LatencyOracle.fit(ds, cfg))
    else:
        serve_http._fit_oracle(True, artifact, ProfetConfig.dnn_epochs, SEED)
    return api.load(artifact)


def _record_dnn(bank):
    """Wrap the bank's DNN member so every served wave's rows, group ids
    and chip outputs are kept for :func:`_dnn_close`."""
    seen = []
    member = bank._dnn_member

    def recording(X, gids):
        out = member(X, gids)
        seen.append((X, gids, out))
        return out

    bank._dnn_member = recording
    return seen


def _serve(oracle):
    """Stand the service up and send every request through a Client;
    returns the requests, their served answers and the DNN member's
    served rows."""
    import jax

    from repro import api
    from repro.core import workloads
    from repro.serve import (BackgroundServer, Client, LatencyService,
                             synthetic_requests)

    service = LatencyService(oracle, max_wave=64)
    _info(f"warm-up {service.stats.warmup_ms:.1f} ms")
    _check(not service.stats.degraded,
           f"service degraded: {service.stats.degraded_reason}")
    _check(oracle.bank_error is None, f"bank error: {oracle.bank_error}")
    _check(oracle.bank is not None, "oracle has no bank")
    _check(oracle.bank.forest_backend == "pallas",
           f"forest backend {oracle.bank.forest_backend!r}, not 'pallas'")

    reqs = synthetic_requests(oracle, n=60, seed=SEED)
    anchors = sorted({a for a, _ in oracle.pairs()})
    model = oracle.dataset.cases[0][0]
    grids = [api.GridRequest(a, model, oracle.targets_from(a),
                             workloads.BATCHES, workloads.PIXELS)
             for a in anchors]
    advise = {"anchor": anchors[0],
              "workload": {"model": model, "batch": 64, "pix": 128}}

    dnn_rows = _record_dnn(oracle.bank)
    compiles = []

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    bg = BackgroundServer(service, host="127.0.0.1", port=0).start()
    try:
        t0 = time.perf_counter()
        with Client(bg.host, bg.port) as c:
            predicted = [c.predict(r) for r in reqs]
            gridded = [c.grid(g)["grid"]["latency_ms"] for g in grids]
            advised = c.advise(advise)
            statsz = c.statsz()["stats"]
        wall = time.perf_counter() - t0
    finally:
        bg.stop()
        jax.monitoring.unregister_event_duration_listener(on_event)
    n_cells = sum(v is not None for g in gridded for p in g for row in p
                  for v in row)
    _info(f"{len(reqs)} /predict, {len(grids)} /grid ({n_cells} cells), "
          f"1 /advise ({len(advised)} rows) in {wall:.3f} s; "
          f"{len(compiles)} compiles during the requests")
    _check(not statsz["degraded"],
           f"service degraded: {statsz['degraded_reason']}")
    _check(statsz["errors"] == 0, f"{statsz['errors']} service errors")
    _check(oracle.bank_error is None, f"bank error: {oracle.bank_error}")
    # every fused dispatch went through the stacked bank's forest launch
    _check(0 < statsz["fused_calls"] == oracle.bank.forest_launches,
           f"{statsz['fused_calls']} fused calls but "
           f"{oracle.bank.forest_launches} bank forest launches")
    print(f"served: {statsz['waves']} waves, {statsz['fused_calls']} fused "
          f"calls, degraded {statsz['degraded']}", flush=True)
    modes = {r["mode"] for r in predicted}
    _check(modes >= {api.MODE_MEASURED, api.MODE_CROSS,
                     api.MODE_TWO_PHASE}, f"modes served: {sorted(modes)}")
    return (reqs, grids, advise, predicted, gridded, advised), dnn_rows


def _forest_exact(oracle, pallas_compiled):
    """The bank's forest kernel vs the numpy traversal on float32-quantized
    rows of every pair: leaf values must be equal bit for bit."""
    import jax
    import numpy as np

    from repro.kernels import forest_eval

    bank = oracle.bank
    f = bank.forest
    ds = oracle.dataset
    X, gid = [], []
    for (anchor, target), g in bank.gid.items():
        rows = oracle.feature_matrix(anchor, ds.cases[g::5])
        X.append(rows)
        gid.append(np.full(len(rows), g))
    X = np.concatenate(X).astype(np.float32).astype(np.float64)
    gid = np.concatenate(gid)
    thr = f["thr"].astype(np.float32).astype(np.float64)
    args = (f["feat"], thr, f["left"], f["right"], f["value"])
    want = forest_eval.leaf_values_grouped_numpy(X, gid, *args, f["depth"])
    got = forest_eval.leaf_values_grouped_pallas(X, gid, *args,
                                                 depth=f["depth"])
    np.testing.assert_array_equal(want.astype(np.float32), got)
    if pallas_compiled:
        # the launch is a Mosaic kernel, not an interpreted emulation
        shapes = forest_eval.pad_forest_stack(*args)
        z = np.zeros(1, np.int32)
        xt = np.zeros((forest_eval._round_up(bank.n_features,
                                             forest_eval.SUBLANES),
                       forest_eval.LANES), np.float32)
        text = forest_eval._grouped_fn().lower(z, z, xt, *shapes).as_text()
        _check("tpu_custom_call" in text, "forest launch is not a TPU kernel")
    print(f"forest exact: {got.shape[1]} rows x {got.shape[0]} trees over "
          f"{bank.n_groups} groups equal the numpy traversal "
          f"({'compiled' if pallas_compiled else 'interpreted'} on "
          f"{jax.default_backend()})", flush=True)


def _dnn_close(bank, dnn_rows):
    """The DNN member the chip served vs a float64 numpy forward of the
    same stacked weights on the same rows."""
    import numpy as np

    _check(dnn_rows, "no wave ran the DNN member")
    X = np.concatenate([x for x, _, _ in dnn_rows])
    gids = np.concatenate([g for _, g, _ in dnn_rows])
    got = np.concatenate([o for _, _, o in dnn_rows]).astype(np.float64)
    params, mu, sd, ys = bank.dnn
    # the same float64 z-score and float32 cast the bank applies
    h = ((X - mu[gids]) / sd[gids]).astype(np.float32).astype(np.float64)
    for i, layer in enumerate(params):
        w = np.asarray(layer["w"], np.float64)[gids]
        b = np.asarray(layer["b"], np.float64)[gids]
        h = np.einsum("ni,nio->no", h, w) + b
        if i < len(params) - 1:
            h = np.maximum(h, 0.0)
    want = h[:, 0] * ys[gids]
    err = float(np.abs(got - want).max() / np.abs(want).max())
    print(f"DNN member vs float64 forward: {len(X)} served rows, largest "
          f"difference {err:.3e} of the largest output (limit {DNN_RTOL:g})",
          flush=True)
    _check(err <= DNN_RTOL, f"DNN member differs by {err:.3e} from the "
           f"float64 forward")


def _served_close(oracle, reqs, grids, advise, predicted, gridded, advised):
    """Served answers vs the same oracle with the float64 host forest."""
    from repro import api

    ref = api.LatencyOracle(oracle.profet, oracle.dataset)
    _check(ref.bank is not None, f"reference bank: {ref.bank_error}")
    ref.bank.backend = "numpy"
    worst = {
        "predict": _max_rel([r["latency_ms"] for r in predicted],
                            ref.predict_many(reqs).latencies()),
        "grid": max(_max_rel([[[float("nan") if v is None else v
                                for v in row] for row in plane]
                              for plane in got],
                             ref.predict_grid(g).latency_ms)
                    for g, got in zip(grids, gridded)),
        "advise": _max_rel(
            [r["latency_ms"] for r in advised],
            [r.latency_ms for r in ref.advise(
                advise["anchor"], api.Workload(**advise["workload"]))]),
    }
    print("served vs float64 host forest, max relative difference: "
          + ", ".join(f"{k} {v:.3e}" for k, v in worst.items())
          + f" (limit {SERVED_RTOL:g})", flush=True)
    for k, v in worst.items():
        _check(v <= SERVED_RTOL, f"{k} answers differ by {v:.3e} "
               f"relative from the float64 host path")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal: no device check, Pallas "
                         "kernel in interpret mode")
    args = ap.parse_args(argv)

    import jax          # here, not at import: spawn children re-import us
    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (default backend {backend!r})",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)

    artifact = OUT / "oracle.pkl"
    try:
        t0 = time.perf_counter()
        fitted = _fit(args, artifact)
        _info(f"fit + save + load {time.perf_counter() - t0:.1f} s "
              f"({len(fitted.pairs())} pairs, members "
              f"{'/'.join(fitted.config.members)}, "
              f"{fitted.config.n_trees} trees)")
        _check(fitted.bank is not None, f"bank error: {fitted.bank_error}")
        if args.rehearse:
            # the chip's kernel, interpreted on the CPU
            fitted.bank.backend = "pallas"
        served, dnn_rows = _serve(fitted)
        _forest_exact(fitted, pallas_compiled=backend == "tpu")
        _dnn_close(fitted.bank, dnn_rows)
        _served_close(fitted, *served)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        artifact.unlink(missing_ok=True)

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    _info("HBM peak " + (f"{peak / 2**20:.1f} MiB" if peak is not None
                         else "not reported"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
