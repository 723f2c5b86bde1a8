"""``ModelBank`` — every fitted (anchor, target) ensemble packed into
stacked, device-resident tensors for single-dispatch wave execution.

After PR 3/4 a wave already costs one fused ``MedianEnsemble.predict`` per
(anchor, target) pair — but a grid sweep over D devices still pays O(D²)
Python-level group dispatches: O(D²) independent forest traversals and
O(D²) separately jitted MLP applies with per-group padding. The bank
collapses the per-group loop:

  - **forest stack** — all pairs' packed forests in one ``(G, T, N_max)``
    tensor set (pad nodes are leaves: ``feat = -1`` self-loops are never
    reached because routing starts at node 0), plus the per-group ``depth``
    vector. A wave's rows — any mix of pairs — route through
    ``kernels.forest_eval.predict_grouped`` in ONE launch (Pallas grid over
    (row-block, tree-tile) on TPU, a single depth-bounded grouped traversal
    with per-group early exit on CPU). Where the forest backend is Pallas
    the bank pads the stack into the kernel layout and places it on the
    device once, at its warm-up or first wave, whichever comes first
    (``forest_eval.device_forest_stack``, counted by
    ``bank.forest_stack_uploads``); every launch passes that device stack,
    so only the wave's rows and block vectors cross to the chip. The host
    ``forest`` dict stays as it was: ``split`` and ``to_payload`` read it,
    and the shard payload stays all numpy (its workers run numpy).
  - **DNN stack** — all heads' params in one vmapped pytree (leading group
    axis) with stacked z-score/target-scale stats; a wave pays ONE
    ``_mlp_apply_multi`` call on a ``(groups, rows, features)`` block,
    bucket-padded once per wave instead of once per group.
  - **linear + phase-2 stacks** — ``(G, D+1)`` least-squares coefficients
    applied row-stably (``LinearRegressor.apply``), and the per-device
    polynomial scaler coefficients evaluated with one Horner pass over all
    two-phase rows.

Equality bar: because routing gathers, the row-stable linear form, the
tree-sequential ``tree_mean``, and Horner evaluation are all per-row
operations, stacked answers match the per-group executor path bit-for-bit
for the float64 members (linear, forest, phase-2); the float32 DNN member
agrees to float32 precision. ``benchmarks/bench_bank.py`` asserts both on
every run.

Banks are derived state: build one from a fitted ``Profet`` and swap it
atomically with the oracle that owns it (``LatencyOracle.bank``,
``LatencyService.oracle_refreshed``). The device forest stack lives and
dies with its bank: a refit or promotion builds a new bank, which places
its own stack; a ``split`` sub-bank or a payload-built bank starts
without one. Ensembles carrying non-production
members (e.g. the frozen ``repro.core.reference`` models used by the
oracle-equivalence suite) raise :class:`BankUnsupportedError` and the
executor falls back to the per-group path.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.regressors import (DNNRegressor, LinearRegressor,
                                   RandomForestRegressor, _mlp_apply_multi,
                                   bucket, stack_dnn_heads)


class BankUnsupportedError(RuntimeError):
    """The fitted model cannot be packed (unexpected member types or
    heterogeneous shapes); callers fall back to per-group execution."""


def _np_tree(tree):
    """Convert a (possibly jax) params pytree to numpy leaves so it can
    ride a pipe or a socket into a worker that never imports jax."""
    if isinstance(tree, dict):
        return {k: _np_tree(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_np_tree(v) for v in tree)
    return np.asarray(tree)


def _tree_index(tree, idx):
    """``leaf[idx]`` over a params pytree of dicts/lists/tuples — a light
    structural map so ``ModelBank.split`` (and the shard plane's spec
    builder) can slice stacked DNN heads without importing jax. Works on
    numpy and jax leaves alike (both support integer-array indexing)."""
    if isinstance(tree, dict):
        return {k: _tree_index(v, idx) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_tree_index(v, idx) for v in tree)
    return tree[idx]


class ModelBank:
    """Stacked ensembles over the trained pair set of one ``Profet``.

    ``forest_launches`` / ``mlp_applies`` count fused dispatches over the
    bank's lifetime — the accounting ``bench_bank`` and ``tests/test_bank``
    assert is exactly one of each per wave.
    """

    def __init__(self, pairs: Sequence[Tuple[str, str]],
                 members: Tuple[str, ...], n_features: int,
                 forest: Optional[dict], lin_coef: Optional[np.ndarray],
                 dnn: Optional[tuple], devices: Tuple[str, ...],
                 scalers: Dict[str, tuple], backend: str = "auto"):
        self.pairs = tuple(pairs)
        self.gid = {p: i for i, p in enumerate(self.pairs)}
        self.members = members
        self.n_features = n_features
        self.forest = forest          # feat/thr/left/right/value/depth dict
        self.lin_coef = lin_coef      # (G, D+1)
        self.dnn = dnn                # (params, mu, sd, ys_f32)
        self.devices = devices
        self.dev_id = {d: i for i, d in enumerate(devices)}
        self.scalers = scalers        # kind -> (coef (n_dev, k), lo, hi)
        self.backend = backend
        self.forest_launches = 0
        self.mlp_applies = 0
        self._forest_stack = None     # device layout, on first Pallas use
        self._forest_stack_lock = threading.Lock()

    @property
    def n_groups(self) -> int:
        return len(self.pairs)

    @property
    def forest_backend(self) -> str:
        """The forest backend this bank's waves run (``"auto"``
        resolved against this process's JAX backend)."""
        if self.backend != "auto":
            return self.backend
        from repro.kernels import forest_eval
        return forest_eval._auto_backend()

    def supports(self, pairs: Iterable[Tuple[str, str]]) -> bool:
        return all(p in self.gid for p in pairs)

    def device_forest_stack(self) -> Optional[tuple]:
        """The forest stack in the Pallas kernel's layout, on the device:
        placed once per bank, on the first call, and passed to every
        launch. None where the forest backend is not Pallas."""
        if self.forest is None or self.forest_backend != "pallas":
            return None
        with self._forest_stack_lock:
            if self._forest_stack is None:
                from repro.kernels import forest_eval
                f = self.forest
                self._forest_stack = forest_eval.device_forest_stack(
                    f["feat"], f["thr"], f["left"], f["right"], f["value"])
            return self._forest_stack

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def build(cls, profet, backend: str = "auto") -> "ModelBank":
        """Pack every fitted pair of ``profet`` into the stacked tensors.
        Raises :class:`BankUnsupportedError` when any ensemble holds a
        member the bank cannot stack (reference models, missing fits)."""
        pairs = sorted(profet.cross)
        if not pairs:
            raise BankUnsupportedError("no trained (anchor, target) pairs")
        members = None
        for pair in pairs:
            ens = profet.cross[pair]
            if members is None:
                members = tuple(ens.members)
            elif tuple(ens.members) != members:
                raise BankUnsupportedError(
                    f"heterogeneous member sets across pairs: "
                    f"{members} vs {tuple(ens.members)} ({pair})")
        known = {"linear", "forest", "dnn"}
        if not set(members) <= known:
            raise BankUnsupportedError(
                f"unstackable members {set(members) - known}")

        forest = lin_coef = dnn = None
        n_features = -1
        if "linear" in members:
            coefs = []
            for pair in pairs:
                lin = profet.cross[pair].models["linear"]
                if not isinstance(lin, LinearRegressor) or lin.coef_ is None:
                    raise BankUnsupportedError(
                        f"linear member of {pair} is "
                        f"{type(lin).__name__}, not a fitted "
                        "LinearRegressor")
                coefs.append(np.asarray(lin.coef_, np.float64))
            if len({c.shape for c in coefs}) != 1:
                raise BankUnsupportedError("linear coef shapes differ")
            lin_coef = np.stack(coefs)
            n_features = lin_coef.shape[1] - 1
        if "forest" in members:
            packed = []
            for pair in pairs:
                rf = profet.cross[pair].models["forest"]
                if not isinstance(rf, RandomForestRegressor) \
                        or rf.forest_ is None:
                    raise BankUnsupportedError(
                        f"forest member of {pair} is "
                        f"{type(rf).__name__}, not a fitted packed forest")
                packed.append(rf.forest_)
            T = packed[0].n_trees
            if any(f.n_trees != T for f in packed):
                raise BankUnsupportedError("tree counts differ across pairs")
            G = len(packed)
            n_max = max(f.feat.shape[1] for f in packed)
            feat = np.full((G, T, n_max), -1, np.int32)
            thr = np.zeros((G, T, n_max), np.float64)
            left = np.zeros((G, T, n_max), np.int32)
            right = np.zeros((G, T, n_max), np.int32)
            value = np.zeros((G, T, n_max), np.float64)
            for g, f in enumerate(packed):
                n = f.feat.shape[1]
                feat[g, :, :n] = f.feat
                thr[g, :, :n] = f.thr
                left[g, :, :n] = f.left
                right[g, :, :n] = f.right
                value[g, :, :n] = f.value
            forest = {"feat": feat, "thr": thr, "left": left,
                      "right": right, "value": value,
                      "depth": np.array([f.depth for f in packed],
                                        np.int64)}
        if "dnn" in members:
            heads = []
            for pair in pairs:
                head = profet.cross[pair].models["dnn"]
                if not isinstance(head, DNNRegressor) or head.params is None:
                    raise BankUnsupportedError(
                        f"dnn member of {pair} is {type(head).__name__}, "
                        "not a fitted DNNRegressor")
                heads.append(head)
            try:
                dnn = stack_dnn_heads(heads)
            except Exception as e:
                raise BankUnsupportedError(
                    f"dnn heads do not stack: {e!r}") from e
            if n_features < 0:
                n_features = dnn[1].shape[1]

        devices = tuple(sorted({d for pair in pairs for d in pair}))
        try:
            scalers = profet.scaler_stack(devices)
        except KeyError as e:
            raise BankUnsupportedError(
                f"missing phase-2 scaler for device {e}") from e
        return cls(pairs=pairs, members=members, n_features=n_features,
                   forest=forest, lin_coef=lin_coef, dnn=dnn,
                   devices=devices, scalers=scalers, backend=backend)

    # ------------------------------------------------------------------
    # group-axis sharding
    # ------------------------------------------------------------------
    def split(self, groups: Sequence[Sequence[Tuple[str, str]]]
              ) -> Tuple[Optional["ModelBank"], ...]:
        """Slice the bank's group axis into sub-banks, one per entry of
        ``groups`` (a partition of ``self.pairs``, e.g. from
        ``planner.partition_pairs``). Each sub-bank carries only its
        pairs' stacked tensors but the FULL device set and phase-2
        scalers — phase-2 is per-device, not per-pair, so every shard
        can interpolate any row it predicted. Slicing is pure gathering
        (``arr[idx]``), so a sub-bank's answers are bit-identical to the
        full bank's for the same rows. Empty groups map to ``None``;
        pairs the bank never trained raise ``BankUnsupportedError``."""
        banks = []
        for part in groups:
            part = tuple(part)
            if not part:
                banks.append(None)
                continue
            missing = [p for p in part if p not in self.gid]
            if missing:
                raise BankUnsupportedError(
                    f"cannot split: pairs not in bank: {missing}")
            idx = np.array([self.gid[p] for p in part], np.int64)
            forest = None
            if self.forest is not None:
                forest = {k: v[idx] for k, v in self.forest.items()}
            lin_coef = None if self.lin_coef is None else self.lin_coef[idx]
            dnn = None
            if self.dnn is not None:
                params, mu, sd, ys = self.dnn
                dnn = (_tree_index(params, idx), mu[idx], sd[idx], ys[idx])
            banks.append(ModelBank(
                pairs=part, members=self.members,
                n_features=self.n_features, forest=forest,
                lin_coef=lin_coef, dnn=dnn, devices=self.devices,
                scalers=self.scalers, backend=self.backend))
        return tuple(banks)

    # ------------------------------------------------------------------
    # wire form (remote shard distribution)
    # ------------------------------------------------------------------
    def to_payload(self) -> dict:
        """The bank as one self-contained wire value: every stacked tensor
        an inline contiguous numpy array (no shared-memory names, no jax
        leaves), ready for the shard worker codecs
        (``repro.serve.frames``). Shard workers are CPU-only, so the
        payload always names the numpy forest backend, whatever the
        parent runs."""
        return {
            "pairs": self.pairs,
            "members": self.members,
            "n_features": self.n_features,
            "devices": self.devices,
            "scalers": {k: tuple(np.ascontiguousarray(a) for a in v)
                        for k, v in self.scalers.items()},
            "backend": "numpy",
            "forest": (None if self.forest is None else
                       {k: np.ascontiguousarray(v)
                        for k, v in self.forest.items()}),
            "lin_coef": (None if self.lin_coef is None
                         else np.ascontiguousarray(self.lin_coef)),
            "dnn": (None if self.dnn is None
                    else (_np_tree(self.dnn[0]), np.asarray(self.dnn[1]),
                          np.asarray(self.dnn[2]),
                          np.asarray(self.dnn[3]))),
        }

    @classmethod
    def from_payload(cls, d: dict) -> "ModelBank":
        """Rebuild a bank around the decoded wire value. The codec hands
        arrays back as zero-copy read-only views over the received frame
        body (``np.frombuffer``) — the remote-host analogue of a
        shared-memory attach; execution only ever reads them."""
        pairs = tuple(tuple(p) for p in d["pairs"])
        return cls(pairs=pairs, members=tuple(d["members"]),
                   n_features=int(d["n_features"]), forest=d["forest"],
                   lin_coef=d["lin_coef"],
                   dnn=None if d["dnn"] is None else tuple(d["dnn"]),
                   devices=tuple(d["devices"]),
                   scalers={k: tuple(v)
                            for k, v in d["scalers"].items()},
                   backend=d["backend"])

    # ------------------------------------------------------------------
    # stacked execution
    # ------------------------------------------------------------------
    def execute(self, X: np.ndarray, gids: np.ndarray) -> np.ndarray:
        """Median-ensemble prediction for every row of ``X``, row ``i``
        answered by group ``gids[i]`` — one grouped forest launch plus one
        stacked MLP apply for the whole wave, whatever mix of pairs it
        carries."""
        X = np.asarray(X, np.float64)
        gids = np.asarray(gids, np.int64)
        preds = []
        if "linear" in self.members:
            design = LinearRegressor._design(X)
            preds.append(LinearRegressor.apply(design, self.lin_coef[gids]))
        if "forest" in self.members:
            from repro.kernels import forest_eval
            f = self.forest
            with obs.span("bank.forest", rows=len(gids)):
                preds.append(forest_eval.predict_grouped(
                    X, gids, f["feat"], f["thr"], f["left"], f["right"],
                    f["value"], depth=f["depth"], backend=self.backend,
                    stack=self.device_forest_stack()))
            self.forest_launches += 1
        if "dnn" in self.members:
            preds.append(self._dnn_member(X, gids))
        return np.median(np.stack(preds), axis=0)

    def _dnn_member(self, X: np.ndarray, gids: np.ndarray) -> np.ndarray:
        """One stacked MLP apply: rows scattered into a dense bucketed
        ``(groups, rows, features)`` block, heads gathered on device."""
        import jax.numpy as jnp
        with obs.span("bank.mlp", rows=len(gids)):
            params, mu, sd, ys = self.dnn
            uniq, local = np.unique(gids, return_inverse=True)
            counts = np.bincount(local)
            g_pad = bucket(len(uniq))
            r_pad = bucket(int(counts.max()), DNNRegressor.PREDICT_BUCKET_MIN)
            # per-row slot inside its group's row block
            order = np.argsort(local, kind="stable")
            starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
            slot = np.empty(len(gids), np.int64)
            slot[order] = np.arange(len(gids)) - starts[local[order]]
            # normalized exactly like DNNRegressor.predict: float64 z-score,
            # then one float32 cast
            Xn = ((X - mu[gids]) / sd[gids]).astype(np.float32)
            block = np.zeros((g_pad, r_pad, X.shape[1]), np.float32)
            block[local, slot] = Xn
            gidx = np.zeros(g_pad, np.int32)
            gidx[:len(uniq)] = uniq
            # the params live on the device; the index and the block cross
            obs.count("bank.h2d_bytes", gidx.nbytes + block.nbytes)
            out = np.asarray(_mlp_apply_multi()(params, jnp.asarray(gidx),
                                                jnp.asarray(block)))
            self.mlp_applies += 1
            return out[local, slot] * ys[gids]

    def interpolate(self, kinds: Sequence[str], dev_ids: np.ndarray,
                    values: np.ndarray, t_min: np.ndarray,
                    t_max: np.ndarray) -> np.ndarray:
        """Vectorized phase-2 over heterogeneous rows: one Horner pass,
        each row using its (device, knob-kind) coefficient row — bitwise
        equal to per-group ``PolyScaler.predict``."""
        with obs.span("bank.phase2", rows=len(values)):
            n = len(values)
            coef = np.empty((n, self.scalers["batch"][0].shape[1]))
            lo = np.empty(n)
            hi = np.empty(n)
            for kind in ("batch", "pixel"):
                sel = np.array([k == kind for k in kinds])
                if not sel.any():
                    continue
                c, l, h = self.scalers[kind]
                coef[sel] = c[dev_ids[sel]]
                lo[sel] = l[dev_ids[sel]]
                hi[sel] = h[dev_ids[sel]]
            x = (np.asarray(values, np.float64) - lo) / (hi - lo)
            r = np.zeros(n)
            for j in range(coef.shape[1]):
                r = r * x + coef[:, j]
            return r * (np.asarray(t_max) - np.asarray(t_min)) + \
                np.asarray(t_min)

    # ------------------------------------------------------------------
    # warm-up
    # ------------------------------------------------------------------
    def warmup(self, max_rows: int = 64) -> float:
        """Pre-compile every MLP bucket shape a wave up to ``max_rows``
        rows can produce (and, when the forest backend is Pallas, place the
        device forest stack and compile the grouped launch over it), so the
        first live wave after a swap pays zero compiles. Returns the wall
        seconds spent."""
        t0 = time.perf_counter()
        if "dnn" in self.members and self.n_features > 0:
            import jax.numpy as jnp
            params = self.dnn[0]
            apply = _mlp_apply_multi()
            g_caps, r_caps = [], []
            g = 1
            while True:
                g_caps.append(min(g, bucket(self.n_groups)))
                if g >= bucket(self.n_groups):
                    break
                g *= 2
            r = DNNRegressor.PREDICT_BUCKET_MIN
            while True:
                r_caps.append(r)
                if r >= bucket(max(max_rows, 1),
                                DNNRegressor.PREDICT_BUCKET_MIN):
                    break
                r *= 2
            for g_pad in sorted(set(g_caps)):
                gidx = jnp.zeros(g_pad, jnp.int32)
                for r_pad in r_caps:
                    block = jnp.zeros((g_pad, r_pad, self.n_features),
                                      jnp.float32)
                    apply(params, gidx, block).block_until_ready()
        if "forest" in self.members and self.n_features > 0 \
                and self.forest_backend == "pallas":
            from repro.kernels import forest_eval
            forest_eval.warm_grouped(self.device_forest_stack(),
                                     n_features=self.n_features,
                                     max_rows=max(max_rows, 1))
        return time.perf_counter() - t0
