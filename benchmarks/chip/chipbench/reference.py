"""The plain reference: PROFET's answers recomputed from the fitted
parameters, with none of the program's code.

It reads only the arrays and JSON that ``fitcache.export`` wrote after the
fit (per-pair linear coefficients, packed forests, DNN weights and z-score
statistics, phase-2 polynomial coefficients, the op-name clustering and
the dataset), and re-implements the arithmetic the configuration states:

- features: the profile's op latencies summed per op-name cluster;
- routing: ``target == anchor`` is answered from the dataset (measured);
  otherwise a client profile or a measured case routes to cross, and an
  off-grid knob to two-phase over the knob's grid min and max configs;
- phase 1: the median of the linear member, the forest member (each tree
  routed ``x[feat] <= thr`` from node 0 to a leaf, leaf values averaged)
  and the DNN member (z-scored inputs, ReLU MLP, times the target scale);
- phase 2: a Horner pass of the target's polynomial over the min-max
  normalized knob, denormalized between the two phase-1 answers;
- ``/advise``: the anchor's row first, then every other device by name.

Arithmetic runs in float64. Where the configuration states a narrower
type (``precision`` in its file: the forest compares and leaves, and the
DNN, in float32), values are rounded to that type at the point it names.
:meth:`Reference.control` lowers every stated type one step (float64 to
float32, float32 to bfloat16): the control that ``correct`` must refuse.
"""
from __future__ import annotations

import json
from typing import Dict, List, Sequence

import ml_dtypes
import numpy as np

LOWER = {"float64": "float32", "float32": "bfloat16"}
_DTYPE = {"float64": np.float64, "float32": np.float32,
          "bfloat16": ml_dtypes.bfloat16}


def rounder(name: str):
    """Round float64 values to ``name`` and back to float64."""
    dtype = _DTYPE[name]
    if dtype is np.float64:
        return lambda a: np.asarray(a, np.float64)
    return lambda a: np.asarray(a, np.float64).astype(dtype).astype(
        np.float64)


class Reference:
    def __init__(self, params: Dict[str, np.ndarray], data: dict,
                 precision: Dict[str, str], members: Sequence[str]):
        self.p = params
        self.data = data
        self.precision = dict(precision)
        self.members = tuple(members)
        self.q = {k: rounder(v) for k, v in precision.items()}
        self.devices = list(data["devices"])
        self.dev = {d: i for i, d in enumerate(self.devices)}
        self.case = {tuple(c): i for i, c in enumerate(data["cases"])}
        self.pair = {tuple(p): g for g, p in enumerate(data["pairs"])}
        self.n_features = data["n_features"]
        self._dataset_rows = {}

    @classmethod
    def load(cls, ref_path, data_path, cfg: dict) -> "Reference":
        with np.load(ref_path) as z:
            params = {k: z[k] for k in z.files}
        with open(data_path) as f:
            data = json.load(f)
        return cls(params, data, cfg["precision"], cfg["members"])

    def control(self) -> "Reference":
        """The same reference one precision step lower throughout."""
        return Reference(self.p, self.data,
                         {k: LOWER[v] for k, v in self.precision.items()},
                         self.members)

    # ------------------------------------------------------------------
    def features(self, items) -> np.ndarray:
        """Cluster sums of one profile, given as ``(op, ms)`` pairs or a
        mapping, summed in the profile's own order."""
        out = np.zeros(self.n_features)
        cluster_of = self.data["cluster_of"]
        for op, ms in (items.items() if isinstance(items, dict) else items):
            if op not in cluster_of:
                raise KeyError(f"op {op!r} is in no cluster")
            out[cluster_of[op]] += ms
        return self.q["features"](out)

    def dataset_row(self, device: str, case: tuple) -> np.ndarray:
        key = (device, case)
        if key not in self._dataset_rows:
            items = self.data["profiles"][device][self.case[case]]
            self._dataset_rows[key] = self.features(items)
        return self._dataset_rows[key]

    def scale_ms(self, device: str) -> float:
        """The device's mean measured latency over the dataset: the scale
        of its answers."""
        return float(np.mean(self.data["latency_ms"][device]))

    def measured_ms(self, device: str, case: tuple) -> float:
        return float(self.data["latency_ms"][device][self.case[case]])

    # ------------------------------------------------------------------
    def _linear(self, X, gids):
        q = self.q["linear"]
        design = np.concatenate([X, np.ones((len(X), 1))], axis=1)
        return q((q(design) * q(self.p["lin_coef"][gids])).sum(axis=1))

    def _forest(self, X, gids):
        qc, ql = self.q["forest_compare"], self.q["forest_leaf"]
        p = self.p
        out = np.empty(len(X))
        for g in np.unique(gids):
            rows = np.flatnonzero(gids == g)
            x = qc(X[rows])                                  # (r, D)
            feat, thr = p["feat"][g], qc(p["thr"][g])        # (T, N)
            left, right = p["left"][g], p["right"][g]
            T = feat.shape[0]
            tt = np.arange(T)[:, None]
            cols = np.arange(len(rows))[None, :]
            nid = np.zeros((T, len(rows)), np.int64)
            while True:
                f = feat[tt, nid]
                live = f >= 0
                if not live.any():
                    break
                xv = x[cols, np.maximum(f, 0)]
                go_left = xv <= thr[tt, nid]
                nid = np.where(live, np.where(go_left, left[tt, nid],
                                              right[tt, nid]), nid)
            out[rows] = ql(p["value"][g][tt, nid]).mean(axis=0)
        return out

    def _dnn(self, X, gids):
        q = self.q["dnn"]
        p = self.p
        n_layers = sum(1 for k in p if k.startswith("w"))
        out = np.empty(len(X))
        for g in np.unique(gids):
            rows = np.flatnonzero(gids == g)
            h = q((X[rows] - p["mu"][g]) / p["sd"][g])
            for i in range(n_layers):
                h = q(h) @ q(p[f"w{i}"][g]) + q(p[f"b{i}"][g])
                if i < n_layers - 1:
                    h = np.maximum(h, 0.0)
                h = q(h)
            out[rows] = q(h[:, 0] * q(p["ys"][g]))
        return out

    def phase1(self, X: np.ndarray, gids: np.ndarray) -> np.ndarray:
        """Median ensemble of every row ``X[i]`` under pair ``gids[i]``."""
        X = np.asarray(X, np.float64)
        gids = np.asarray(gids, np.int64)
        member = {"linear": self._linear, "forest": self._forest,
                  "dnn": self._dnn}
        preds = np.stack([member[m](X, gids) for m in self.members])
        return self.q["median_phase2"](np.median(preds, axis=0))

    def phase2(self, target: str, knob: str, value, t_min, t_max):
        q = self.q["median_phase2"]
        i = self.dev[target]
        coef = q(self.p[f"{knob}_coef"][i])
        lo, hi = self.p[f"{knob}_lo"][i], self.p[f"{knob}_hi"][i]
        x = q((q(value) - lo) / (hi - lo))
        r = np.zeros_like(x)
        for c in coef:
            r = q(r * x + c)
        return q(r * q(q(t_max) - q(t_min)) + q(t_min))

    # ------------------------------------------------------------------
    def route(self, body: dict) -> dict:
        """The plan of one ``/predict`` body: mode, anchor, target, case
        and the phase-1 rows it needs as ``(pair, features)``."""
        anchor, target = body["anchor"], body["target"]
        w = body["workload"]
        case = (w["model"], int(w["batch"]), int(w["pix"]))
        profile = body.get("profile")
        mode = body.get("mode", "auto")
        knob = body.get("knob", "batch")
        if anchor not in self.dev or target not in self.dev:
            raise KeyError(f"unknown device in {anchor!r} -> {target!r}")
        plan = {"anchor": anchor, "target": target, "case": case,
                "scale_ms": self.scale_ms(target)}
        if target == anchor:
            if case not in self.case:
                raise KeyError(f"{case} was never measured on {anchor}")
            return {**plan, "mode": "measured",
                    "latency_ms": self.measured_ms(anchor, case)}
        if mode == "auto":
            mode = ("cross" if profile is not None or case in self.case
                    else "two_phase")
        pair = self.pair[(anchor, target)]
        if mode == "cross":
            x = (self.features(profile) if profile is not None
                 else self.dataset_row(anchor, case))
            return {**plan, "mode": "cross", "rows": [(pair, x)]}
        model, batch, pix = case
        if knob == "batch":
            lo = (model, min(self.data["batches"]), pix)
            hi = (model, max(self.data["batches"]), pix)
            value = batch
        else:
            lo = (model, batch, min(self.data["pixels"]))
            hi = (model, batch, max(self.data["pixels"]))
            value = pix
        return {**plan, "mode": "two_phase", "knob": knob, "value": value,
                "rows": [(pair, self.dataset_row(anchor, lo)),
                         (pair, self.dataset_row(anchor, hi))]}

    def answer(self, plans: List[dict]) -> List[dict]:
        """Fill ``latency_ms`` of every plan from one vectorized phase-1
        pass over all of their rows."""
        rows = [r for p in plans for r in p.get("rows", ())]
        if rows:
            y = self.phase1(np.stack([x for _, x in rows]),
                            np.array([g for g, _ in rows]))
        k = 0
        for p in plans:
            n = len(p.get("rows", ()))
            if p["mode"] == "cross":
                p["latency_ms"] = float(y[k])
            elif p["mode"] == "two_phase":
                p["latency_ms"] = float(self.phase2(
                    p["target"], p["knob"], np.float64(p["value"]),
                    y[k], y[k + 1]))
            k += n
        return plans

    def predict(self, bodies: Sequence[dict]) -> List[dict]:
        return self.answer([self.route(b) for b in bodies])

    def advise_order(self, anchor: str) -> List[str]:
        return [anchor] + sorted(d for d in self.devices if d != anchor)

    def advise(self, bodies: Sequence[dict]) -> List[List[dict]]:
        """The rows of every ``/advise`` body, in the order served."""
        plans, spans = [], []
        for b in bodies:
            order = self.advise_order(b["anchor"])
            spans.append((len(plans), len(order)))
            plans.extend(self.route({"anchor": b["anchor"], "target": t,
                                     "workload": b["workload"],
                                     "profile": b.get("profile")})
                         for t in order)
        self.answer(plans)
        return [plans[s:s + n] for s, n in spans]
