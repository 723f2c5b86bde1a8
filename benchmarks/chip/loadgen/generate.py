"""The one traffic generator: a traffic file's parameters and ``--seed``
in, the request bodies and their schedule out.

It reads only the dataset the fit cache exported (devices, cases, anchor
profiles), never the program. Parameters of a traffic file:

- ``endpoint``: ``predict`` (one ``/predict`` per request) or ``advise``
  (one ``/advise`` sweep per request: the anchor's row and a row for every
  other device);
- ``loop``: ``open`` (each request sent at its due time, whatever is in
  flight) or ``closed`` (``clients`` connections, each sending its next
  request the moment its last one returns);
- open loop: ``rate_per_s`` and ``arrivals`` — ``poisson`` (a fixed count,
  ``rate_per_s`` times the duration, at uniformly drawn times: a Poisson
  process held to its mean count, so every seed brings the same amount of
  work); ``connections`` to keep open;
- closed loop: ``clients`` and ``max_rate_per_s``, which sizes the pool of
  distinct requests to draw from;
- ``warm_s``: seconds of the same traffic before the window, as set-up;
- ``zipf_s``: popularity of (anchor, case) pairs, rank ``k`` drawn with
  weight ``k ** -zipf_s`` over a fixed ranking (``rank_seed``); 0 is
  uniform;
- ``mix`` (``predict``): shares of ``measured`` (target is the anchor),
  ``cross`` and ``two_phase`` (an off-grid knob from ``off_grid_batches``
  or ``off_grid_pixels``; where its grid min or max config is unmeasured
  the request falls back to cross without a profile);
- ``client_profile_frac``: share of cross requests (``predict``) or of
  sweeps (``advise``) that carry a client profile, the anchor profile of
  the case times ``1 + profile_noise_sd * N(0, 1)`` per op: never repeated,
  so never answered from the cache.

A cell's own file (``cells/<cell>.json``) may set any of these for that
cell, such as the rate at four fifths of its knee.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def _popularity(n: int, s: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
    return w / w.sum()


class _Draws:
    def __init__(self, traffic: dict, data: dict,
                 rng: np.random.Generator):
        self.t = traffic
        self.data = data
        self.rng = rng
        self.anchors = sorted(data["devices"])
        self.cases = [tuple(c) for c in data["cases"]]
        self.case_set = set(self.cases)
        items = [(a, i) for a in self.anchors
                 for i in range(len(self.cases))]
        order = np.random.default_rng(traffic.get("rank_seed", 0)
                                      ).permutation(len(items))
        self.items = [items[i] for i in order]
        self.cdf = np.cumsum(_popularity(len(items),
                                         traffic.get("zipf_s", 0.0)))

    def anchor_case(self):
        k = int(np.searchsorted(self.cdf, self.rng.random() * self.cdf[-1],
                                side="right"))
        a, i = self.items[min(k, len(self.items) - 1)]
        return a, i, self.cases[i]

    def profile(self, anchor: str, case_i: int) -> Dict[str, float]:
        base = self.data["profiles"][anchor][case_i]
        z = self.rng.standard_normal(len(base))
        sd = self.t.get("profile_noise_sd", 0.05)
        return {op: float(v * max(1.0 + sd * zi, 0.05))
                for (op, v), zi in zip(base, z)}

    def target(self, anchor: str) -> str:
        others = [d for d in self.anchors if d != anchor]
        return others[int(self.rng.integers(len(others)))]

    def predict(self) -> dict:
        anchor, ci, case = self.anchor_case()
        model, batch, pix = case
        w = {"model": model, "batch": batch, "pix": pix}
        mix = self.t["mix"]
        u = self.rng.random()
        if u < mix["measured"]:
            return {"anchor": anchor, "target": anchor, "workload": w}
        body = {"anchor": anchor, "target": self.target(anchor),
                "workload": w}
        if u < mix["measured"] + mix["cross"]:
            if self.rng.random() < self.t.get("client_profile_frac", 0.0):
                body["profile"] = self.profile(anchor, ci)
            return body
        if self.rng.random() < 0.5:
            knob = "batch"
            b = int(self.rng.choice(self.t["off_grid_batches"]))
            lo, hi = ((model, min(self.data["batches"]), pix),
                      (model, max(self.data["batches"]), pix))
            body["workload"] = {**w, "batch": b}
        else:
            knob = "pixel"
            p = int(self.rng.choice(self.t["off_grid_pixels"]))
            lo, hi = ((model, batch, min(self.data["pixels"])),
                      (model, batch, max(self.data["pixels"])))
            body["workload"] = {**w, "pix": p}
        if lo in self.case_set and hi in self.case_set:
            body["knob"] = knob
        else:
            body["workload"] = w        # cross on the measured case
        return body

    def advise(self) -> dict:
        anchor, ci, case = self.anchor_case()
        model, batch, pix = case
        body = {"anchor": anchor,
                "workload": {"model": model, "batch": batch, "pix": pix}}
        if self.rng.random() < self.t.get("client_profile_frac", 1.0):
            body["profile"] = self.profile(anchor, ci)
        return body


def _arrivals(traffic: dict, rng: np.random.Generator,
              duration: float) -> np.ndarray:
    n = int(round(traffic["rate_per_s"] * duration))
    kind = traffic.get("arrivals", "poisson")
    if kind == "poisson":
        return np.sort(rng.uniform(0.0, duration, n))
    raise ValueError(f"unknown arrivals {kind!r}")


def generate(traffic: dict, data: dict, seed: int,
             seconds: float) -> dict:
    """Request bodies and schedule for a warm phase and a window of
    ``seconds``. Open loop: ``warm``/``window`` bodies with due offsets
    from each phase's start. Closed loop: one ``pool`` the clients draw
    from in order, sized for ``max_rate_per_s`` over both phases."""
    rng = np.random.default_rng(int(seed))
    draws = _Draws(traffic, data, rng)
    make = getattr(draws, traffic["endpoint"])
    warm_s = float(traffic.get("warm_s", 0.0))
    out = {"endpoint": traffic["endpoint"], "loop": traffic["loop"],
           "warm_s": warm_s, "seconds": float(seconds),
           "drain_s": float(traffic.get("drain_s", 60.0))}
    if traffic["loop"] == "open":
        for phase, dur in (("warm", warm_s), ("window", float(seconds))):
            due = _arrivals(traffic, rng, dur)
            out[phase + "_due"] = due.tolist()
            out[phase] = [make() for _ in range(len(due))]
        out["connections"] = int(traffic.get("connections", 64))
    elif traffic["loop"] == "closed":
        n = int(math.ceil(traffic["max_rate_per_s"] * (warm_s + seconds)))
        out["pool"] = [make() for _ in range(n)]
        out["clients"] = int(traffic["clients"])
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return out


def bodies_in_order(gen: dict) -> List[dict]:
    """Every body, indexed as the driver indexes requests."""
    if gen["loop"] == "open":
        return gen["warm"] + gen["window"]
    return gen["pool"]
