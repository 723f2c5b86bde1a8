"""The comparison that decides ``correct``.

Three numbers, each held to the limit in ``limits/<cell>.json``:

- ``answer_gap``: the largest gap ``|served - reference|`` over every
  answer the window produced, relative to the larger of the reference's
  answer and the target device's mean measured latency (``scale_ms``).
  The floor keeps an answer near zero from turning the float32 DNN's
  rounding, which scales with the target's latencies, into a large
  relative gap;
- ``wrong_route``: answers whose mode, anchor, target or workload differ
  from the reference's routing, plus ``/advise`` responses whose rows come
  in another order or number (exact: limit 0);
- ``lost``: requests due in the window that got no answer at all, or an
  error other than the admission queue's typed 503 (exact: limit 0).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

NUMBERS = ("answer_gap", "wrong_route", "lost")
OVERLOADED = 503


def _gap(got: float, want: float, scale: float) -> float:
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), scale)


def _route_ok(row: dict, want: dict) -> bool:
    w = row.get("workload") or {}
    return (row.get("mode") == want["mode"]
            and row.get("anchor") == want["anchor"]
            and row.get("target") == want["target"]
            and (w.get("model"), w.get("batch"), w.get("pix"))
            == want["case"])


def compare(endpoint: str, served: Sequence[Optional[dict]],
            statuses: Sequence[Optional[int]],
            expected: Sequence) -> Dict[str, float]:
    """``served[i]`` is the decoded answer of request ``i`` (the
    ``result`` of ``/predict``, the ``rows`` of ``/advise``), ``None``
    without one; ``statuses[i]`` its HTTP status (``None``: no response);
    ``expected[i]`` the reference's plan (``/predict``) or row list
    (``/advise``)."""
    return _compare(endpoint, served, statuses, expected)[0]


def worst(endpoint: str, served, statuses, expected):
    """The served row and reference plan behind ``answer_gap``."""
    return _compare(endpoint, served, statuses, expected)[1]


def _compare(endpoint, served, statuses, expected):
    gap, wrong, lost, at = 0.0, 0, 0, None
    for got, status, want in zip(served, statuses, expected):
        if status != 200 or got is None:
            lost += status != OVERLOADED
            continue
        pairs = [(got, want)] if endpoint == "predict" else (
            list(zip(got, want)) if len(got) == len(want) else None)
        if pairs is None:
            wrong += 1
            continue
        for row, w in pairs:
            if not _route_ok(row, w):
                wrong += 1
                continue
            g = _gap(float(row["latency_ms"]), w["latency_ms"],
                     w["scale_ms"])
            if g > gap:
                gap, at = g, (row, w)
    return {"answer_gap": gap, "wrong_route": wrong, "lost": lost}, at


def as_served(endpoint: str, expected: Sequence) -> List:
    """Reference plans in the served form, so a stand-in for the program
    (the control) is compared exactly as the program is."""
    def row(p):
        model, batch, pix = p["case"]
        return {"mode": p["mode"], "anchor": p["anchor"],
                "target": p["target"], "latency_ms": p["latency_ms"],
                "workload": {"model": model, "batch": batch, "pix": pix}}
    if endpoint == "predict":
        return [row(p) for p in expected]
    return [[row(p) for p in rows] for rows in expected]


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def lines(numbers: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k}: {numbers[k]!r} (limit {limits[k]!r})"
            for k in NUMBERS]
