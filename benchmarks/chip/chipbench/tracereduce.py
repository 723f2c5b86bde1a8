"""From a profiler trace to device busy time, kernel time and idle gaps.

:func:`extract` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
what the metrics need, as plain lists: every operation on each device's
``XLA Ops`` line, and the host spans the harness put around the program's
layers (``spans.HOST_SPANS``) and around the window (``WINDOW``). The
other functions work on that extract alone, so a small recorded one
(``testdata/``) tests them without JAX.

All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "chipbench.window"
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
NO_SPAN = "no host span"

Interval = Tuple[float, float]


def extract(logdir: str, host_names: Iterable[str]) -> dict:
    """The device ops and named host spans of the newest trace under
    ``logdir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    names = set(host_names) | {WINDOW}
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name.startswith(OP_LINE):
                    device.setdefault(plane.name, []).extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name in names)
    return {"device": device, "host": host}


def window(ex: dict) -> Interval:
    spans = [(s, s + d) for n, s, d in ex["host"] if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(spans)}")
    return spans[0]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], win: Interval) -> List[Interval]:
    lo, hi = win
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy(ops: Sequence[list], win: Interval) -> List[Interval]:
    """Union of one device's operation intervals inside the window."""
    return merge(clip(((s, s + d) for _, s, d in ops), win))


def gaps(busy_iv: List[Interval], win: Interval) -> List[Interval]:
    out, t = [], win[0]
    for s, e in busy_iv:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < win[1]:
        out.append((t, win[1]))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``, both sorted and merged."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def attribute(idle: List[Interval], host: Sequence[list],
              priority: Sequence[str]) -> Dict[str, float]:
    """Split the idle time by what the host was doing: each instant goes
    to the first span name in ``priority`` active then, the rest to
    ``NO_SPAN``. Returns seconds per name."""
    left = merge(idle)
    out: Dict[str, float] = {}
    for name in priority:
        spans = merge((s, s + d) for n, s, d in host if n == name)
        part = intersect(left, spans)
        if part:
            out[name] = total(part) / 1e9
            left = subtract(left, part)
    if left:
        out[NO_SPAN] = total(left) / 1e9
    return out


def op_name(hlo: str) -> str:
    """The short name of a device operation: ``fusion.14`` for the trace's
    ``%fusion.14 = f32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_seconds(ops: Sequence[list], win: Interval,
               match: str = "") -> float:
    """Summed device time of the operations whose name contains ``match``,
    clipped to the window."""
    return total(clip(((s, s + d) for n, s, d in ops if match in n),
                      win)) / 1e9


def reduce(ex: dict, priority: Sequence[str], top: int = 10) -> dict:
    """Window, busy and idle time per chip (averaged), the top device
    operations by time, and idle time attributed to host spans (chip 0)."""
    win = window(ex)
    planes = sorted(ex["device"])
    if not planes:
        raise ValueError("the trace holds no device operations")
    per_chip = {p: busy(ex["device"][p], win) for p in planes}
    busy_s = sum(total(b) for b in per_chip.values()) / len(planes) / 1e9
    ops: Dict[str, float] = {}
    for p in planes:
        for n, s, d in ex["device"][p]:
            part = clip([(s, s + d)], win)
            if part:
                k = op_name(n)
                ops[k] = ops.get(k, 0.0) + total(part) / 1e9 / len(planes)
    idle = attribute(gaps(per_chip[planes[0]], win), ex["host"], priority)
    rank = lambda kv: -kv[1]            # noqa: E731
    return {"window_s": (win[1] - win[0]) / 1e9, "busy_s": busy_s,
            "device_ops": sorted(ops.items(), key=rank)[:top],
            "idle_gaps": sorted(idle.items(), key=rank)[:top]}
