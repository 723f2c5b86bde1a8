"""Tests of the readers of the program's own spans and counters, on the CPU.

Each reader takes the window's delta of the ``trace`` table that /statsz
carries (``repro.obs.snapshot()``). They are tested on hand-made
``statsz_before/after`` bodies and on a window served end to end on the
CPU from a tiny fit.
"""
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import harness, spec  # noqa: E402

PROGRAM_READERS = ("queue_ms.point", "resolve_ms.point", "decode_ms.point",
                   "encode_ms.point", "planner_ms.advise",
                   "forest_ms.advise", "mlp_ms.advise", "h2d_mb.advise",
                   "forest_fill.advise")


def _ctx(records, **kw):
    base = dict(records=records, w0=0.0, w1=10.0, wait_until=70.0)
    base.update(kw)
    return harness.Ctx(**base)


def _trace(spans, counters):
    return {"spans": {k: {"n": n, "total_s": t, "self_s": s}
                      for k, (n, t, s) in spans.items()},
            "counters": counters}


def test_program_span_and_counter_readers():
    before = _trace({"latency_service.queue_wait": (10, 0.05, 0.05),
                     "transport.resolve": (10, 0.02, 0.02),
                     "transport.decode": (10, 0.001, 0.001),
                     "transport.encode": (12, 0.002, 0.002),
                     "latency_service.wave": (4, 0.4, 0.01),
                     "planner.plan": (20, 0.03, 0.02),
                     "bank.forest": (4, 0.2, 0.2),
                     "bank.mlp": (4, 0.08, 0.08)},
                    {"bank.h2d_bytes": 1_000_000, "bank.forest_rows": 100,
                     "bank.forest_slots": 1024})
    after = _trace({"latency_service.queue_wait": (30, 0.25, 0.25),
                    "transport.resolve": (30, 0.12, 0.12),
                    "transport.decode": (30, 0.005, 0.005),
                    "transport.encode": (32, 0.004, 0.004),
                    "latency_service.wave": (9, 0.9, 0.02),
                    "planner.plan": (60, 0.08, 0.045),
                    "bank.forest": (9, 0.7, 0.7),
                    "bank.mlp": (9, 0.18, 0.18)},
                   {"bank.h2d_bytes": 31_000_000, "bank.forest_rows": 420,
                    "bank.forest_slots": 9216})
    ctx = _ctx([], statsz_before={"waves": 4, "trace": before},
               statsz_after={"waves": 9, "trace": after})
    want = {"queue_ms.point": 10.0, "resolve_ms.point": 5.0,
            "decode_ms.point": 0.2, "encode_ms.point": 0.1,
            # per wave: self time of the planner, whole member spans
            "planner_ms.advise": 5.0, "forest_ms.advise": 100.0,
            "mlp_ms.advise": 20.0, "h2d_mb.advise": 6.0,
            "forest_fill.advise": 100.0 * 320 / 8192}
    for name in PROGRAM_READERS:
        assert spec.reader(name)(ctx) == pytest.approx(want[name]), name
    # a program without the table (the parent of this metric), a window in
    # which nothing moved, or a member that never ran reads nothing
    bare = _ctx([], statsz_before={"waves": 4}, statsz_after={"waves": 9})
    still = _ctx([], statsz_before={"trace": before},
                 statsz_after={"trace": before})
    del after["spans"]["bank.mlp"]
    del after["counters"]["bank.forest_slots"]
    no_member = _ctx([], statsz_before={"trace": before},
                     statsz_after={"trace": after})
    for name in PROGRAM_READERS:
        assert spec.reader(name)(bare) is None, name
        assert spec.reader(name)(still) is None, name
    assert spec.reader("mlp_ms.advise")(no_member) is None
    assert spec.reader("forest_fill.advise")(no_member) is None


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    from chipbench import fitcache
    cfg = json.loads((HERE / "configs" / "paper-4dev.json").read_text())
    cfg.update(name="tiny", devices=["T4", "V100", "K80"],
               models=["LeNet5", "AlexNet", "ResNet18"], n_trees=10,
               dnn_epochs=5)
    cfg["service"] = {**cfg["service"], "max_wave": 16}
    cache = tmp_path_factory.mktemp("program_readers_cache")
    fitcache.load_or_fit(cfg, json.dumps(cfg).encode(), cache / "fit")
    s = harness.Session(
        "paper-4dev.point-zipf", cfg=cfg, config_bytes=json.dumps(
            cfg).encode(), cache_dir=cache, require_chip=False,
        jax_cache=False, log=lambda line: None,
        traffic_overrides={"rate_per_s": 150.0, "warm_s": 0.3,
                           "drain_s": 10.0})
    yield s
    s.close()


def test_program_readers_on_a_window_served_on_the_cpu(session):
    """The program's own totals reach the readers through the window's
    /statsz bodies. On the CPU the forest runs its numpy traversal, so
    the kernel's fill has nothing to read."""
    m = session.window(24, 1.0)
    ctx = _ctx(m["records"], statsz_before=m["out"]["statsz_before"],
               statsz_after=m["out"]["statsz_after"])
    got = {name: spec.reader(name)(ctx) for name in PROGRAM_READERS}
    assert got.pop("forest_fill.advise") is None
    assert all(v is not None and v > 0 for v in got.values()), got
