"""repro.obs: span self time under nesting, records and counters adding
up, no update lost between threads, and spans inside a profiler session."""
import threading
import time

from repro import obs


def _moved(before, after, name):
    was = before["spans"].get(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
    now = after["spans"][name]
    return {k: now[k] - was[k] for k in ("n", "total_s", "self_s")}


def test_nested_spans_give_their_parent_its_self_time():
    before = obs.snapshot()
    with obs.span("test_obs.outer", wave=1):
        time.sleep(0.02)
        with obs.span("test_obs.inner"):
            time.sleep(0.03)
        with obs.span("test_obs.inner"):
            with obs.span("test_obs.leaf"):
                time.sleep(0.01)
    after = obs.snapshot()
    outer = _moved(before, after, "test_obs.outer")
    inner = _moved(before, after, "test_obs.inner")
    leaf = _moved(before, after, "test_obs.leaf")
    assert (outer["n"], inner["n"], leaf["n"]) == (1, 2, 1)
    assert outer["total_s"] >= 0.06
    # self time is the span less the spans directly nested in it
    assert abs(outer["self_s"] - (outer["total_s"] - inner["total_s"])) \
        < 1e-9
    assert abs(inner["self_s"] - (inner["total_s"] - leaf["total_s"])) \
        < 1e-9
    assert leaf["self_s"] == leaf["total_s"]
    assert outer["self_s"] >= 0.02
    assert inner["self_s"] >= 0.03


def test_records_and_counters_add_up():
    before = obs.snapshot()
    for s in (0.25, 0.5, 0.125):
        obs.record("test_obs.lag", s)
    obs.count("test_obs.bytes", 7)
    obs.count("test_obs.bytes", 35)
    after = obs.snapshot()
    lag = _moved(before, after, "test_obs.lag")
    assert lag == {"n": 3, "total_s": 0.875, "self_s": 0.875}
    assert after["counters"]["test_obs.bytes"] \
        - before["counters"].get("test_obs.bytes", 0) == 42


def test_threads_lose_no_update():
    """More threads than cores, switching often: every count, record and
    span lands in the table."""
    import os
    import sys
    before = obs.snapshot()
    n, k = 4_000, 2 * (os.cpu_count() or 1) + 2
    go = threading.Barrier(k)

    def work():
        go.wait()
        for _ in range(n):
            obs.count("test_obs.race", 1)
            obs.record("test_obs.race", 1.0)
            with obs.span("test_obs.race_span"):
                pass
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(k)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    after = obs.snapshot()
    assert after["counters"]["test_obs.race"] \
        - before["counters"].get("test_obs.race", 0) == k * n
    assert _moved(before, after, "test_obs.race") == \
        {"n": k * n, "total_s": 1.0 * k * n, "self_s": 1.0 * k * n}
    assert _moved(before, after, "test_obs.race_span")["n"] == k * n


def test_spans_inside_a_profiler_session(tmp_path):
    """With a session open the annotation is built and takes late ids;
    the table counts the span as it does without one."""
    import jax
    before = obs.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test_obs.traced", wave=3) as sp:
            sp.set(uid=11, n=2)
    finally:
        jax.profiler.stop_trace()
    assert _moved(before, obs.snapshot(), "test_obs.traced")["n"] == 1
    assert list(tmp_path.rglob("*.xplane.pb"))
