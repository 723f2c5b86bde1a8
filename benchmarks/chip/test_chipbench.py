"""Tests of the on-chip benchmark's own code, on the CPU.

The yardstick (trace reduction, metric arithmetic, load generation, the
reference and the comparison that decides ``correct``) is tested here
without a chip: on small recorded and hand-made traces, on a stub HTTP
server, and on a tiny fit served end to end on the CPU with the timed
path broken underneath.
"""
import http.server
import json
import os
import pathlib
import socketserver
import subprocess
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import checks, fitcache, harness, roofline  # noqa: E402
from chipbench import spans, spec  # noqa: E402
from chipbench import tracereduce as tr  # noqa: E402
from chipbench.reference import Reference  # noqa: E402
from loadgen import generate as loadgen  # noqa: E402

BENCH = spec.benchmark()
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "0123456789_.-")


def _tiny_config(**kw):
    cfg = json.loads((HERE / "configs" / "paper-4dev.json").read_text())
    cfg.update(name="tiny", devices=["T4", "V100", "K80"],
               models=["LeNet5", "AlexNet", "ResNet18"], n_trees=10,
               dnn_epochs=5)
    cfg["service"] = {**cfg["service"], "max_wave": 16}
    cfg.update(kw)
    return cfg


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------

def test_every_part_is_found_by_its_name():
    for c in BENCH["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert json.loads((spec.ROOT / c["file"]).read_text())["name"] \
            == c["name"]
    from chipbench import trainstep
    for w in BENCH["workloads"]:
        cfg = spec.load_json(spec.ROOT / spec.config_entry(
            BENCH, w["config"])["file"])
        train = spec.kind(cfg) == "train"
        assert spec.traffic(w["traffic"])["endpoint"] in (
            ("train",) if train else ("predict", "advise"))
        assert set(spec.cell_settings(w["name"])["limits"]) \
            == set(trainstep.NUMBERS if train else checks.NUMBERS)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert spec.end_to_end(BENCH, w["name"])
        assert spec.per_layer(BENCH, w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.reader(m["name"]))
    with pytest.raises(KeyError):
        spec.cell(BENCH, "no-such.cell")


def test_every_training_configuration_names_a_module_of_the_interface(
        tmp_path, monkeypatch):
    """A training configuration's ``"reference"`` names a file under the
    benchmark that provides all the harness asks of an architecture; a
    missing key, file or name is an error that names the configuration's
    file."""
    from chipbench import trainstep
    train = [c for c in BENCH["configs"] if spec.kind(
        spec.load_json(spec.ROOT / c["file"])) == "train"]
    assert train
    for c in train:
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert (spec.BENCH / cfg["reference"]).is_file()
        ref = trainstep.reference(cfg, c["file"])
        assert all(hasattr(ref, n) for n in trainstep.INTERFACE)
        assert set(ref.VOCAB_AXES) <= set(ref.PROGRAM_PATHS)
        assert trainstep.reference(cfg, c["file"]) is ref
    with pytest.raises(KeyError, match="granite-3-2b.json"):
        trainstep.reference({"kind": "train"}, "configs/granite-3-2b.json")
    monkeypatch.setattr(spec, "BENCH", tmp_path)
    with pytest.raises(FileNotFoundError, match="other.json"):
        trainstep.reference({"reference": "no_such.py"}, "other.json")
    (tmp_path / "half.py").write_text("def dims(cfg):\n    return cfg\n")
    with pytest.raises(AttributeError, match="half.json.*program_config"):
        trainstep.reference({"reference": "half.py"}, "half.json")


def test_per_layer_metrics_follow_the_end_to_end_metric_they_move():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in spec.end_to_end(BENCH, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in spec.per_layer(BENCH, w["name"]):
            assert m["moves"] in e2e
    # a metric without a list of cells goes to every cell reporting the
    # end-to-end metric it moves, those of later changes included
    bench = {**BENCH, "per_layer": BENCH["per_layer"] + [
        {"name": "x_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "transport", "moves": "p95_ms"}]}
    names = {m["name"] for m in spec.per_layer(bench,
                                               "paper-4dev.point-zipf")}
    assert "x_ms" in names
    assert "x_ms" not in {m["name"] for m in spec.per_layer(
        bench, "paper-4dev.advise-profiled")}


def test_benchmark_json_keeps_to_its_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert set(n) <= NAME_CHARS and len(n) <= 64
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(isinstance(x, str) and "\n" not in x for x in layers)


def test_unknown_device_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _hand_trace():
    # window 0..100 ns; device busy 10..30, 25..40 (overlap), 70..80
    return {"device": {"/device:TPU:0": [
                ["forest_grouped", 10, 20], ["fusion.1", 25, 15],
                ["forest_grouped", 70, 10], ["before", -50, 20]]},
            "host": [[tr.WINDOW, 0, 100],
                     ["ModelBank.execute", 0, 45],
                     ["forest_eval.predict_grouped", 5, 10],
                     ["LatencyService.run_once", 40, 40]]}


def test_reduction_on_a_hand_made_trace():
    ex = _hand_trace()
    red = tr.reduce(ex, ["forest_eval.predict_grouped",
                         "ModelBank.execute", "LatencyService.run_once"])
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)        # 10..40, 70..80
    assert dict(red["device_ops"])["forest_grouped"] == pytest.approx(30e-9)
    assert "before" not in dict(red["device_ops"])
    # idle: 0..10, 40..70, 80..100
    idle = dict(red["idle_gaps"])
    assert idle["forest_eval.predict_grouped"] == pytest.approx(5e-9)
    assert idle["ModelBank.execute"] == pytest.approx(10e-9)  # 0..5, 40..45
    assert idle["LatencyService.run_once"] == pytest.approx(25e-9)
    assert idle[tr.NO_SPAN] == pytest.approx(20e-9)
    assert sum(idle.values()) == pytest.approx(60e-9)
    assert tr.op_seconds(ex["device"]["/device:TPU:0"], tr.window(ex),
                         "forest_grouped") == pytest.approx(30e-9)


def test_interval_arithmetic():
    a = [(0, 10), (20, 30)]
    b = [(5, 25)]
    assert tr.intersect(a, b) == [(5, 10), (20, 25)]
    assert tr.subtract(a, b) == [(0, 5), (25, 30)]
    assert tr.merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert tr.gaps([(2, 3)], (0, 5)) == [(0, 2), (3, 5)]


def test_reduction_on_a_recorded_trace():
    """A 60 ms slice of a traced chip window (catalog-9dev.advise-
    profiled), reduced by the code and by a plain per-nanosecond-free
    recount here."""
    ex = json.loads((HERE / "testdata" / "trace_small.json").read_text())
    red = tr.reduce(ex, spans.HOST_SPANS)
    lo, hi = tr.window(ex)
    ops = ex["device"][sorted(ex["device"])[0]]
    # busy by a plain sweep over sorted clipped intervals
    ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d in ops
                 if s + d > lo and s < hi)
    busy, end = 0.0, lo
    for s, e in ivs:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert 0 < red["busy_s"] < red["window_s"]
    kern = sum(min(s + d, hi) - max(s, lo) for n, s, d in ops
               if "forest_grouped" in n and s + d > lo and s < hi)
    assert kern > 0
    assert tr.op_seconds(ops, (lo, hi), "forest_grouped") \
        == pytest.approx(kern / 1e9, rel=1e-12)
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)


# ---------------------------------------------------------------------------
# metric arithmetic
# ---------------------------------------------------------------------------

def _ctx(records, **kw):
    base = dict(records=records, w0=0.0, w1=10.0, wait_until=70.0)
    base.update(kw)
    return harness.Ctx(**base)


def test_p95_counts_failures_as_missing():
    p95 = spec.reader("p95_ms")
    ok = [{"due": 0.0, "finish": 0.010, "status": 200} for _ in range(95)]
    assert p95(_ctx(ok + [{"due": 0.0, "finish": 0.020, "status": 200}]
                    * 5)) == pytest.approx(10.0)
    # five of a hundred failed: they are the slowest, waited to the end
    failed = [{"due": 1.0, "finish": None, "status": None}] * 4 + \
        [{"due": 1.0, "finish": 1.001, "status": 503}]
    assert p95(_ctx(ok + failed)) == pytest.approx(10.0)
    failed.append({"due": 2.0, "finish": None, "status": None})
    assert p95(_ctx(ok[:-1] + failed)) == pytest.approx(68_000.0)


def test_rows_and_transport_and_counters():
    recs = [{"status": 200, "finish": 1.0, "sent": 0.5, "service_ms": 100.0,
             "body": [{}] * 9},
            {"status": 200, "finish": 11.0, "sent": 0.5, "service_ms": 1.0,
             "body": [{}] * 9},
            {"status": 503, "finish": 2.0, "sent": 0.5, "service_ms": None,
             "body": None}]
    assert spec.reader("rows_per_s")(_ctx(recs)) == pytest.approx(0.9)
    assert spec.reader("transport_ms.point")(_ctx(recs[:1])) \
        == pytest.approx(400.0)
    ctx = _ctx(recs, statsz_before={"requests": 10, "cache_hits": 2,
                                    "waves": 4, "wall_s": 1.0},
               statsz_after={"requests": 30, "cache_hits": 7, "waves": 9,
                             "wall_s": 1.5})
    assert spec.reader("cache_hit_share.point")(ctx) == pytest.approx(25.0)
    assert spec.reader("wave_ms.advise")(ctx) == pytest.approx(100.0)


def test_forest_roofline_bytes_and_ops_on_a_known_stack():
    # two groups of 3 trees; nodes per group 7+5+3=15 and 1+1+1=3
    assert roofline.forest_launch_bytes([15, 3], rows=4, n_features=33,
                                        n_trees=3) \
        == 20 * 18 + 4 * 4 * 33 + 4 * 4 * 3
    t, bound = roofline.least_time(ops=1e6, nbytes=819e9, peak_ops=197e12,
                                   peak_bytes=819e9)
    assert (t, bound) == (1.0, "bytes")
    assert roofline.least_time(197e12, 1.0, 197e12, 819e9) == (1.0, "ops")
    assert roofline.dnn_flops(33, [128, 64, 32, 16, 1]) == 2 * (
        33 * 128 + 128 * 64 + 64 * 32 + 32 * 16 + 16)
    assert roofline.linear_flops(33) == 68
    # the reader: one launch of 4 rows over both groups, depth 2 each
    ex = {"device": {"/device:TPU:0": [["forest_grouped", 0, 1000]]},
          "host": [[tr.WINDOW, 0, 10_000]]}
    ctx = _ctx([], trace=tr.reduce(ex, []), extract=ex,
               forest_launches=[(0.0, 1.0, 4, np.array([0, 1]), 3 * 2 * 4)],
               forest_nodes=np.array([15, 3]), ref_pairs=[("a", "b"),
                                                          ("b", "a")],
               bank_pairs=(("a", "b"), ("b", "a")), n_features=33,
               cfg={"n_trees": 3},
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    want = 100 * (20 * 18 + 4 * 4 * 33 + 4 * 4 * 3) / 819e9 / 1e-6
    assert spec.reader("forest_roofline.advise")(ctx) == pytest.approx(want)
    # the kernel ran, but no launch was recorded: the run fails
    ctx.forest_launches = []
    with pytest.raises(RuntimeError, match="no launch was recorded"):
        spec.reader("forest_roofline.advise")(ctx)


def test_span_records_read_the_arguments_by_name():
    class Kernels:
        @staticmethod
        def predict(X, gid, feat, *, depth, backend="auto"):
            return "out"
    got = []
    spans._sync(Kernels, "predict", "predict",
                lambda t0, t1, args: got.append((t1 >= t0, dict(args))))
    assert Kernels.predict("X", feat="F", gid="G", depth="D") == "out"
    assert got == [(True, {"X": "X", "gid": "G", "feat": "F",
                           "depth": "D"})]


# ---------------------------------------------------------------------------
# load generation
# ---------------------------------------------------------------------------

def _data():
    cases = [["LeNet5", b, p] for b in (16, 64, 256) for p in (32, 256)]
    return {"devices": ["A", "B", "C"], "batches": [16, 64, 256],
            "pixels": [32, 256], "cases": cases,
            "profiles": {d: [[["Conv2D", 1.0 + i], ["MatMul", 2.0]]
                             for i in range(len(cases))]
                         for d in "ABC"}}


def test_generator_is_seeded_and_keeps_the_work_fixed():
    t = dict(spec.traffic("point-zipf"), rate_per_s=200.0)
    a = loadgen.generate(t, _data(), 2**33 + 5, 2.0)
    b = loadgen.generate(t, _data(), 2**33 + 5, 2.0)
    c = loadgen.generate(t, _data(), 7, 2.0)
    assert a == b
    assert a["window"] != c["window"]
    assert len(a["window"]) == len(c["window"]) == 400
    due = a["window_due"]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 2.0
    kinds = [("measured" if r["anchor"] == r["target"] else
              "two_phase" if "knob" in r else "cross") for r in a["window"]]
    assert {"measured", "cross", "two_phase"} <= set(kinds)
    profiles = [json.dumps(r["profile"]) for r in a["window"]
                if "profile" in r]
    assert profiles and len(set(profiles)) == len(profiles)
    adv = loadgen.generate(dict(spec.traffic("advise-profiled"),
                                max_rate_per_s=10.0), _data(), 3, 1.0)
    assert len(adv["pool"]) == 30
    assert all("profile" in r for r in adv["pool"])


class _SlowHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay_s = 0.05

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(self.delay_s)
        self._send({"ok": True, "result": {}, "service_ms": 1.0})

    def do_GET(self):
        self._send({"ok": True, "stats": {"requests": 0}})

    def _send(self, payload):
        data = json.dumps(payload).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):
        pass


def test_open_loop_sends_on_time_and_times_from_the_due_time():
    """On one connection to a server that answers one request each 50 ms,
    the driver still sends each request at its due time (pipelined), so
    the queue the slow server builds shows in the latency from the due
    time, and the driver reports its own lateness."""
    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), _SlowHandler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        n = 8
        spec_ = {"host": "127.0.0.1", "port": srv.server_address[1],
                 "loop": "open", "warm_s": 0.0, "seconds": 0.5,
                 "drain_s": 5.0, "connections": 1, "warm_due": [],
                 "window_due": [0.01 * i for i in range(n)],
                 "requests": [["/predict", "{}"]] * n}
        p = subprocess.run([sys.executable,
                            str(HERE / "loadgen" / "driver.py")],
                           input=json.dumps(spec_) + "\nGO\n",
                           capture_output=True, text=True, timeout=60)
        lines = p.stdout.splitlines()
        assert lines[:2] == ["READY", "DONE"]
        out = json.loads(lines[2])
    finally:
        srv.shutdown()
        srv.server_close()
    recs = out["records"]
    assert len(recs) == n and all(r[4] == 200 for r in recs)
    late = [r[2] - r[1] for r in recs]           # sent - due
    assert max(late) < 0.03 and min(late) >= 0
    lat = [r[3] - r[1] for r in recs]            # finish - due
    # the last request waited behind seven others served one at a time
    assert lat[-1] > 4 * _SlowHandler.delay_s
    assert out["w0"] <= recs[0][1] and out["w1"] >= recs[-1][1]


def test_closed_loop_runs_on_from_the_warm_phase_into_the_window():
    """The closed loop is not drained before the window: the window opens
    with each client's request in flight, those requests are the window's
    first, and no request is sent after the window closes."""
    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), _SlowHandler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        spec_ = {"host": "127.0.0.1", "port": srv.server_address[1],
                 "loop": "closed", "clients": 2, "warm_s": 0.3,
                 "seconds": 0.5, "drain_s": 5.0,
                 "requests": [["/advise", "{}"]] * 200}
        p = subprocess.run([sys.executable,
                            str(HERE / "loadgen" / "driver.py")],
                           input=json.dumps(spec_) + "\nGO\n",
                           capture_output=True, text=True, timeout=60)
        lines = p.stdout.splitlines()
        assert lines[:3] == ["READY", "DONE"] + lines[2:3]
        out = json.loads(lines[2])
    finally:
        srv.shutdown()
        srv.server_close()
    recs = out["records"]
    assert all(r[4] == 200 for r in recs) and not out["exhausted"]
    keys = [r[0] for r in recs]
    assert keys == sorted(set(keys)) and keys[0] > 0   # after the warm phase
    carried = [r for r in recs if r[2] < out["w0"]]
    assert len(carried) == 2 and recs[:2] == carried
    assert keys[2:] == list(range(keys[1] + 1, keys[1] + len(keys) - 1))
    assert all(r[3] >= out["w0"] for r in carried)
    assert all(r[2] <= out["w1"] for r in recs)


# ---------------------------------------------------------------------------
# fit cache
# ---------------------------------------------------------------------------

def test_a_changed_source_byte_misses_the_cache(tmp_path):
    src = tmp_path / "repro"
    (src / "api").mkdir(parents=True)
    (src / "api" / "bank.py").write_text("x = 1\n")
    (src / "notes.txt").write_text("not code")
    key = fitcache.cache_key(b"{}", 0, fitcache.source_digest(src))
    (src / "notes.txt").write_text("still not code")
    assert fitcache.cache_key(b"{}", 0, fitcache.source_digest(src)) == key
    (src / "api" / "bank.py").write_text("x = 2\n")
    assert fitcache.cache_key(b"{}", 0, fitcache.source_digest(src)) != key
    assert fitcache.cache_key(b"{}", 1, fitcache.source_digest(src)) \
        != fitcache.cache_key(b"{}", 0, fitcache.source_digest(src))


# ---------------------------------------------------------------------------
# the reference and correct, on a tiny fit served on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    cfg = _tiny_config()
    cache = tmp_path_factory.mktemp("chipbench_cache")
    oracle, paths, hit, _ = fitcache.load_or_fit(
        cfg, json.dumps(cfg).encode(), cache / "fit")
    assert not hit
    return cfg, cache, oracle, Reference.load(paths["ref"], paths["data"],
                                              cfg)


def test_reference_matches_the_oracle(fitted):
    from repro import api
    cfg, _, oracle, ref = fitted
    t = dict(spec.traffic("point-zipf"), rate_per_s=300.0,
             client_profile_frac=0.5)
    bodies = loadgen.generate(t, ref.data, 11, 1.0)["window"]
    reqs = [api.PredictRequest(
        b["anchor"], b["target"], api.Workload(**b["workload"]),
        profile=b.get("profile"), knob=b.get("knob", "batch"))
        for b in bodies]
    got = oracle.predict_many(reqs).results
    want = ref.predict(bodies)
    assert {w["mode"] for w in want} == {"measured", "cross", "two_phase"}
    for g, w in zip(got, want):
        assert g.mode == w["mode"] and g.target == w["target"]
        assert g.latency_ms == pytest.approx(w["latency_ms"], rel=1e-5)
    adv = loadgen.generate(dict(spec.traffic("advise-profiled"),
                                max_rate_per_s=5.0), ref.data, 12, 1.0)
    for body, rows in zip(adv["pool"], ref.advise(adv["pool"])):
        served = oracle.advise(body["anchor"],
                               api.Workload(**body["workload"]),
                               profile=body["profile"])
        assert [r.target for r in served] == [r["target"] for r in rows]
        assert [r.mode for r in served] == [r["mode"] for r in rows]
        assert rows[0]["mode"] == "measured"
        np.testing.assert_allclose([r.latency_ms for r in served],
                                   [r["latency_ms"] for r in rows],
                                   rtol=1e-5)


def test_the_control_is_not_correct(fitted):
    """The reference one precision step lower (bfloat16 forest and DNN,
    float32 elsewhere), put in the program's place, fails the limits."""
    _, _, _, ref = fitted
    limits = spec.cell_settings("paper-4dev.point-zipf")["limits"]
    ctl = ref.control()
    for seed in (1, 2, 3):
        bodies = loadgen.generate(dict(spec.traffic("point-zipf"),
                                       rate_per_s=300.0), ref.data, seed,
                                  1.0)["window"]
        numbers = checks.compare("predict", checks.as_served(
            "predict", ctl.predict(bodies)), [200] * len(bodies),
            ref.predict(bodies))
        assert not checks.verdict(numbers, limits), numbers


@pytest.fixture(scope="module")
def session(fitted):
    cfg, cache, _, _ = fitted
    s = harness.Session(
        "paper-4dev.point-zipf", cfg=cfg, config_bytes=json.dumps(
            cfg).encode(), cache_dir=cache, require_chip=False,
        jax_cache=False, log=lambda line: None,
        traffic_overrides={"rate_per_s": 150.0, "warm_s": 0.3,
                           "drain_s": 10.0})
    yield s
    s.close()


def _altered(out, gids):
    out = out.copy()
    out[0] *= 1.1
    return out


def _half_left_out(out, gids):
    out = out.copy()
    h = (len(out) + 1) // 2
    out[h:] = out[:h].mean()
    return out


@pytest.mark.parametrize("fault", [None, _altered, _half_left_out],
                         ids=["sound", "answer_altered", "half_left_out"])
def test_correct_catches_a_broken_timed_path(session, monkeypatch, fault):
    """The rest of a run, with the bank's answers broken where they are
    produced: ``correct`` holds for the sound path and fails for each
    fault."""
    from repro.api.bank import ModelBank
    if fault is not None:
        orig = ModelBank.execute
        monkeypatch.setattr(ModelBank, "execute",
                            lambda self, X, gids: fault(orig(self, X, gids),
                                                        gids))
    # a seed of its own: the service's cache keeps earlier windows' answers
    m = session.window(31 + [None, _altered, _half_left_out].index(fault),
                       1.0)
    numbers = session.check(m)
    assert numbers["lost"] == 0 and numbers["wrong_route"] == 0
    assert checks.verdict(numbers, session.limits) is (fault is None), \
        numbers


def test_a_routing_fault_is_caught(session, monkeypatch):
    """An answer that comes from the wrong plan (every two-phase request
    served as cross on its measured neighbour) fails ``wrong_route``."""
    from repro.api import planner
    orig = planner.plan_request

    def cross_only(req, dataset, pairs):
        if req.target != req.anchor and req.profile is None \
                and req.workload.case not in dataset.measurements[req.anchor]:
            req = type(req)(req.anchor, req.target, req.workload,
                            profile=dataset.profile(req.anchor,
                                                    dataset.cases[0]))
        return orig(req, dataset, pairs)
    monkeypatch.setattr(planner, "plan_request", cross_only)
    numbers = session.check(session.window(22, 1.0))
    assert numbers["wrong_route"] > 0
    assert not checks.verdict(numbers, session.limits)


def test_a_whole_run_prints_a_result_line(fitted):
    cfg, cache, _, _ = fitted
    lines = []
    res = harness.run("paper-4dev.advise-profiled", 2**31 + 3, 1.0, False,
                      cfg=cfg, config_bytes=json.dumps(cfg).encode(),
                      cache_dir=cache, require_chip=False, jax_cache=False,
                      log=lines.append,
                      traffic_overrides={"warm_s": 0.3, "clients": 4,
                                         "max_rate_per_s": 3000.0,
                                         "drain_s": 10.0})
    assert res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "fit_cache", "checks"]
    assert res["fit_cache"] == "hit"
    assert set(res["metrics"]) == {"rows_per_s", "setup_s"}
    assert res["metrics"]["rows_per_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert lines[-3:] == checks.lines(
        {k: v["value"] for k, v in res["checks"].items()},
        {k: v["limit"] for k, v in res["checks"].items()})
    assert any(line.startswith("fit cache: hit") for line in lines)


def test_a_cold_run_prepares_the_other_configurations(tmp_path):
    """The run that fits also fits every other configuration whose cache
    misses and warms its bank up, so the next run of any cell is warm."""
    small = _tiny_config(name="small", devices=["T4", "V100"],
                         models=["LeNet5"], n_trees=3, dnn_epochs=2)
    other = dict(small, name="other", devices=["K80", "V100"])

    def session(cfg, others, lines):
        s = harness.Session(
            "paper-4dev.point-zipf", cfg=cfg,
            config_bytes=json.dumps(cfg).encode(), cache_dir=tmp_path,
            require_chip=False, jax_cache=False, log=lines.append,
            others=[(c, json.dumps(c).encode()) for c in others])
        s.close()
        return s
    first, second = [], []
    assert not session(small, [other], first).fit_hit
    assert any(line.startswith("prepared other") for line in first)
    assert session(other, [small], second).fit_hit
    assert not any(line.startswith("prepared") for line in second)


def test_without_a_chip_the_command_prints_no_result(tmp_path):
    """``run.py`` in a CPU-only process exits non-zero and prints no
    result line."""
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "paper-4dev.point-zipf", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
