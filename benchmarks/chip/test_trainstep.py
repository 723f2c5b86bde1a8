"""Tests of the benchmark's training-step path and of the harness's CPU
split, on the CPU.

The training cell runs here at the program's smoke-size preset of the
same architecture (three layers, width 64), through the harness with
``require_chip=False``: the program's step checked against ``lmref``, the
control one precision lower, and the timed path broken underneath in
each way the cell can break.
"""
import contextlib
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import checks, harness, lmref, roofline, spec  # noqa: E402
from chipbench import trainfaults, trainstep  # noqa: E402

CELL = "granite-3-2b.train-4k"
SMALL = {"seq_len": 64, "batch": 2}
# At width 64 bfloat16 rounds a larger share of each product than at the
# cell's 2,048, so the cell's limits (set from chip readings) would fail
# the sound step here. These are set the same way from CPU readings at
# this size: sound steps read at most 3.6e-4, 3.2e-3 and 2.0e-3 (four
# seeds); the control at least 3.5e-4, 2.4e-2 and 6.8e-3, failing
# grad_gap on each; each fault fails at least one number by a factor of
# five or more.
SMOKE_LIMITS = {"loss_gap": 1e-3, "grad_gap": 2e-2, "change_gap": 2e-2}


def _smoke_config():
    cfg = json.loads((HERE / "configs" / "granite-3-2b.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=3, vocab_size=256)
    return cfg


@pytest.fixture
def smoke(monkeypatch):
    """The program's registry hands out the architecture's smoke-size
    preset, whose widths ``_smoke_config`` states."""
    from repro.configs import base
    get = base.get_config
    monkeypatch.setattr(base, "get_config",
                        lambda arch, smoke=False: get(arch, smoke=True))


def _settings(name):
    return {**spec.load_json(HERE / "cells" / f"{name}.json"),
            "limits": SMOKE_LIMITS}


def _run(tmp_path, seed, trace=False, lines=None):
    return harness.run(CELL, seed, 0.5, trace, cfg=_smoke_config(),
                       cache_dir=tmp_path, require_chip=False,
                       jax_cache=False, traffic_overrides=SMALL,
                       log=(lines.append if lines is not None
                            else lambda line: None))


def _session(tmp_path):
    return trainstep.TrainSession(CELL, cfg=_smoke_config(),
                                  cache_dir=tmp_path, require_chip=False,
                                  jax_cache=False, traffic_overrides=SMALL,
                                  log=lambda line: None)


# ---------------------------------------------------------------------------
# the cell's files and the harness's dispatch
# ---------------------------------------------------------------------------

def test_the_training_configuration_states_its_cut_and_job():
    cfg = spec.load_json(HERE / "configs" / "granite-3-2b.json")
    entry = spec.config_entry(spec.benchmark(), "granite-3-2b")
    assert spec.kind(cfg) == "train"
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["hidden_size"] // cfg["num_attention_heads"] \
        == cfg["head_dim"]
    # the program's registered architecture has the widths the file
    # states, so the cut is the depth alone
    mc = trainstep.reference(cfg, entry["file"]).program_config(cfg)
    assert (mc.num_layers, mc.d_model, mc.d_ff, mc.vocab_size) \
        == (8, 2048, 8192, 49155)
    assert set(spec.cell_settings(CELL)["limits"]) == set(trainstep.NUMBERS)


def test_a_cold_bank_run_fits_no_training_configuration():
    """Only bank configurations are prepared by a bank cell's cold run."""
    s = harness.Session.__new__(harness.Session)
    s.bench, s.cfg = spec.benchmark(), {"name": "paper-4dev"}
    names = [c["name"] for c, _ in s._other_configs()]
    assert names == ["catalog-9dev"]
    assert all(spec.kind(c) == "bank" for c, _ in s._other_configs())


def test_the_training_path_through_the_harness(tmp_path, monkeypatch,
                                              smoke):
    """A run of the training cell yields ``step_ms`` and ``setup_s``
    untraced, and with a trace ``mfu.train`` and ``idle_share.train``
    (the CPU has no device plane: the trace's extract is stood in for)."""
    monkeypatch.setattr(spec, "cell_settings", _settings)
    lines = []
    res = _run(tmp_path, 2**31 + 11, lines=lines)
    assert res["correct"] is True, res["checks"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(res["metrics"]) == {"step_ms", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["metrics"]["step_ms"]["value"] > 0
    assert list(res["checks"]) == list(trainstep.NUMBERS)
    assert lines[-3:] == checks.lines(
        {k: v["value"] for k, v in res["checks"].items()},
        {k: v["limit"] for k, v in res["checks"].items()})
    assert any(line.startswith("compiles inside the window: 0")
               for line in lines)

    import jax
    from chipbench import tracereduce

    def extract(logdir, names):
        return {"device": {"/device:TPU:0": [["fusion.1", 0, 250_000_000]]},
                "host": [[tracereduce.WINDOW, 0, 1_000_000_000]]}
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(tracereduce, "extract", extract)
    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops": 197e12})
    res = _run(tmp_path, 2**31 + 12, trace=True)
    assert set(res["metrics"]) == {"mfu.train", "idle_share.train"}
    assert res["metrics"]["idle_share.train"]["value"] == pytest.approx(75.0)
    flops = roofline.train_step_flops(3, 64, 4, 2, 16, 128, 256, 2, 64)
    assert res["metrics"]["mfu.train"]["value"] == pytest.approx(
        100.0 * flops * res["attempted"] / 197e12)
    assert res["device"]["busy_s"] == pytest.approx(0.25)
    assert [list(op) for op in res["breakdown"]["device_ops"]] \
        == [["fusion.1", 0.25]]


def test_the_window_is_seeded_and_its_first_rows_all_differ(tmp_path,
                                                           smoke):
    s = _session(tmp_path)
    s.start(5)
    first = s.got["loss"]
    rows = [np.asarray(s.feed(s.dkey, i)["tokens"]) for i in (1, 2, 3)]
    assert all(not np.array_equal(a, b) for i, a in enumerate(rows)
               for b in rows[i + 1:])
    s.start(6)
    assert not np.array_equal(first, s.got["loss"])
    s.start(5)
    np.testing.assert_array_equal(first, s.got["loss"])
    assert rows[0].max() < 256 and rows[0].shape == (2, 64)
    # a seed past 32 bits is a seed of its own
    import jax
    assert not np.array_equal(
        jax.random.key_data(trainstep.seed_key(2**33 + 5, 0)),
        jax.random.key_data(trainstep.seed_key(5, 0)))
    s.close()


# ---------------------------------------------------------------------------
# the reference, the control and the faults
# ---------------------------------------------------------------------------

def test_the_configurations_module_reads_as_lmref_called_directly(tmp_path,
                                                                   smoke):
    """The module the harness loads from the configuration's
    ``"reference"`` is ``lmref``'s file, and gives the readings and the
    FLOP count that ``lmref`` and ``roofline`` give when called directly."""
    s = _session(tmp_path)
    assert pathlib.Path(s.ref.__file__).resolve() \
        == pathlib.Path(lmref.__file__).resolve()
    s.start(9)
    batches = s.batches()
    via = s.ref.readings(s.wkey, batches, s.dims, s.adam, s.ref.REFERENCE)
    direct = lmref.readings(s.wkey, batches, lmref.dims(s.cfg),
                            trainstep.adam(s.cfg, lmref))
    assert set(via) == set(direct) == {"loss", "grad", "change"}
    for k in via:
        np.testing.assert_array_equal(via[k], direct[k])
    assert s.flops_per_step == roofline.train_step_flops(
        3, 64, 4, 2, 16, 128, 256, 2, 64)
    s.close()


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_the_batch",
                                   "layer_skipped", "attention_unmasked",
                                   "loss_altered"])
def test_correct_catches_a_broken_training_step(tmp_path, monkeypatch,
                                                smoke, fault):
    """The rest of a run, with the program's step broken underneath:
    ``correct`` holds for the sound step and fails for each fault."""
    monkeypatch.setattr(spec, "cell_settings", _settings)
    with (trainfaults.planted(fault) if fault else contextlib.nullcontext()):
        res = _run(tmp_path, 21)
    assert res["correct"] is (fault is None), res["checks"]


def test_the_training_control_is_not_correct(tmp_path, smoke):
    """The reference with float8 matrix products (one step below the
    job's bfloat16), put in the program's place, fails the limits."""
    limits = SMOKE_LIMITS
    s = _session(tmp_path)
    for seed in (1, 2, 2**31 + 3):
        s.start(seed)
        batches = [(b["tokens"], b["labels"]) for b in
                   (s.feed(s.dkey, i)
                    for i in range(1, trainstep.CHECKED_STEPS + 1))]
        want = lmref.readings(s.wkey, batches, s.dims, s.adam)
        ctl = lmref.readings(s.wkey, batches, s.dims, s.adam, lmref.CONTROL)
        numbers = trainstep.compare(ctl, want, lmref.leaf_names(s.dims))
        assert not checks.verdict(numbers, limits), numbers
        assert checks.verdict(trainstep.compare(s.got, want,
                                                lmref.leaf_names(s.dims)),
                              limits)
    s.close()


def test_compare_takes_the_worst_leaf_against_the_median_floor():
    names = ["a", "b", "c", "d"]
    want = {"loss": np.array([10.0, 9.0]), "grad": np.array([1, 2, 3, 1e-6]),
            "change": np.array([1.0, 1.0, 1.0, 5.0])}
    got = {"loss": np.array([10.0, 9.09]), "grad": np.array([1, 2, 3.1, 0.1]),
           "change": np.array([1.0, 1.2, 1.0, 0.0])}
    n = trainstep.compare(got, want, names)
    assert n["loss_gap"] == pytest.approx(0.01) and n["loss_at"] == 2
    # the still leaf's gradient gap is over the median leaf's norm (1.5)
    assert n["grad_gap"] == pytest.approx((0.1 - 1e-6) / 1.5)
    assert n["grad_at"] == "d"
    # and its change is not compared at all
    assert n["change_gap"] == pytest.approx(0.2) and n["change_at"] == "b"
    assert n["leaves_compared"] == 3


# ---------------------------------------------------------------------------
# the model FLOP count against the compiler's
# ---------------------------------------------------------------------------

def test_train_step_flops_against_the_cost_analysis():
    """One layer without remat (XLA's cost analysis counts a scanned
    body once, and would count the recompute), full attention as the
    program computes it at this length. The cost analysis also counts the
    elementwise work the model FLOPs leave out (norms, softmax, SiLU,
    AdamW): 3.7% here, so the count may lie up to 8% below it and never
    above."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.launch import steps as ST
    from repro.models import model as M
    from repro.train import optimizer as OPT
    mc = dataclasses.replace(get_config("granite-3-2b", smoke=True),
                             num_layers=1, d_model=256, d_ff=1024,
                             num_heads=4, num_kv_heads=2, head_dim=64,
                             vocab_size=1024, remat=False)
    hp = ST.make_opt_hparams(mc)
    p = M.abstract_init(mc)[0]
    o = jax.eval_shape(lambda p: OPT.init_state(p, hp), p)
    b = {k: jax.ShapeDtypeStruct((2, 256), jnp.int32)
         for k in ("tokens", "labels")}
    ca = jax.jit(ST.make_train_step(mc, hp)).lower(p, o, b).compile() \
        .cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    full = roofline.train_step_flops(1, 256, 4, 2, 64, 1024, 1024, 2, 256,
                                     causal=False)
    assert 1.0 <= ca["flops"] / full <= 1.08
    causal = roofline.train_step_flops(1, 256, 4, 2, 64, 1024, 1024, 2, 256)
    assert full - causal == 3 * (2 * 2 * 2 * 256 ** 2 * 4 * 64) // 2


def test_train_step_flops_of_the_cell():
    """8 layers of 60.8 M weights and the 100.7 M tied head: about 0.59 B
    weights, 6 x 0.59 B x 8,192 tokens plus the causal attention."""
    f = roofline.train_step_flops(8, 2048, 32, 8, 64, 8192, 49155, 2, 4096)
    weights = 8 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192) \
        + 49155 * 2048
    assert weights == pytest.approx(0.587e9, rel=1e-3)
    attention = 3 * 8 * 2 * 2 * 4096 * 4096 * 2048
    assert f == 6 * weights * 8192 + attention


# ---------------------------------------------------------------------------
# the load generator's CPU of its own
# ---------------------------------------------------------------------------

@pytest.fixture
def sleeper():
    child = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(30)"])
    yield child
    child.kill()
    child.wait()


def test_split_cpus_on_many_cpus(sleeper):
    mine = os.sched_getaffinity(0)
    allowed = sorted(mine)
    if len(allowed) < 2:
        pytest.skip("this process may use one CPU only")
    split = harness.split_cpus(sleeper.pid, allowed)
    try:
        ours, theirs, restore = split
        assert theirs == {allowed[-1]}
        assert ours == set(allowed[:-1])
        assert os.sched_getaffinity(sleeper.pid) == theirs
        for tid in map(int, os.listdir("/proc/self/task")):
            with contextlib.suppress(OSError):
                assert not os.sched_getaffinity(tid) & theirs
    finally:
        split[2]()
    assert os.sched_getaffinity(0) == mine


def test_split_cpus_on_one_cpu_changes_nothing(sleeper):
    mine, theirs = os.sched_getaffinity(0), os.sched_getaffinity(
        sleeper.pid)
    assert harness.split_cpus(sleeper.pid, [min(mine)]) is None
    assert os.sched_getaffinity(0) == mine
    assert os.sched_getaffinity(sleeper.pid) == theirs


def test_per_second_counts_each_whole_second():
    t0 = time.monotonic()
    times = [t0 + 0.1, t0 + 0.9, t0 + 1.5, t0 + 2.999, t0 + 3.2, t0 - 0.1]
    assert harness.per_second(times, t0, 3.0) == [2, 1, 1]
    assert harness.per_second(times, t0, 3.0, [8] * 6) == [16, 8, 8]
