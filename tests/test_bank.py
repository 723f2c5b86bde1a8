"""ModelBank stacked execution: bitwise equality vs the per-group executor
path on mixed waves, ragged group shapes, single-dispatch accounting,
grouped kernel backends, the row-registry content key, and epoch-swap bank
rebuilds under concurrent replay."""
import threading

import numpy as np
import pytest

from repro import api, obs
from repro.api import executor
from repro.api.bank import BankUnsupportedError, ModelBank
from repro.core import workloads
from repro.core.predictor import ProfetConfig
from repro.core.regressors import (DNNRegressor, RandomForestRegressor,
                                   bucket)
from repro.kernels import forest_eval
from repro.serve import LatencyService, synthetic_requests

# float64-only members: stacked vs per-group must be bit-identical
CFG = ProfetConfig(members=("linear", "forest"), n_trees=15, seed=0)


@pytest.fixture(scope="module")
def oracle():
    ds = workloads.generate(devices=("T4", "V100", "K80"),
                            models=("LeNet5", "AlexNet", "ResNet18"))
    return api.LatencyOracle.fit(ds, CFG)


@pytest.fixture(scope="module")
def dnn_oracle():
    ds = workloads.generate(devices=("T4", "V100"),
                            models=("LeNet5", "AlexNet"))
    return api.LatencyOracle.fit(ds, ProfetConfig(dnn_epochs=5, n_trees=10,
                                                  seed=0))


@pytest.fixture(scope="module")
def stream(oracle):
    return synthetic_requests(oracle, n=200, seed=1)


# ---------------------------------------------------------------------------
# stacked vs per-group equality
# ---------------------------------------------------------------------------


def test_stacked_matches_per_group_bitwise(oracle, stream):
    """Mixed measured/cross/two-phase wave over every pair: the banked
    single-dispatch answer equals the per-group path bit-for-bit (all
    members are float64)."""
    plans = [oracle.plan(r) for r in stream]
    banked = oracle.execute(plans)
    legacy = executor.execute_plans(oracle.profet, plans, epoch="x",
                                    bank=None)
    assert banked.banked and not legacy.banked
    np.testing.assert_array_equal(banked.latencies(), legacy.latencies())
    assert set(banked.mode_counts) == {api.MODE_MEASURED, api.MODE_CROSS,
                                       api.MODE_TWO_PHASE}


def test_stacked_matches_with_dnn_member(dnn_oracle):
    """With the float32 DNN member the stacked wave agrees to float32
    precision; the float64 members stay exact (asserted member-wise)."""
    reqs = synthetic_requests(dnn_oracle, n=120, seed=2)
    plans = [dnn_oracle.plan(r) for r in reqs]
    banked = dnn_oracle.execute(plans)
    legacy = executor.execute_plans(dnn_oracle.profet, plans, epoch="x",
                                    bank=None)
    np.testing.assert_allclose(banked.latencies(), legacy.latencies(),
                               rtol=1e-5)
    bank = dnn_oracle.bank
    pair = dnn_oracle.pairs()[0]
    X = dnn_oracle.feature_matrix(pair[0], dnn_oracle.dataset.cases[:9])
    gids = np.full(len(X), bank.gid[pair])
    ens = dnn_oracle.ensemble(*pair)
    from repro.core.regressors import LinearRegressor
    np.testing.assert_array_equal(
        LinearRegressor.apply(LinearRegressor._design(X),
                              bank.lin_coef[gids]),
        ens.models["linear"].predict(X))
    f = bank.forest
    np.testing.assert_array_equal(
        forest_eval.predict_grouped(X, gids, f["feat"], f["thr"], f["left"],
                                    f["right"], f["value"],
                                    depth=f["depth"], backend="numpy"),
        ens.models["forest"].predict(X))


def test_ragged_groups_one_row_next_to_sweep(oracle):
    """A grid sweep (many rows, one pair) mixed with 1-row groups on other
    pairs still executes as one dispatch and matches per-group answers."""
    ds = oracle.dataset
    sweep = [api.PredictRequest("T4", "V100", api.Workload.from_case(c))
             for c in ds.cases]
    singles = [api.PredictRequest("V100", "K80",
                                  api.Workload.from_case(ds.cases[0])),
               api.PredictRequest("K80", "T4",
                                  api.Workload.from_case(ds.cases[1]))]
    plans = [oracle.plan(r) for r in sweep + singles]
    banked = oracle.execute(plans)
    legacy = executor.execute_plans(oracle.profet, plans, epoch="x",
                                    bank=None)
    assert banked.fused_calls == 1 and legacy.fused_calls == 3
    np.testing.assert_array_equal(banked.latencies(), legacy.latencies())


def test_single_dispatch_accounting(oracle, dnn_oracle, stream):
    """One grouped forest launch + one stacked MLP apply per wave,
    regardless of how many pairs the wave mixes."""
    plans = [oracle.plan(r) for r in stream]
    before = oracle.bank.forest_launches
    batch = oracle.execute(plans)
    assert batch.fused_calls == 1
    assert oracle.bank.forest_launches == before + 1

    reqs = synthetic_requests(dnn_oracle, n=60, seed=4)
    f0, m0 = dnn_oracle.bank.forest_launches, dnn_oracle.bank.mlp_applies
    batch = dnn_oracle.predict_many(reqs)
    assert batch.fused_calls == 1
    assert dnn_oracle.bank.forest_launches == f0 + 1
    assert dnn_oracle.bank.mlp_applies == m0 + 1


def test_all_measured_wave_needs_no_dispatch(oracle):
    ds = oracle.dataset
    reqs = [api.PredictRequest("T4", "T4", api.Workload.from_case(c))
            for c in ds.cases[:5]]
    batch = oracle.predict_many(reqs)
    assert batch.fused_calls == 0
    assert [r.mode for r in batch] == [api.MODE_MEASURED] * 5


# ---------------------------------------------------------------------------
# bank construction / fallback
# ---------------------------------------------------------------------------


def test_unbankable_members_fall_back_per_group(oracle):
    """Ensembles holding non-production members (the frozen reference
    models) cannot stack; the oracle serves per-group instead of failing."""
    from repro.core import reference
    ds = workloads.generate(devices=("T4", "V100"), models=("LeNet5",))
    profet = reference.fit_profet_reference(
        ds, ProfetConfig(members=("linear", "forest"), n_trees=5, seed=0))
    with pytest.raises(BankUnsupportedError):
        ModelBank.build(profet)
    ref_oracle = api.LatencyOracle(profet, ds)
    assert ref_oracle.bank is None
    req = api.PredictRequest("T4", "V100",
                             api.Workload.from_case(ds.cases[0]))
    batch = ref_oracle.predict_many([req])
    assert not batch.banked and batch.fused_calls == 1
    assert np.isfinite(batch.latencies()).all()


def test_bank_pads_ragged_forests(oracle):
    """Pairs grow different node counts; the (G, T, N_max) stack pads with
    leaves and keeps per-group depth."""
    bank = oracle.bank
    f = bank.forest
    assert f["feat"].shape[0] == len(oracle.pairs())
    assert f["feat"].shape[1] == CFG.n_trees
    assert (f["depth"] > 0).all()
    # pad nodes are leaves (feat < 0) — routing can never enter them
    assert (f["feat"] < f["feat"].shape[2]).all()


# ---------------------------------------------------------------------------
# grouped kernels
# ---------------------------------------------------------------------------


def _toy_forest_stack(seed=0, n_groups=3, n_trees=8, d=4):
    rng = np.random.default_rng(seed)
    forests = []
    for g in range(n_groups):
        X = rng.uniform(-2, 2, size=(50 + 30 * g, d))
        y = np.sin(X[:, 0] * (g + 1)) + X[:, d - 1]
        rf = RandomForestRegressor(n_estimators=n_trees, max_depth=5 + g,
                                   seed=g).fit(X, y)
        forests.append(rf.forest_)
    T = forests[0].n_trees
    n_max = max(f.feat.shape[1] for f in forests)
    stack = {}
    for name, fill in (("feat", -1), ("thr", 0.0), ("left", 0),
                       ("right", 0), ("value", 0.0)):
        arr = np.full((n_groups, T, n_max), fill,
                      getattr(forests[0], name).dtype)
        for g, f in enumerate(forests):
            arr[g, :, :f.feat.shape[1]] = getattr(f, name)
        stack[name] = arr
    stack["depth"] = np.array([f.depth for f in forests])
    return forests, stack


def test_grouped_numpy_matches_per_group_kernel():
    forests, s = _toy_forest_stack()
    rng = np.random.default_rng(7)
    X = rng.uniform(-2, 2, size=(83, 4))
    gid = rng.integers(0, len(forests), size=83)
    got = forest_eval.leaf_values_grouped_numpy(
        X, gid, s["feat"], s["thr"], s["left"], s["right"], s["value"],
        s["depth"])
    for g, f in enumerate(forests):
        sel = gid == g
        ref = forest_eval.leaf_values_numpy(X[sel], f.feat, f.thr, f.left,
                                            f.right, f.value, depth=f.depth)
        np.testing.assert_array_equal(got[:, sel], ref)


@pytest.mark.parametrize("n_trees,d", [(8, 4), (11, 13)],
                         ids=["aligned-trees", "padded-trees-features"])
def test_grouped_pallas_interpret_matches_grouped_numpy(n_trees, d):
    """The (row-block, tree-tile) Pallas kernel (interpret mode) agrees
    exactly with the grouped numpy traversal on a float32-quantized bank,
    including the kernel's padding: node counts that are not a multiple
    of 128, tree counts that are not a multiple of 8, feature counts that
    are not a multiple of 8, and rows spilling over one row block."""
    _, s = _toy_forest_stack(seed=3, n_trees=n_trees, d=d)
    G, T, N = s["feat"].shape
    assert T == n_trees and N % forest_eval.LANES
    n = forest_eval.LANES + 37
    rng = np.random.default_rng(11)
    X = rng.uniform(-2, 2, size=(n, d)).astype(np.float32).astype(
        np.float64)
    thr32 = s["thr"].astype(np.float32).astype(np.float64)
    gid = rng.integers(0, G, size=n)
    gid[:forest_eval.LANES + 5] = 1          # group 1 fills two row blocks
    v_np = forest_eval.leaf_values_grouped_numpy(
        X, gid, s["feat"], thr32, s["left"], s["right"], s["value"],
        s["depth"])
    v_pl = forest_eval.leaf_values_grouped_pallas(
        X, gid, s["feat"], thr32, s["left"], s["right"], s["value"],
        depth=s["depth"])
    np.testing.assert_array_equal(v_np.astype(np.float32), v_pl)


def _counters_moved(before):
    after = obs.snapshot()["counters"]
    return {k: v - before["counters"].get(k, 0) for k, v in after.items()}


def test_grouped_pallas_launch_counts_its_upload_and_fill():
    """One interpret-mode launch over a tiny stack: ``bank.h2d_bytes``
    moves by the summed bytes of the host arrays the launch receives (block
    group and depth vectors, the transposed rows, the padded stack), and
    the fill counters by the rows and the launched slots."""
    _, s = _toy_forest_stack(seed=3, n_trees=11, d=13)
    G, T, N = s["feat"].shape
    lanes = forest_eval.LANES
    n = lanes + 37
    gid = np.zeros(n, np.int64)
    gid[lanes + 5:] = 2                      # group 0 fills two blocks
    X = np.random.default_rng(4).uniform(-2, 2, size=(n, 13))
    before = obs.snapshot()
    forest_eval.leaf_values_grouped_pallas(
        X, gid, s["feat"], s["thr"], s["left"], s["right"], s["value"],
        depth=s["depth"])
    moved = _counters_moved(before)
    n_blocks = bucket(3)                     # 2 blocks + 1 block, bucketed
    t_pad = -(-T // 8) * 8
    n_pad = -(-N // lanes) * lanes
    d_pad = 16
    want = (2 * 4 * n_blocks                 # block_gid, block_depth
            + 4 * d_pad * n_blocks * lanes   # xt
            + 5 * 4 * G * t_pad * n_pad)     # five padded stack arrays
    assert moved["bank.h2d_bytes"] == want
    assert moved["bank.forest_rows"] == n
    assert moved["bank.forest_slots"] == n_blocks * lanes


def test_bank_wave_spans_its_members_and_counts_the_mlp_upload(dnn_oracle):
    """A banked wave runs each member once under its span; the MLP apply
    uploads its bucketed input block and head indices, nothing else (the
    stacked heads already live on the device)."""
    bank = dnn_oracle.bank
    X = np.random.default_rng(5).uniform(0, 1, size=(9, bank.n_features))
    gids = np.array([0, 0, 1, 1, 1, 2, 0, 1, 2]) % bank.n_groups
    before = obs.snapshot()
    bank.execute(X, gids)
    bank.interpolate(["batch", "pixel"], np.array([0, 1]),
                     np.array([48.0, 96.0]), np.ones(2), np.full(2, 3.0))
    after = obs.snapshot()
    for name in ("bank.forest", "bank.mlp", "bank.phase2"):
        assert after["spans"][name]["n"] \
            - before["spans"].get(name, {"n": 0})["n"] == 1, name
    g_pad = bucket(len(np.unique(gids)))
    r_pad = bucket(int(np.bincount(gids).max()),
                   DNNRegressor.PREDICT_BUCKET_MIN)
    assert _counters_moved(before).get("bank.h2d_bytes", 0) == \
        4 * g_pad + 4 * g_pad * r_pad * bank.n_features


@pytest.mark.parametrize("n_trees,d", [(8, 4), (11, 13)],
                         ids=["aligned-trees", "padded-trees-features"])
def test_grouped_pallas_device_stack_matches_host_launch(n_trees, d):
    """A launch over the device-resident stack (``stack=``) answers
    bit-identically to the host-array launch and to the grouped numpy
    traversal on a float32-quantized stack: a group spilling over two row
    blocks, tree and feature counts that are not multiples of 8."""
    _, s = _toy_forest_stack(seed=3, n_trees=n_trees, d=d)
    G = s["feat"].shape[0]
    n = forest_eval.LANES + 37
    rng = np.random.default_rng(11)
    X = rng.uniform(-2, 2, size=(n, d)).astype(np.float32).astype(
        np.float64)
    thr32 = s["thr"].astype(np.float32).astype(np.float64)
    gid = rng.integers(0, G, size=n)
    gid[:forest_eval.LANES + 5] = 1          # group 1 fills two row blocks
    args = (s["feat"], thr32, s["left"], s["right"], s["value"])
    stack = forest_eval.device_forest_stack(*args)
    v_np = forest_eval.leaf_values_grouped_numpy(X, gid, *args, s["depth"])
    v_host = forest_eval.leaf_values_grouped_pallas(X, gid, *args,
                                                    depth=s["depth"])
    v_dev = forest_eval.leaf_values_grouped_pallas(X, gid, *args,
                                                   depth=s["depth"],
                                                   stack=stack)
    np.testing.assert_array_equal(v_dev, v_host)
    np.testing.assert_array_equal(v_np.astype(np.float32), v_dev)
    np.testing.assert_array_equal(
        forest_eval.predict_grouped(X, gid, *args, depth=s["depth"],
                                    backend="pallas", stack=stack),
        forest_eval.tree_mean(v_host))


def test_grouped_pallas_device_stack_launch_counts_only_the_rows():
    """Placing the stack counts one ``bank.forest_stack_uploads`` and no
    ``bank.h2d_bytes``; a launch over it counts the block vectors and the
    transposed rows alone, and no further upload."""
    _, s = _toy_forest_stack(seed=3, n_trees=11, d=13)
    lanes = forest_eval.LANES
    n = lanes + 37
    gid = np.zeros(n, np.int64)
    gid[lanes + 5:] = 2                      # group 0 fills two blocks
    X = np.random.default_rng(4).uniform(-2, 2, size=(n, 13))
    args = (s["feat"], s["thr"], s["left"], s["right"], s["value"])
    before = obs.snapshot()
    stack = forest_eval.device_forest_stack(*args)
    placed = _counters_moved(before)
    assert placed.get("bank.forest_stack_uploads") == 1
    assert placed.get("bank.h2d_bytes", 0) == 0
    before = obs.snapshot()
    forest_eval.leaf_values_grouped_pallas(X, gid, *args, depth=s["depth"],
                                           stack=stack)
    moved = _counters_moved(before)
    n_blocks = bucket(3)                     # 2 blocks + 1 block, bucketed
    d_pad = 16
    assert moved["bank.h2d_bytes"] == (2 * 4 * n_blocks
                                       + 4 * d_pad * n_blocks * lanes)
    assert moved.get("bank.forest_stack_uploads", 0) == 0
    assert moved["bank.forest_rows"] == n
    assert moved["bank.forest_slots"] == n_blocks * lanes


def _forest_upload_bytes(gids, n_features):
    """What a grouped launch over a device stack uploads for ``gids``:
    the block group and depth vectors and the transposed rows."""
    lanes = forest_eval.LANES
    n_blocks = bucket(int((-(-np.bincount(gids) // lanes)).sum()))
    d_pad = -(-n_features // 8) * 8
    return 2 * 4 * n_blocks + 4 * d_pad * n_blocks * lanes


def test_pallas_bank_places_its_stack_once(dnn_oracle):
    """A Pallas-backend bank places its forest stack on its first wave and
    reuses it: two waves count one upload, and their ``bank.h2d_bytes`` is
    the rows' bytes and the MLP blocks, no stack."""
    bank = ModelBank.build(dnn_oracle.profet, backend="pallas")
    rng = np.random.default_rng(5)
    waves = [np.array([0, 0, 1, 1, 1, 0, 1]) % bank.n_groups,
             np.arange(20) % bank.n_groups]
    before = obs.snapshot()
    for gids in waves:
        bank.execute(rng.uniform(0, 1, size=(len(gids), bank.n_features)),
                     gids)
    moved = _counters_moved(before)
    assert moved.get("bank.forest_stack_uploads") == 1
    want = 0
    for gids in waves:
        g_pad = bucket(len(np.unique(gids)))
        r_pad = bucket(int(np.bincount(gids).max()),
                       DNNRegressor.PREDICT_BUCKET_MIN)
        want += (_forest_upload_bytes(gids, bank.n_features)
                 + 4 * g_pad + 4 * g_pad * r_pad * bank.n_features)
    assert moved["bank.h2d_bytes"] == want
    assert bank.forest_launches == 2


def test_pallas_bank_warmup_places_the_stack_and_covers_waves(dnn_oracle):
    """Warm-up places the stack and compiles the grouped launch over it:
    a wave after it compiles nothing and places no second stack."""
    import logging

    import jax
    bank = ModelBank.build(dnn_oracle.profet, backend="pallas")
    before = obs.snapshot()
    bank.warmup(max_rows=64)
    assert _counters_moved(before).get("bank.forest_stack_uploads") == 1
    gids = np.arange(50) % bank.n_groups
    X = np.random.default_rng(2).uniform(0, 1, size=(50, bank.n_features))
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("jax._src.dispatch")
    before = obs.snapshot()
    with jax.log_compiles(True):
        logger.addHandler(handler)
        try:
            bank.execute(X, gids)
        finally:
            logger.removeHandler(handler)
    compiles = [r.getMessage() for r in records
                if "Compiling" in r.getMessage()]
    assert not compiles, compiles
    assert _counters_moved(before).get("bank.forest_stack_uploads", 0) == 0


def test_pallas_banks_of_different_fits_keep_their_own_stacks(oracle):
    """Two Pallas banks from different fits each answer from their own
    device stack, exactly as the host-array launch over their own forest;
    a bank split or rebuilt from its payload starts without one."""
    ds = workloads.generate(devices=("T4", "V100", "K80"),
                            models=("LeNet5", "AlexNet", "ResNet18"))
    other = api.LatencyOracle.fit(
        ds, ProfetConfig(members=("linear", "forest"), n_trees=7, seed=3))
    banks = [ModelBank.build(o.profet, backend="pallas")
             for o in (oracle, other)]
    assert banks[0].pairs == banks[1].pairs
    rng = np.random.default_rng(8)
    X = rng.uniform(0, 1, size=(40, banks[0].n_features))
    gids = rng.integers(0, banks[0].n_groups, size=40)
    got = []
    for bank in banks:
        f = bank.forest
        host = forest_eval.predict_grouped(
            X, gids, f["feat"], f["thr"], f["left"], f["right"], f["value"],
            depth=f["depth"], backend="pallas")
        dev = forest_eval.predict_grouped(
            X, gids, f["feat"], f["thr"], f["left"], f["right"], f["value"],
            depth=f["depth"], backend="pallas",
            stack=bank.device_forest_stack())
        np.testing.assert_array_equal(dev, host)
        got.append(bank.execute(X, gids))
    assert banks[0].device_forest_stack() is not \
        banks[1].device_forest_stack()
    assert not np.allclose(got[0], got[1])
    sub, = banks[0].split([banks[0].pairs])
    assert sub._forest_stack is None
    assert ModelBank.from_payload(banks[0].to_payload())._forest_stack \
        is None


def test_oracle_refresh_serves_from_the_new_banks_stack(monkeypatch):
    """Across an ``oracle_refreshed`` swap on the Pallas backend every
    answer matches the per-group path of the oracle that served it: the
    incoming bank warms up with its own stack, and no wave after the swap
    routes through the old one."""
    monkeypatch.setattr(forest_eval, "_auto_backend", lambda: "pallas")
    ds = workloads.generate(devices=("T4", "V100"),
                            models=("LeNet5", "AlexNet"))
    o1 = api.LatencyOracle.fit(ds, CFG)
    o2 = api.LatencyOracle.fit(
        ds, ProfetConfig(members=("linear", "forest"), n_trees=7, seed=3))
    reqs = synthetic_requests(o1, n=24, seed=6)
    plans = [o1.plan(r) for r in reqs]
    before = obs.snapshot()
    svc = LatencyService(o1, max_wave=8, cache_size=0, warmup_rows=16)
    served = []
    for o in (o1, o2):
        if o is o2:
            svc.oracle_refreshed(o2, fingerprint="e2")
        subs = [svc.submit(r) for r in reqs]
        svc.run()
        assert all(sr.error is None for sr in subs)
        served.append(np.array([sr.result.latency_ms for sr in subs]))
    assert _counters_moved(before).get("bank.forest_stack_uploads") == 2
    assert o1.bank._forest_stack is not o2.bank._forest_stack
    for o, got in zip((o1, o2), served):
        want = executor.execute_plans(o.profet, plans, epoch="x",
                                      bank=None).latencies()
        np.testing.assert_array_equal(got, want)
    assert not np.allclose(served[0], served[1])


def test_payload_of_a_bank_with_a_device_stack_is_numpy(dnn_oracle):
    """The shard payload never carries the device stack: after a Pallas
    bank has placed it, every array of ``to_payload()`` is numpy."""
    import jax
    bank = ModelBank.build(dnn_oracle.profet, backend="pallas")
    assert bank.device_forest_stack() is not None
    payload = bank.to_payload()
    assert payload["backend"] == "numpy"
    assert set(payload["forest"]) == set(bank.forest)

    def leaves(v):
        if isinstance(v, dict):
            for x in v.values():
                yield from leaves(x)
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from leaves(x)
        else:
            yield v
    found = list(leaves(payload))
    assert not [v for v in found if isinstance(v, jax.Array)]
    assert sum(isinstance(v, np.ndarray) for v in found) > 10


def test_leaf_values_depth_bound_matches_unbounded():
    forests, _ = _toy_forest_stack(seed=5)
    f = forests[0]
    rng = np.random.default_rng(0)
    X = rng.uniform(-2, 2, size=(29, 4))
    a = forest_eval.leaf_values_numpy(X, f.feat, f.thr, f.left, f.right,
                                      f.value)
    b = forest_eval.leaf_values_numpy(X, f.feat, f.thr, f.left, f.right,
                                      f.value, depth=f.depth)
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# row registry content key (id-aliasing regression)
# ---------------------------------------------------------------------------


def test_row_registry_keys_by_content_not_identity():
    """Two DISTINCT dict objects with equal content must share one row
    (under the old ``id(profile)`` key they got two), and different
    content must never share — the id-aliasing bug where a GC'd transient
    profile's address is reused by a new, different profile."""
    reg = executor._RowRegistry()
    case = ("LeNet5", 32, 64)
    k1 = reg.add("T4", "V100", {"conv": 1.0, "relu": 0.5}, case)
    k2 = reg.add("T4", "V100", {"conv": 1.0, "relu": 0.5}, case)
    assert k1 == k2 and reg.n_rows == 1
    k3 = reg.add("T4", "V100", {"conv": 2.0, "relu": 0.5}, case)
    assert k3 != k1 and reg.n_rows == 2


def test_equal_content_client_profiles_dedup_end_to_end(oracle):
    ds = oracle.dataset
    case = ds.cases[0]
    reqs = [api.PredictRequest("T4", "V100", api.Workload.from_case(case),
                               profile=dict(ds.profile("T4", case)))
            for _ in range(4)]
    batch = oracle.predict_many(reqs)
    assert batch.rows == 1
    assert len(set(batch.latencies())) == 1


# ---------------------------------------------------------------------------
# warm-up + epoch swaps
# ---------------------------------------------------------------------------


def test_warmup_builds_bank_and_reports_ms(oracle):
    svc = LatencyService(oracle, max_wave=16, warmup=True)
    assert oracle.bank is not None
    assert svc.stats.warmup_ms >= 0.0
    assert "warmup_ms" in svc.stats.summary()
    # warm-up happens again for the incoming oracle of a refresh
    before = svc.stats.warmup_ms
    svc.oracle_refreshed(oracle, fingerprint="deploy-2")
    assert svc.stats.warmup_ms >= before


def test_mlp_bucket_warmup_covers_wave_shapes(dnn_oracle):
    """After warm-up every bucket shape a wave can produce is compiled:
    serving a fresh mixed wave triggers no new compilation."""
    import jax
    bank = dnn_oracle.bank
    # the service default: 2x the wave size, since every two-phase request
    # registers a min AND a max phase-1 row
    bank.warmup(max_rows=64)
    reqs = synthetic_requests(dnn_oracle, n=32, seed=9)
    plans = [dnn_oracle.plan(r) for r in reqs]
    with jax.log_compiles(True):
        import logging
        records = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r)
        logger = logging.getLogger("jax._src.dispatch")
        logger.addHandler(handler)
        try:
            dnn_oracle.execute(plans)
        finally:
            logger.removeHandler(handler)
    compiles = [r for r in records if "Compiling" in r.getMessage()]
    assert not compiles, [r.getMessage() for r in compiles]


def test_bucket_helper():
    assert bucket(0) == 1 and bucket(1) == 1
    assert bucket(5) == 8 and bucket(8) == 8 and bucket(9) == 16
    assert bucket(3, floor=8) == 8


def test_epoch_swap_rebuilds_bank_no_stale_answers(oracle):
    """Concurrent replay across an oracle_refreshed swap: every response's
    latency must match what the oracle generation named by its epoch
    would answer — zero stale (old-model, new-epoch) answers."""
    ds = workloads.generate(devices=("T4", "V100", "K80"),
                            models=("LeNet5", "AlexNet", "ResNet18"))
    o1 = api.LatencyOracle.fit(ds, CFG)
    o2 = api.LatencyOracle.fit(
        ds, ProfetConfig(members=("linear", "forest"), n_trees=7, seed=3))
    reqs = synthetic_requests(o1, n=120, seed=6)
    expected = {"e1": o1.predict_many(reqs).latencies(),
                "e2": o2.predict_many(reqs).latencies()}
    assert not np.allclose(expected["e1"], expected["e2"])

    svc = LatencyService(o1, max_wave=8, cache_size=0, epoch="e1")
    stop = threading.Event()

    def drain():
        while not stop.is_set():
            svc.run_once()
        svc.run()

    t = threading.Thread(target=drain)
    t.start()
    submitted = []
    try:
        for i, r in enumerate(reqs):
            submitted.append((i, svc.submit(r)))
            if i == len(reqs) // 2:
                svc.oracle_refreshed(o2, fingerprint="e2")
    finally:
        stop.set()
        t.join()
    assert all(sr.done for _, sr in submitted)
    for i, sr in submitted:
        assert sr.error is None
        epoch = sr.result.epoch
        assert epoch in expected
        np.testing.assert_array_equal(sr.result.latency_ms,
                                      expected[epoch][i])
