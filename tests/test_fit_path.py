"""The vectorized ensemble-training hot path (bench_fit's subject):

  - oracle equivalence: level-synchronous grower vs the recursive reference
    (same bootstrap plan -> same splits, same node counts, same predictions)
  - packed-forest kernel: Pallas (interpreted) vs numpy traversal, exact
  - vmapped multi-target DNN vs sequential per-target fits, within tolerance
  - minibatch plan: every epoch covers every sample (the dropped-tail fix)
  - packed-forest pickling: round-trip + legacy node-list rejection
"""
import pickle

import numpy as np
import pytest

from repro.core import reference
from repro.core.ensemble import mape
from repro.core.regressors import (DNNRegressor, LegacyForestError,
                                   PackedForest, RandomForestRegressor,
                                   epoch_batches, fit_dnn_multi)
from repro.kernels import forest_eval


def _forest_data(n=90, d=6, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = np.sin(X[:, 0] * 2) + X[:, 1] ** 2 + 0.1 * rng.normal(size=n)
    return X, y, rng.normal(size=(40, d))


# ---------------------------------------------------------------------------
# oracle equivalence: vectorized grower vs recursive reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("max_depth,seed", [(4, 5), (24, 1)])
def test_grower_matches_recursive_reference(max_depth, seed):
    X, y, Xq = _forest_data()
    rf = RandomForestRegressor(n_estimators=6, max_depth=max_depth,
                               seed=seed).fit(X, y)
    ref = reference.ReferenceForest(n_estimators=6, max_depth=max_depth,
                                    seed=seed).fit(X, y)
    # identical structure: node counts and the (feature, threshold) multiset
    # of every tree (thresholds are computed by the same float ops -> bitwise)
    f = rf.forest_
    assert [int(c) for c in f.n_nodes] == [len(t) for t in ref.trees_]
    for t in range(f.n_trees):
        mine = sorted((int(f.feat[t, i]), float(f.thr[t, i]))
                      for i in range(f.n_nodes[t]) if f.feat[t, i] >= 0)
        assert mine == ref.split_multiset()[t]
    # identical predictions on train and unseen rows (leaf values are the
    # same weighted means accumulated in a different but equivalent order,
    # so they agree to the last ulp, not bitwise)
    np.testing.assert_allclose(rf.predict(X), ref.predict(X),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(rf.predict(Xq), ref.predict(Xq),
                               rtol=1e-12, atol=1e-12)


def test_grower_handles_constant_target_and_tiny_data():
    X = np.array([[0.0], [1.0], [2.0]])
    rf = RandomForestRegressor(n_estimators=3, seed=0).fit(X, np.ones(3))
    np.testing.assert_allclose(rf.predict(X), np.ones(3))
    assert all(n == 1 for n in rf.forest_.n_nodes)      # no splits grown
    rf1 = RandomForestRegressor(n_estimators=2, seed=0).fit(X[:1], [5.0])
    np.testing.assert_allclose(rf1.predict(X), 5.0)


def test_grower_feature_subsampling_stays_deterministic():
    X, y, Xq = _forest_data()
    kw = dict(n_estimators=5, max_features="sqrt", seed=9)
    p1 = RandomForestRegressor(**kw).fit(X, y).predict(Xq)
    p2 = RandomForestRegressor(**kw).fit(X, y).predict(Xq)
    np.testing.assert_array_equal(p1, p2)
    # sqrt-subsampled forests differ from all-features forests
    p3 = RandomForestRegressor(n_estimators=5, seed=9).fit(X, y).predict(Xq)
    assert not np.array_equal(p1, p3)


# ---------------------------------------------------------------------------
# packed-forest kernel: Pallas vs numpy traversal
# ---------------------------------------------------------------------------


def _one_group(X, f, thr, value):
    """The grouped Pallas kernel over a single forest (G=1)."""
    return forest_eval.leaf_values_grouped_pallas(
        X, np.zeros(len(X), np.int64), f.feat[None], thr[None],
        f.left[None], f.right[None], value[None], depth=[f.depth])


def test_forest_eval_pallas_matches_numpy_exactly():
    X, y, Xq = _forest_data(n=120, d=4, seed=7)
    f = RandomForestRegressor(n_estimators=9, seed=2).fit(X, y).forest_
    # quantize to the kernel dtype so BOTH backends route in float32 —
    # then leaf values must agree bit-for-bit
    X32 = Xq.astype(np.float32)
    thr32 = f.thr.astype(np.float32)
    val32 = f.value.astype(np.float32)
    v_np = forest_eval.leaf_values_numpy(X32, f.feat, thr32, f.left,
                                         f.right, val32)
    v_pl = _one_group(X32, f, thr32, val32)
    np.testing.assert_array_equal(v_np.astype(np.float32), v_pl)


def test_forest_eval_pallas_blocking_covers_ragged_rows():
    """Rows spanning several row blocks, the last one ragged, answer as
    they do alone: routing never mixes rows across block padding."""
    X, y, _ = _forest_data(n=80, d=3, seed=11)
    f = RandomForestRegressor(n_estimators=4, seed=4).fit(X, y).forest_
    n = 2 * forest_eval.LANES + 44
    Xq = np.random.default_rng(0).normal(size=(n, 3)).astype(np.float32)
    thr32, val32 = f.thr.astype(np.float32), f.value.astype(np.float32)
    v_full = _one_group(Xq, f, thr32, val32)
    v_blocked = np.concatenate([_one_group(Xq[:13], f, thr32, val32),
                                _one_group(Xq[13:], f, thr32, val32)],
                               axis=1)
    np.testing.assert_array_equal(v_full, v_blocked)
    assert v_full.shape == (4, n)


def test_forest_predict_backends_agree_and_rejects_unknown():
    X, y, Xq = _forest_data(n=100, d=5, seed=13)
    f = RandomForestRegressor(n_estimators=7, seed=1).fit(X, y).forest_
    args = (Xq, f.feat, f.thr, f.left, f.right, f.value)
    p_np = forest_eval.predict(*args, depth=f.depth, backend="numpy")
    p_pl = forest_eval.predict(*args, depth=f.depth, backend="pallas")
    np.testing.assert_allclose(p_pl, p_np, rtol=1e-5)
    with pytest.raises(ValueError, match="backend"):
        forest_eval.predict(*args, depth=f.depth, backend="cuda")


# ---------------------------------------------------------------------------
# vmapped multi-target DNN vs sequential per-target fits
# ---------------------------------------------------------------------------


def test_multi_target_dnn_matches_sequential_fits():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(120, 5))
    w = rng.normal(size=5)
    base = X @ w + 3.0
    Y = np.stack([base, 2.0 * base + 1.0, np.abs(base) + 0.5])
    joint = fit_dnn_multi(X, Y, epochs=60, seed=0)
    for k in range(Y.shape[0]):
        seq = DNNRegressor(epochs=60, seed=0).fit(X, Y[k])
        pj, ps = joint[k].predict(X), seq.predict(X)
        # identical init + identical minibatch plan; only vmap-batched float
        # reassociation separates the two paths
        np.testing.assert_allclose(pj, ps, rtol=2e-3, atol=2e-3)
        # equivalence is the point; the loose MAPE bound only guards against
        # both paths failing identically (targets cross zero, so MAPE is high)
        assert mape(Y[k], pj) < 35.0


def test_multi_target_scales_each_target_independently():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(80, 3))
    y = X @ rng.normal(size=3) + 5.0
    models = fit_dnn_multi(X, np.stack([y, 1000.0 * y]), epochs=40, seed=0)
    assert mape(y, models[0].predict(X)) < 30.0
    assert mape(1000.0 * y, models[1].predict(X)) < 30.0


# ---------------------------------------------------------------------------
# minibatch plan: the dropped-tail regression
# ---------------------------------------------------------------------------


def test_epoch_batches_cover_every_sample_every_epoch():
    n, bs, epochs = 10, 4, 3
    batches = epoch_batches(np.random.default_rng(0), n, bs, epochs)
    nb = -(-n // bs)
    assert batches.shape == (epochs * nb, bs)
    for e in range(epochs):
        seen = set(batches[e * nb:(e + 1) * nb].ravel().tolist())
        assert seen == set(range(n))     # pre-fix: at most n - n % bs seen
    # the pre-fix loop dropped the tail whenever n % bs != 0
    old_steps = len(range(0, n - bs + 1, bs))
    assert old_steps * bs < n <= nb * bs


def test_epoch_batches_exact_when_divisible():
    batches = epoch_batches(np.random.default_rng(0), 8, 4, 2)
    assert batches.shape == (4, 4)
    for e in range(2):
        assert set(batches[2 * e:2 * e + 2].ravel().tolist()) == set(range(8))


def test_dnn_fit_trains_on_tail_heavy_shapes():
    # n just over one batch: the pre-fix loop ran ONE step per epoch and
    # never touched bs..n-1 within an epoch
    rng = np.random.default_rng(2)
    X = rng.normal(size=(130, 4))
    y = X @ rng.normal(size=4) + 10.0     # strictly positive, latency-like
    m = DNNRegressor(epochs=80, batch_size=128, seed=0).fit(X, y)
    pred = m.predict(X)
    assert np.all(np.isfinite(pred))
    # must beat the constant-mean predictor: impossible without real steps
    assert np.sqrt(np.mean((pred - y) ** 2)) < np.std(y)


# ---------------------------------------------------------------------------
# packed-forest pickling
# ---------------------------------------------------------------------------


def test_forest_pickle_roundtrip_preserves_predictions():
    X, y, Xq = _forest_data()
    rf = RandomForestRegressor(n_estimators=5, seed=6).fit(X, y)
    clone = pickle.loads(pickle.dumps(rf))
    assert isinstance(clone.forest_, PackedForest)
    np.testing.assert_array_equal(clone.predict(Xq), rf.predict(Xq))


def test_forest_rejects_legacy_node_list_state():
    rf = RandomForestRegressor.__new__(RandomForestRegressor)
    with pytest.raises(LegacyForestError, match="refit"):
        rf.__setstate__({"trees": [], "n_estimators": 10})
    with pytest.raises(LegacyForestError, match="refit"):
        rf.__setstate__({"__forest_pack_schema__": 1, "forest_": None})
    with pytest.raises(LegacyForestError, match="missing"):
        PackedForest.from_state({"feat": np.zeros((1, 1)), "depth": 0})


def test_v1_tombstones_raise_on_unpickle():
    # a schema-v1 artifact stream restores _Tree/_Node instances by calling
    # __setstate__ with the old attribute dict — the tombstones make that a
    # clear "refit required" error instead of a silent re-pack
    from repro.core import regressors
    for cls, state in ((regressors._Tree, {"nodes": [], "max_depth": 24}),
                       (regressors._Node, {"feature": 0})):
        obj = cls.__new__(cls)
        with pytest.raises(LegacyForestError, match="schema v1"):
            obj.__setstate__(state)
