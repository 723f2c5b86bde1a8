"""Optimizer, checkpointing, trainer, fault tolerance, compression, data."""
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as CB
from repro.data import pipeline as DP
from repro.distributed import compression as COMP
from repro.train import checkpoint as CKPT
from repro.train import optimizer as OPT
from repro.train.fault_tolerance import (FailureInjector, SimulatedPreemption,
                                         run_with_recovery)
from repro.train.trainer import Trainer, TrainConfig


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_minimizes_quadratic():
    hp = OPT.OptHParams(learning_rate=0.1, weight_decay=0.0, warmup_steps=0,
                        decay_steps=1000, grad_clip=1e9)
    params = {"w": jnp.array([5.0, -3.0])}
    state = OPT.init_state(params, hp)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = OPT.apply_updates(params, grads, state, hp)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_lr_schedule_warmup_then_cosine():
    hp = OPT.OptHParams(learning_rate=1.0, warmup_steps=10, decay_steps=100,
                        min_lr_ratio=0.1)
    lr = lambda s: float(OPT.lr_schedule(hp, jnp.asarray(s)))
    assert lr(5) == pytest.approx(0.5)
    assert lr(10) == pytest.approx(1.0, abs=0.01)
    assert lr(100) == pytest.approx(0.1, abs=0.01)
    assert lr(55) < lr(20)


def test_bf16_optimizer_state():
    hp = OPT.OptHParams(state_dtype="bfloat16")
    params = {"w": jnp.zeros((4, 4))}
    state = OPT.init_state(params, hp)
    assert state["m"]["w"].dtype == jnp.bfloat16
    grads = {"w": jnp.ones((4, 4))}
    _, state, _ = OPT.apply_updates(params, grads, state, hp)
    assert state["m"]["w"].dtype == jnp.bfloat16


def test_grad_clipping_bounds_update():
    hp = OPT.OptHParams(learning_rate=1.0, grad_clip=1.0, warmup_steps=0,
                        weight_decay=0.0)
    params = {"w": jnp.zeros(3)}
    state = OPT.init_state(params, hp)
    _, _, metrics = OPT.apply_updates(params, {"w": jnp.full(3, 1e6)}, state,
                                      hp)
    assert float(metrics["grad_norm"]) > 1e5  # reported pre-clip


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    CKPT.save(tmp_path, 3, t)
    out = CKPT.restore(tmp_path, 3, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_prune(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        CKPT.save(tmp_path, s, t, keep=2)
    assert CKPT.all_steps(tmp_path) == [4, 5]
    assert CKPT.latest_step(tmp_path) == 5
    step, out = CKPT.restore_latest(tmp_path, t)
    assert step == 5


def test_checkpoint_no_partial_publish(tmp_path):
    """A leftover .tmp dir is never listed as a valid checkpoint."""
    t = _tree()
    CKPT.save(tmp_path, 1, t)
    (tmp_path / "step_2.tmp").mkdir()
    assert CKPT.all_steps(tmp_path) == [1]


def test_checkpoint_shape_mismatch_raises(tmp_path):
    t = _tree()
    CKPT.save(tmp_path, 1, t)
    bad = dict(t, a=jnp.zeros((3, 3)))
    with pytest.raises(ValueError):
        CKPT.restore(tmp_path, 1, bad)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_deterministic_and_seekable():
    cfg = CB.get_config("llama3_2_1b", smoke=True)
    p1 = DP.make_pipeline(cfg, seq_len=16, global_batch=4, seed=1)
    p2 = DP.make_pipeline(cfg, seq_len=16, global_batch=4, seed=1)
    b0, b1 = next(p1), next(p1)
    p2.skip_to(1)
    np.testing.assert_array_equal(next(p2)["tokens"], b1["tokens"])
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_data_labels_are_shifted_tokens():
    cfg = CB.get_config("llama3_2_1b", smoke=True)
    b = DP.make_pipeline(cfg, seq_len=16, global_batch=2).batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_data_host_sharding_partitions_batch():
    cfg = CB.get_config("llama3_2_1b", smoke=True)
    full = DP.make_pipeline(cfg, seq_len=8, global_batch=4).batch_at(0)
    parts = [DP.make_pipeline(cfg, seq_len=8, global_batch=4, num_hosts=2,
                              host_id=h).batch_at(0) for h in (0, 1)]
    stacked = np.concatenate([p["tokens"] for p in parts])
    np.testing.assert_array_equal(stacked, full["tokens"])


def test_data_modality_stubs():
    vlm = CB.get_config("llama3_2_vision_90b", smoke=True)
    b = DP.make_pipeline(vlm, seq_len=8, global_batch=2).batch_at(0)
    assert b["patches"].shape == (2, vlm.num_patches, vlm.d_model)
    aud = CB.get_config("whisper_tiny", smoke=True)
    b = DP.make_pipeline(aud, seq_len=8, global_batch=2).batch_at(0)
    assert b["frames"].shape == (2, aud.encoder_seq, aud.d_model)


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_cfg():
    return CB.get_config("llama3_2_1b", smoke=True)


def test_trainer_loss_decreases(smoke_cfg):
    tc = TrainConfig(seq_len=64, global_batch=8, num_steps=30, log_every=0)
    tr = Trainer(smoke_cfg, tc)
    tr.run()
    first = np.mean([h["loss"] for h in tr.history[:5]])
    last = np.mean([h["loss"] for h in tr.history[-5:]])
    assert last < first


def test_grad_accum_matches_single_batch(smoke_cfg):
    """microbatches=2 over one batch == microbatches=1 (same data, same
    update, modulo f32 reduction order)."""
    tc1 = TrainConfig(seq_len=32, global_batch=4, num_steps=1, log_every=0,
                      microbatches=1, seed=3)
    tc2 = TrainConfig(seq_len=32, global_batch=4, num_steps=1, log_every=0,
                      microbatches=2, seed=3)
    t1, t2 = Trainer(smoke_cfg, tc1), Trainer(smoke_cfg, tc2)
    batch = next(t1.data)
    m1 = t1.train_one(batch)
    m2 = t2.train_one(batch)
    assert m1["loss"] == pytest.approx(m2["loss"], rel=2e-2)
    for a, b in zip(jax.tree.leaves(t1.params), jax.tree.leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-3, rtol=5e-2)


def test_trainer_checkpoint_resume(tmp_path, smoke_cfg):
    tc = TrainConfig(seq_len=32, global_batch=4, num_steps=10, log_every=0,
                     ckpt_every=5, ckpt_dir=str(tmp_path))
    tr = Trainer(smoke_cfg, tc)
    tr.run()
    tr2 = Trainer(smoke_cfg, tc)
    assert tr2.maybe_restore()
    assert tr2.step == 10
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fault_tolerance_recovers(tmp_path, smoke_cfg):
    inj = FailureInjector([4, 9])

    def mk(attempt):
        tc = TrainConfig(seq_len=32, global_batch=4, num_steps=12,
                         log_every=0, ckpt_every=3, ckpt_dir=str(tmp_path))
        return Trainer(smoke_cfg, tc)

    rep = run_with_recovery(mk, 12, injector=inj)
    assert rep.restarts == 2
    assert rep.completed_steps == 12
    assert rep.preemptions == [4, 9]
    assert np.isfinite(rep.final_metrics["loss"])


def test_elastic_restore_across_meshes(tmp_path, smoke_cfg):
    """Save un-meshed, restore with explicit shardings (1-device mesh) —
    the elastic re-mesh path in miniature."""
    tc = TrainConfig(seq_len=32, global_batch=4, num_steps=2, log_every=0,
                     ckpt_every=2, ckpt_dir=str(tmp_path))
    tr = Trainer(smoke_cfg, tc)
    tr.run()
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    from repro.distributed import sharding as SH
    p_sh = SH.tree_param_shardings(tr.axes, mesh, tr.params)
    step, out = CKPT.restore_latest(
        tmp_path, {"params": tr.params, "opt": tr.opt_state,
                   "data_index": jnp.int32(0)},
        shardings={"params": p_sh,
                   "opt": jax.tree.map(lambda _: None, tr.opt_state),
                   "data_index": None})
    assert step == 2


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (128,))
    q, s = COMP.quantize_int8(x)
    err = jnp.abs(COMP.dequantize(q, s) - x).max()
    assert float(err) <= float(s) / 2 + 1e-7


def test_error_feedback_is_unbiased_over_steps():
    """With a CONSTANT gradient, EF quantization's cumulative output over T
    steps converges to T*g (error never accumulates)."""
    g = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.1
    r = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    T = 50
    for _ in range(T):
        q, s, r = COMP.ef_quantize(g, r)
        total = total + COMP.dequantize(q, s)
    np.testing.assert_allclose(total / T, g, atol=float(s) / 2 + 1e-6)


def test_compressed_psum_single_axis():
    from jax.sharding import AxisType, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    g = {"w": jnp.linspace(-1, 1, 16).reshape(4, 4)}
    r = COMP.init_residuals(g)

    def f(g, r):
        return COMP.compressed_psum(g, r, "pod")

    out, new_r = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()))(g, r)
    np.testing.assert_allclose(out["w"], g["w"], atol=2e-2)


def test_compression_error_small_for_smooth_grads():
    g = jax.random.normal(jax.random.PRNGKey(2), (1024,))
    assert COMP.compression_error(g) < 0.01
