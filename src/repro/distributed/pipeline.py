"""GPipe-style pipeline parallelism over a mesh axis.

Maps a stack of L identical blocks onto S pipeline stages laid out along a
mesh axis (the multi-pod design point: stages over ``pod``). Microbatches
flow stage-to-stage via ``jax.lax.ppermute`` inside a ``shard_map``; the
schedule is plain GPipe (fill, steady state, drain): T = M + S - 1 ticks for
M microbatches, bubble fraction (S-1)/T.

This is the beyond-paper scaling lever for depth: at 1000+ nodes the layer
scan stops fitting a single pod's HBM, and the ``pod`` axis can carry stages
instead of pure data parallelism. The utility is model-agnostic: it
pipelines any ``block_fn(params_slice, x) -> x`` whose stacked parameters
have a leading layer axis.

Cost model (per microbatch of shape (mb, s, d)): one (mb, s, d) ppermute per
stage boundary per direction — exactly the activations, nothing else crosses
pods.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(block_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stacked_params: Any,
                   x: jnp.ndarray,
                   *,
                   mesh: Mesh,
                   axis: str = "pod",
                   microbatches: int) -> jnp.ndarray:
    """Apply L stacked blocks to ``x`` with pipeline parallelism.

    ``stacked_params``: pytree with leading dim L (L % S == 0); stage s owns
    layers [s*L/S, (s+1)*L/S). ``x``: (B, ...) with B % microbatches == 0.
    Returns block_fn applied L times to x, numerically identical to the
    sequential scan (same order, same dtypes).
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    M = microbatches
    assert B % M == 0, (B, M)
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % S == 0, (L, S)

    mb = B // M
    xm = x.reshape((M, mb) + x.shape[1:])

    # stage-shard the layer axis; microbatches replicated along `axis`
    p_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    other = tuple(a for a in mesh.axis_names if a != axis)

    def stage_body(params_local, xm_local):
        """Runs on ONE stage. params_local: (L/S, ...); xm_local: (M, mb, ...)."""
        idx = jax.lax.axis_index(axis)
        T = M + S - 1
        zeros = jnp.zeros_like(xm_local[0])
        outputs = jnp.zeros_like(xm_local)

        def apply_stage(x_in):
            def one(x, p):
                return block_fn(p, x), None
            out, _ = jax.lax.scan(one, x_in, params_local)
            return out

        def tick(t, carry):
            recv, outputs = carry
            # stage 0 injects microbatch t (if still filling); others use recv
            m_in = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(idx == 0, xm_local[m_in], recv)
            active = (t - idx >= 0) & (t - idx < M)
            y = jnp.where(active, apply_stage(x_in), zeros)
            # last stage banks its finished microbatch (index t - (S-1))
            m_out = jnp.clip(t - (S - 1), 0, M - 1)
            bank = active & (idx == S - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(bank, y, outputs[m_out]), m_out, 0)
            # ship activations one stage downstream (ring permute)
            recv = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return recv, outputs

        _, outputs = jax.lax.fori_loop(0, T, tick, (zeros, outputs))
        # only the last stage banked real outputs; broadcast its buffer to
        # all stages (masked psum) so the result is replicated along `axis`
        outputs = jax.lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    fn = jax.shard_map(stage_body, mesh=mesh,
                       in_specs=(p_specs, P()), out_specs=P(),
                       check_vma=False)
    out = fn(stacked_params, xm)
    return out.reshape((B,) + x.shape[1:])


def bubble_fraction(num_stages: int, microbatches: int) -> float:
    """GPipe pipeline bubble: (S-1) / (M + S - 1)."""
    return (num_stages - 1) / (microbatches + num_stages - 1)
