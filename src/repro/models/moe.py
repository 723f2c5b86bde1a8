"""Mixture-of-Experts FFN with capacity-based top-k routing (GShard style).

Partitioning:
  - ``expert`` mode (num_experts divisible by the model axis, e.g. DBRX 16e on
    a 16-way axis): expert dimension is sharded over ``model`` — true expert
    parallelism; the token dispatch reshard lowers to an all-to-all.
  - ``ffn`` mode (e.g. Grok 8e on a 16-way axis): experts replicated across the
    axis, per-expert d_ff sharded over ``model`` (tensor parallelism inside
    each expert).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.layers import dense_init, split_tree

# Production mesh model-axis size (both assigned meshes use 16).
MODEL_AXIS_SIZE = 16


def partition_mode(num_experts: int) -> str:
    return "expert" if num_experts % MODEL_AXIS_SIZE == 0 else "ffn"


def moe_init(key, d_model, d_ff, num_experts, dtype=jnp.float32):
    mode = partition_mode(num_experts)
    e_ax = "expert" if mode == "expert" else "expert_ffn"
    f_ax = "mlp_ep" if mode == "expert" else "mlp"
    ks = jax.random.split(key, 4)
    return split_tree({
        "router": dense_init(ks[0], (d_model, num_experts), ("embed", None), dtype),
        "wi": dense_init(ks[1], (num_experts, d_model, d_ff),
                         (e_ax, "embed", f_ax), dtype),
        "wu": dense_init(ks[2], (num_experts, d_model, d_ff),
                         (e_ax, "embed", f_ax), dtype),
        "wd": dense_init(ks[3], (num_experts, d_ff, d_model),
                         (e_ax, f_ax, "embed"), dtype),
    })


# ---------------------------------------------------------------------------
# dispatch / combine with controlled transposes
#
# XLA's generic transpose of the combine gather is a scatter the SPMD
# partitioner handles badly (f32 (G, S*k, D) collective-permutes / all-
# reduces, measured ~6.4e12 bytes/step on dbrx). Both directions are given
# explicitly via custom_vjp so forward AND backward run the local
# (expert-replicated, batch-parallel) gather/scatter with an explicit
# reshard — the transpose of a gather is a scatter-add with the SAME
# indices, and slot indices are unique per (group, expert, capacity) slot,
# so bf16 accumulation is exact (only masked zeros ever collide).
# ---------------------------------------------------------------------------


def _batch_shard_map(fn, mesh, n_in):
    """Run ``fn`` with every arg/out sharded on dim 0 over the batch axes and
    replicated elsewhere. A shard_map region is OPAQUE to the SPMD
    partitioner, so the data-dependent scatter/gather inside executes
    locally per batch shard — no partitioner fallback possible."""
    from jax.sharding import PartitionSpec as P

    batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = P(batch)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                         out_specs=spec, check_vma=False)


def _make_dispatch_combine(E, capacity):
    from repro.distributed.sharding import current_mesh

    def dispatch_local(src, flat_e, pos):
        G = src.shape[0]
        g_idx = jnp.arange(G)[:, None]
        out = jnp.zeros((G, E, capacity, src.shape[-1]), src.dtype)
        return out.at[g_idx, flat_e, pos].add(src)

    def combine_local(eo, flat_e, pos):
        g_idx = jnp.arange(eo.shape[0])[:, None]
        return eo[g_idx, flat_e, pos]

    mesh = current_mesh()
    if mesh is None:
        return dispatch_local, combine_local

    @jax.custom_vjp
    def dispatch(src, flat_e, pos):
        return _batch_shard_map(dispatch_local, mesh, 3)(src, flat_e, pos)

    def dispatch_fwd(src, flat_e, pos):
        return dispatch(src, flat_e, pos), (flat_e, pos)

    def dispatch_bwd(res, ct):
        flat_e, pos = res
        # keep the resharded cotangent in bf16: the (G,E,C,D) all-gather at
        # the expert-parallel boundary is half the bytes vs f32
        return combine(ct.astype(jnp.bfloat16), flat_e, pos), None, None

    @jax.custom_vjp
    def combine(eo, flat_e, pos):
        return _batch_shard_map(combine_local, mesh, 3)(eo, flat_e, pos)

    def combine_fwd(eo, flat_e, pos):
        return combine(eo, flat_e, pos), (flat_e, pos)

    def combine_bwd(res, ct):
        flat_e, pos = res
        return dispatch(ct.astype(jnp.bfloat16), flat_e, pos), None, None

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)
    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def moe_apply(p, x, cfg):
    """x: (B, S, D). Returns (out, aux) where aux carries load-balance and
    router-z losses (added to the training loss with small coefficients)."""
    B, S, D = x.shape
    E = cfg.num_experts
    k = cfg.num_experts_per_tok
    capacity = max(1, int(S * k / E * cfg.capacity_factor))
    mode = partition_mode(E)
    e_ax = "expert" if mode == "expert" else None

    logits = jnp.einsum("gsd,de->gse", x, p["router"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)              # (G,S,E)
    topv, topi = jax.lax.top_k(gates, k)                 # (G,S,k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # --- aux losses (Switch-style) ---
    me = jnp.mean(gates, axis=(0, 1))                            # mean gate prob
    ce = jnp.mean(jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = {
        "load_balance": E * jnp.sum(me * ce),
        "router_z": jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2),
    }

    # --- position-in-expert via cumulative count over flattened (S*k) choices
    flat_e = topi.reshape(B, S * k)                      # (G, S*k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (G, S*k, E)
    pos = jnp.cumsum(onehot, axis=1) - 1
    pos_in_e = jnp.take_along_axis(pos, flat_e[..., None], -1)[..., 0]
    keep = pos_in_e < capacity                           # capacity drop mask
    pos_in_e = jnp.minimum(pos_in_e, capacity - 1)

    w_flat = topv.reshape(B, S * k) * keep.astype(jnp.float32)

    # --- dispatch: (G, E, C, D)
    # jnp.repeat == x[:, repeat(arange(S), k), :] but lowers to
    # broadcast+reshape instead of a constant-index gather: the gather form
    # defeats SPMD batch propagation and replicates the (B, S*k, D) tensor
    # on every device (measured f32[256,16384,6144] full-batch fusions).
    src = jnp.repeat(x, k, axis=1) * keep[..., None].astype(x.dtype)
    src = constrain(src, "batch", None, None)
    dispatch, combine = _make_dispatch_combine(E, capacity)
    # batch-parallel scatter (experts replicated), then reshard to expert
    # parallelism for the FFN — see _make_dispatch_combine
    dispatched = dispatch(src, flat_e, pos_in_e).astype(x.dtype)
    dispatched = constrain(dispatched, "batch", e_ax, None, None)

    # --- expert FFN
    gi = jnp.einsum("gecd,edf->gecf", dispatched, p["wi"].astype(x.dtype))
    up = jnp.einsum("gecd,edf->gecf", dispatched, p["wu"].astype(x.dtype))
    h = jax.nn.silu(gi) * up
    h = constrain(h, "batch", e_ax, None, "mlp" if mode == "ffn" else None)
    eo = jnp.einsum("gecf,efd->gecd", h, p["wd"].astype(x.dtype))
    eo = constrain(eo, "batch", e_ax, None, None)

    # --- combine back to (G, S, D): expert-replicating gather with a
    # controlled transpose (see _make_dispatch_combine)
    gathered = combine(eo.astype(x.dtype), flat_e, pos_in_e)  # (G, S*k, D)
    gathered = gathered * w_flat[..., None].astype(x.dtype)
    # sum the k expert choices per token: reshape (G, S, k, D) -> sum over k
    # (the scatter-add form with repeated indices replicates, this doesn't)
    out = gathered.reshape(B, S, k, D).sum(axis=2)
    return constrain(out, "batch", "seq", None), aux
