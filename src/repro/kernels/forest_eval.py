"""Packed-forest batch inference: evaluate a whole stacked ``(n_trees,
n_nodes)`` CART forest over a row block in one launch.

Two backends behind one ``predict``:

  - ``numpy``  — float64 iterative routing, the exact production CPU path
    (bit-identical per-row vs batched, which ``bench_grid`` relies on);
  - ``pallas`` — one grouped kernel launch on TPU (float32): each grid step
    holds one group's 8-tree tile in VMEM, and a ``fori_loop`` bounded by
    the grown depth routes 8 trees x 128 rows in lockstep through
    ``take_along_axis`` gathers that each stay inside one (8, 128) vreg.

Both backends return per-tree LEAF VALUES ``(n_trees, n_rows)`` from their
inner routine; the tree-mean is taken by the shared ``tree_mean`` in
float64, so the two paths agree exactly whenever their routing agrees (see
``tests/test_fit_path.py`` for the bit-equality check on a float32-quantized
forest).

The GROUPED entry points (``leaf_values_grouped_numpy`` /
``leaf_values_grouped_pallas`` / ``predict_grouped``) evaluate a whole
STACK of forests — ``(n_groups, n_trees, n_nodes)`` arrays, every row
carrying its group id — in ONE launch. This is the ``repro.api.bank``
hot path: a serving wave mixing any number of (anchor, target) pairs costs
one traversal, not one per pair. Because routing gathers and the tree-mean
are elementwise/per-row operations, grouped answers are bit-identical to
running each group's forest separately.

The grouped Pallas launch takes its forest stack one of two ways. On the
HOST path (no ``stack``) every launch pads the ``(G, T, N)`` arrays into
the kernel layout (:func:`pad_forest_stack`) and hands those numpy arrays
to the jitted kernel, which copies the whole stack to the device; the
single-forest :func:`predict` (fit time, the per-group fallback) takes
it. With ``stack=`` the caller passes the five padded arrays already on
the device (:func:`device_forest_stack`, built once per stack), and only
the block vectors and the wave's rows cross per launch. ``ModelBank``
holds one such stack for its lifetime. Both paths launch the same kernel
on the same values, so their answers are bit-identical.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro import obs


def tree_mean(vals: np.ndarray) -> np.ndarray:
    """Float64 mean over the tree axis of ``(n_trees, n_rows)`` leaf values,
    accumulated tree-sequentially so every ROW's result is independent of
    how many other rows ride in the batch. (``np.mean(axis=0)`` is not
    column-stable: its pairwise blocking changes with the row count, so
    per-group and stacked evaluation would disagree in the last ulp.)"""
    vals = np.asarray(vals, np.float64)
    acc = np.zeros(vals.shape[1], np.float64)
    for t in range(vals.shape[0]):
        acc += vals[t]
    return acc / vals.shape[0]


def _auto_backend() -> str:
    """``"pallas"`` where JAX runs on a TPU, the numpy traversal elsewhere."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "numpy"


def leaf_values_numpy(X, feat, thr, left, right, value,
                      depth: Optional[int] = None) -> np.ndarray:
    """Route every row through every tree; returns (n_trees, n_rows) leaf
    values. Comparisons run in the dtype of ``X``/``thr`` as given.

    ``depth`` (the packed forest's grown depth) bounds the traversal
    exactly: after ``depth`` routing steps every node is a leaf, so the
    loop needs no per-iteration liveness re-scan over all trees. Without
    it the traversal falls back to scanning for live nodes each step.
    """
    X = np.asarray(X)
    m = X.shape[0]
    T = feat.shape[0]
    nid = np.zeros((T, m), np.int64)
    cols = np.arange(m)[None, :]
    step = 0
    while True:
        if depth is not None and step >= depth:
            break
        F = np.take_along_axis(feat, nid, axis=1).astype(np.int64)
        live = F >= 0
        if depth is None and not live.any():
            break
        TH = np.take_along_axis(thr, nid, axis=1)
        L = np.take_along_axis(left, nid, axis=1).astype(np.int64)
        R = np.take_along_axis(right, nid, axis=1).astype(np.int64)
        xv = X[cols, np.maximum(F, 0)]
        nid = np.where(live, np.where(xv <= TH, L, R), nid)
        step += 1
    return np.take_along_axis(value, nid, axis=1)


def leaf_values_grouped_numpy(X, gid, feat, thr, left, right, value,
                              depth) -> np.ndarray:
    """Grouped traversal: forest arrays are stacked ``(G, T, N)``, ``gid``
    assigns every row of ``X`` to one group, and ``depth`` is the per-group
    grown depth. Returns ``(T, n_rows)`` leaf values in ROW order, each row
    routed through its own group's forest — one launch for the whole wave.

    Rows are processed deepest-group-first so the active set is always a
    prefix: once a step exceeds a group's depth its rows (already at
    leaves) drop out of the gathers entirely instead of being re-routed
    in place. Routing is elementwise per row, so results are bit-identical
    to per-group :func:`leaf_values_numpy` calls.
    """
    X = np.asarray(X)
    gid = np.asarray(gid, np.int64)
    m = X.shape[0]
    G, T, _ = feat.shape
    depth = np.asarray(depth, np.int64)
    if m == 0:
        return np.empty((T, 0), np.asarray(value).dtype)

    # deepest group first: active columns at step s are the prefix with
    # depth > s (fully-leaf groups — depth 0 — never enter the loop)
    order = np.argsort(-depth[gid], kind="stable")
    gs = gid[order]
    Xs = np.ascontiguousarray(X[order])
    neg = -depth[gs]                      # ascending, for searchsorted

    # flat gather bases: element (t, j) of the stacked arrays lives at
    # gs[j]*T*N + t*N + node — one precomputed base + np.take per gather
    # is several times faster than broadcast 3-array fancy indexing
    N = feat.shape[2]
    base = gs[None, :] * (T * N) + np.arange(T)[:, None] * N   # (T, m)
    d_feats = Xs.shape[1]
    xbase = np.arange(m)[None, :] * d_feats
    feat_f = np.ascontiguousarray(feat).reshape(-1)
    thr_f = np.ascontiguousarray(thr).reshape(-1)
    left_f = np.ascontiguousarray(left).reshape(-1)
    right_f = np.ascontiguousarray(right).reshape(-1)
    value_f = np.ascontiguousarray(value).reshape(-1)
    Xs_f = Xs.reshape(-1)

    nid = np.zeros((T, m), np.int32)   # node ids fit int32; the flat
    max_depth = int(depth.max(initial=0))  # gather index is int64 via base
    for step in range(max_depth):
        k = int(np.searchsorted(neg, -step, side="left"))  # depth > step
        if k == 0:
            break
        sub = nid[:, :k]
        flat = base[:, :k] + sub
        F = feat_f.take(flat)
        live = F >= 0
        TH = thr_f.take(flat)
        L = left_f.take(flat)
        R = right_f.take(flat)
        xv = Xs_f.take(xbase[:, :k] + np.maximum(F, 0))
        nid[:, :k] = np.where(live, np.where(xv <= TH, L, R), sub)
    leaves = value_f.take(base + nid)
    out = np.empty_like(leaves)
    out[:, order] = leaves
    return out


# Kernel tiling. Mosaic gathers (``take_along_axis``) only within one
# (8, 128) vreg, so the kernel works on tiles of SUBLANES trees x LANES rows
# and reads every table in vreg-sized chunks: node tables in LANES-wide
# chunks along the node axis, the transposed feature block in SUBLANES-high
# chunks along the feature axis.
LANES = 128
SUBLANES = 8


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def pad_forest_stack(feat, thr, left, right, value):
    """The kernel layout of a ``(G, T, N)`` forest stack: trees padded to a
    multiple of SUBLANES, nodes to a multiple of LANES, int32/float32.
    Padded nodes are leaves (``feat = -1``) that routing never enters;
    padded trees are a single leaf of value 0 whose rows the caller drops."""
    G, T, N = np.shape(feat)
    Tp, Np = _round_up(T, SUBLANES), _round_up(N, LANES)

    def pad(a, fill, dtype):
        out = np.full((G, Tp, Np), fill, dtype)
        out[:, :T, :N] = a
        return out

    return (pad(feat, -1, np.int32), pad(thr, 0, np.float32),
            pad(left, 0, np.int32), pad(right, 0, np.int32),
            pad(value, 0, np.float32))


def device_forest_stack(feat, thr, left, right, value):
    """The :func:`pad_forest_stack` layout of a ``(G, T, N)`` forest
    stack, placed on the device once (``jax.device_put``): the ``stack=``
    argument of the grouped launch. Counts ``bank.forest_stack_uploads``."""
    import jax
    stack = tuple(jax.device_put(a) for a in
                  pad_forest_stack(feat, thr, left, right, value))
    obs.count("bank.forest_stack_uploads", 1)
    return stack


def grouped_leaf_values(block_gid, block_depth, xt, feat, thr, left, right,
                        value, *, interpret: bool = False):
    """The traceable grouped kernel — a function of shapes only, so it can
    be lowered for a described chip. Grid ``(row_blocks, tree_tiles)``:
    block ``i`` holds LANES rows of one group, ``block_gid[i]`` steers the
    forest BlockSpecs to that group's ``(SUBLANES, N)`` tree tile and
    ``block_depth[i]`` bounds its routing loop (padding blocks carry depth
    0 and route nothing).

    ``xt``: ``(d_pad, n_blocks * LANES)`` float32, features on sublanes,
    rows on lanes; forest arrays in the :func:`pad_forest_stack` layout.
    Returns ``(T_pad, n_blocks * LANES)`` float32 leaf values.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d_pad, n_rows = xt.shape
    _, Tp, Np = feat.shape
    n_blocks = n_rows // LANES

    def take(chunk, idx, axis):
        return jnp.take_along_axis(chunk, idx, axis=axis,
                                   mode="promise_in_bounds")

    def kernel(g_ref, dep_ref, x_ref, f_ref, t_ref, l_ref, r_ref, v_ref,
               o_ref):
        def nodes(ref, nid):
            # chunk c answers node ids >= c*LANES; later chunks overwrite
            out = None
            for c in range(Np // LANES):
                lo = c * LANES
                got = take(ref[0, :, lo:lo + LANES],
                           jnp.clip(nid - lo, 0, LANES - 1), 1)
                out = got if out is None else jnp.where(nid >= lo, got, out)
            return out

        def features(f):
            out = None
            for k in range(d_pad // SUBLANES):
                lo = k * SUBLANES
                got = take(x_ref[lo:lo + SUBLANES, :],
                           jnp.clip(f - lo, 0, SUBLANES - 1), 0)
                out = got if out is None else jnp.where(f >= lo, got, out)
            return out

        def body(_, nid):
            f = nodes(f_ref, nid)                      # (SUBLANES, LANES)
            t = nodes(t_ref, nid)
            nl = nodes(l_ref, nid)
            nr = nodes(r_ref, nid)
            xv = features(jnp.maximum(f, 0))
            return jnp.where(f >= 0, jnp.where(xv <= t, nl, nr), nid)

        nid = jax.lax.fori_loop(0, dep_ref[pl.program_id(0)], body,
                                jnp.zeros((SUBLANES, LANES), jnp.int32))
        o_ref[...] = nodes(v_ref, nid)

    tile = pl.BlockSpec((1, SUBLANES, Np), lambda i, t, g, dep: (g[i], t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks, Tp // SUBLANES),
        in_specs=[pl.BlockSpec((d_pad, LANES), lambda i, t, g, dep: (0, i)),
                  tile, tile, tile, tile, tile],
        out_specs=pl.BlockSpec((SUBLANES, LANES),
                               lambda i, t, g, dep: (t, i)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, n_rows), jnp.float32),
        interpret=interpret,
        name="forest_grouped",
    )(block_gid, block_depth, xt, feat, thr, left, right, value)


_GROUPED_FN = None


def _grouped_fn():
    """The one jitted grouped launch, keyed on shapes. Compiled for the
    device on a TPU backend; the Pallas interpreter elsewhere (a
    correctness tool, not a CPU fast path)."""
    global _GROUPED_FN
    if _GROUPED_FN is None:
        import functools

        import jax
        _GROUPED_FN = jax.jit(functools.partial(
            grouped_leaf_values,
            interpret=jax.default_backend() != "tpu"))
    return _GROUPED_FN


def leaf_values_grouped_pallas(X, gid, feat, thr, left, right, value, *,
                               depth, stack=None) -> np.ndarray:
    """Grouped Pallas traversal: ONE launch over (row-block, tree-tile)
    pairs, float32. Rows are sorted by group and padded per group to LANES
    multiples; the block COUNT is power-of-two bucketed (padding blocks
    carry depth 0), so the launch's static shapes come from a bounded set
    and a warmed executable serves any wave mix. Returns ``(T, n_rows)``
    in original row order.

    ``stack``: the :func:`device_forest_stack` of ``feat .. value``; the
    launch then passes it instead of padding and uploading the stack."""
    from repro.core.regressors import bucket

    X = np.asarray(X)
    gid = np.asarray(gid, np.int64)
    m, d = X.shape
    T = np.shape(feat)[1]
    depth = np.asarray(depth, np.int64)
    if m == 0:
        return np.empty((T, 0), np.float32)

    order = np.argsort(gid, kind="stable")
    groups, counts = np.unique(gid, return_counts=True)
    blocks_per = -(-counts // LANES)
    used = int(blocks_per.sum())
    n_blocks = bucket(used)
    block_gid = np.zeros(n_blocks, np.int32)
    block_gid[:used] = np.repeat(groups, blocks_per)
    block_depth = np.zeros(n_blocks, np.int32)
    block_depth[:used] = depth[block_gid[:used]]
    # padded column of each sorted row: its group's block run + its rank
    run_start = np.repeat(np.cumsum(blocks_per) - blocks_per, counts) * LANES
    rank = np.arange(m) - np.repeat(np.cumsum(counts) - counts, counts)
    pos = run_start + rank
    xt = np.zeros((_round_up(d, SUBLANES), n_blocks * LANES), np.float32)
    xt[:d, pos] = X[order].T

    if stack is None:
        stack = pad_forest_stack(feat, thr, left, right, value)
    args = (block_gid, block_depth, xt, *stack)
    # host arrays cross to the device on every launch; device arrays do not
    obs.count("bank.h2d_bytes", sum(a.nbytes for a in args
                                    if isinstance(a, np.ndarray)))
    obs.count("bank.forest_rows", m)
    obs.count("bank.forest_slots", n_blocks * LANES)
    out = np.asarray(_grouped_fn()(*args))
    res = np.empty((T, m), np.float32)
    res[:, order] = out[:T, pos]
    return res


def warm_grouped(stack, *, n_features: int, max_rows: int) -> None:
    """Compile every block-count bucket a wave of up to ``max_rows`` rows
    can produce over the device ``stack`` the waves will pass (each
    group's rows fill whole blocks, so at most
    ``min(max_rows, G + max_rows / LANES)`` blocks)."""
    from repro.core.regressors import bucket

    G = stack[0].shape[0]
    d_pad = _round_up(n_features, SUBLANES)
    cap = bucket(min(max_rows, G + -(-max_rows // LANES)))
    nb = 1
    while nb <= cap:
        zeros = np.zeros(nb, np.int32)
        np.asarray(_grouped_fn()(zeros, zeros,
                                 np.zeros((d_pad, nb * LANES), np.float32),
                                 *stack))
        nb *= 2


def predict(X, feat, thr, left, right, value, *, depth: int,
            backend: str = "auto") -> np.ndarray:
    """Forest prediction = float64 mean over per-tree leaf values.

    ``backend="auto"`` runs the compiled Pallas kernel on TPU and the exact
    numpy traversal elsewhere; ``"pallas"`` on a single forest is the
    grouped kernel with one group.
    """
    if backend == "auto":
        backend = _auto_backend()
    if backend == "numpy":
        vals = leaf_values_numpy(X, feat, thr, left, right, value,
                                 depth=depth)
    elif backend == "pallas":
        X = np.asarray(X)
        vals = leaf_values_grouped_pallas(
            X, np.zeros(len(X), np.int64), *(np.asarray(a)[None] for a in
                                            (feat, thr, left, right, value)),
            depth=[depth])
    else:
        raise ValueError(f"unknown forest_eval backend {backend!r}")
    return tree_mean(vals)


def predict_grouped(X, gid, feat, thr, left, right, value, *, depth,
                    backend: str = "auto", stack=None) -> np.ndarray:
    """Grouped forest prediction: every row routed through its own group's
    stacked forest, ONE launch + one shared float64 tree-mean. Same backend
    policy as :func:`predict`; ``stack`` (the device-resident layout of
    ``feat .. value``) serves the Pallas launch, the numpy one ignores it."""
    if backend == "auto":
        backend = _auto_backend()
    if backend == "numpy":
        vals = leaf_values_grouped_numpy(X, gid, feat, thr, left, right,
                                         value, depth)
    elif backend == "pallas":
        vals = leaf_values_grouped_pallas(X, gid, feat, thr, left, right,
                                          value, depth=depth, stack=stack)
    else:
        raise ValueError(f"unknown forest_eval backend {backend!r}")
    return tree_mean(vals)
