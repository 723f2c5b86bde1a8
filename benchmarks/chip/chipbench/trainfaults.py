"""Faults a training step can have, planted in the program underneath an
unchanged harness: what ``correct`` has to catch. The CPU tests plant
each in a whole run; ``train_control.py`` reads each at the cell's own
size on the chip, where it sets the upper reading of a limit.

Each is a ``(module, attribute, replacement)`` of the program, looked up
when the step is traced, so a step built while one is planted has it.
"""
from __future__ import annotations

import contextlib

import numpy as np


def faults() -> dict:
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import model as M
    from repro.models import transformer as T
    from repro.train import optimizer as OPT
    loss_fn, forward, apply = M.loss_fn, T.forward, OPT.apply_updates

    def unmasked(q, k, v, **_):
        """Every query against every key, over blocks of 512 query rows
        (the whole score matrix of a 4,096-token step does not fit)."""
        B, S, H, hd = q.shape
        k, v = L._repeat_kv(k, H), L._repeat_kv(v, H)
        n = max(S // 512, 1)
        qb = jnp.moveaxis(q.reshape(B, n, S // n, H, hd), 1, 0)
        out = jax.lax.map(jax.checkpoint(
            lambda qi: L._sdpa(qi, k, v, None, 1.0 / np.sqrt(hd))), qb)
        return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)
    return {
        # the step hands its state back unchanged
        "state_unchanged": (OPT, "apply_updates",
                            lambda p, g, s, hp: (p, s, apply(p, g, s, hp)[2])),
        # half of the batch left out, the mean taken over the rest
        "half_the_batch": (M, "loss_fn", lambda p, c, b: loss_fn(
            p, c, {k: v[:v.shape[0] // 2] for k, v in b.items()})),
        # the last layer never runs
        "layer_skipped": (T, "forward", lambda p, c, b: forward(
            dict(p, blocks=jax.tree.map(lambda a: a[:-1], p["blocks"])),
            c, b)),
        # every position sees the whole sequence
        "attention_unmasked": (L, "causal_attention", unmasked),
        # the loss, the step's answer, altered where it is produced
        "loss_altered": (M, "loss_fn", lambda p, c, b: jax.tree.map(
            lambda x: x * 1.01, loss_fn(p, c, b))),
    }


@contextlib.contextmanager
def planted(name: str):
    obj, attr, value = faults()[name]
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)
