"""A mixture-of-experts decoder's module of the training harness's
interface (``chipbench/trainstep.py`` lists it): what a training
configuration of the program's ``moe`` family brings as a file of its own,
named by the configuration's ``"reference"``. The tests run it at the
smoke widths of the program's ``dbrx-132b``.

The reference is float32 ``jax.numpy`` and imports nothing of the program;
only :func:`program_config` imports ``repro``, inside the function. Norms,
rotary positions, attention, the numerics and AdamW are ``lmref``'s. Each
layer: RMSNorm, causal GQA attention, RMSNorm, then a mixture of SwiGLU
experts. A softmax router scores every expert; each token takes the top
``k`` of its gates, renormalised to sum to one, and its output is the
weighted sum of those experts' outputs. No token is dropped: every expert
is computed for every token and weighted zero where it was not chosen.
The loss is the mean next-token cross entropy through an untied output
head, plus the router's two auxiliary terms, each summed over the layers,
divided by the depth and weighed as the configuration's ``aux_loss``
says: the load balance ``E * sum_e (mean gate of e) * (share of tokens
whose first choice is e)``, and the z-loss, the mean square of the log-sum-
exp of the router's logits.

Layout (layers stacked on a leading axis): ``tok (V, d)``, ``out (d, V)``,
``wq (L, d, H, hd)``, ``wk``/``wv (L, d, KV, hd)``, ``wo (L, H, hd, d)``,
``router (L, d, E)``, ``wi``/``wu (L, E, d, F)``, ``wd (L, E, F, d)``,
``ln1``/``ln2 (L, d)``, ``final_norm (d,)``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench import lmref
from chipbench.lmref import CONTROL, REFERENCE, Adam, Numerics  # noqa: F401

STACKED = ("wq", "wk", "wv", "wo", "router", "wi", "wu", "wd", "ln1", "ln2")
LEAVES = ("tok", "out") + STACKED + ("final_norm",)
NORMS = ("ln1", "ln2", "final_norm")
PROGRAM_PATHS = {
    "tok": ("embed", "tok"), "out": ("embed", "out"),
    "wq": ("blocks", "attn", "wq"), "wk": ("blocks", "attn", "wk"),
    "wv": ("blocks", "attn", "wv"), "wo": ("blocks", "attn", "wo"),
    "router": ("blocks", "ffn", "router"), "wi": ("blocks", "ffn", "wi"),
    "wu": ("blocks", "ffn", "wu"), "wd": ("blocks", "ffn", "wd"),
    "ln1": ("blocks", "ln1"), "ln2": ("blocks", "ln2"),
    "final_norm": ("final_norm",)}
# the program pads the embedding's rows and the head's columns
VOCAB_AXES = {"tok": 0, "out": 1}

leaf_norms = functools.partial(lmref.leaf_norms, leaves=LEAVES,
                               stacked=STACKED)


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    experts: int
    top_k: int
    load_balance: float
    router_z: float
    eps: float
    rope_theta: float


def dims(cfg: dict) -> Dims:
    return Dims(
        vocab=cfg["vocab_size"], d=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ff=cfg["intermediate_size"], experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"],
        load_balance=cfg["aux_loss"]["load_balance"],
        router_z=cfg["aux_loss"]["router_z"], eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"])


def program_config(cfg: dict):
    """The program's registered architecture with the depth and numerics
    the file states, and a capacity factor of E/k: each expert then has a
    slot for every token of a row, so the program drops none, as the
    reference does not. Checked against every width the file gives and
    against the program's auxiliary weights."""
    from repro.configs import base
    from repro.models import model
    job = cfg["job"]
    mc = base.get_config(cfg["arch"])
    mc = dataclasses.replace(
        mc, num_layers=cfg["num_hidden_layers"], dtype=job["compute_dtype"],
        param_dtype=job["param_dtype"], remat=job["remat"] != "none",
        remat_policy=job["remat"],
        capacity_factor=mc.num_experts / mc.num_experts_per_tok)
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab_size": cfg["vocab_size"],
            "num_experts": cfg["num_local_experts"],
            "num_experts_per_tok": cfg["num_experts_per_tok"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "norm_eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
            "qkv_bias": cfg["attention_bias"], "family": "moe"}
    got = {k: getattr(mc, k) for k in want}
    if got != want or dict(model.AUX_COEFS) != cfg["aux_loss"]:
        raise RuntimeError(f"the program's {cfg['arch']} is not the "
                           f"configuration: {got}, {model.AUX_COEFS} != "
                           f"{want}, {cfg['aux_loss']}")
    return mc


def step_flops(dm: Dims, batch: int, seq: int, causal: bool = True) -> int:
    """Model FLOPs of one training step: 6 per weight per token of every
    product a token passes through (attention, the router, its ``k``
    experts and not the others, the output head), plus the attention's two
    products three times over, of which a causal mask needs half. The
    embedding's lookup is no product; recomputation, and expert slots the
    program computes beyond the ``k`` chosen, are not counted."""
    weights = dm.layers * (
        2 * dm.d * dm.heads * dm.head_dim + 2 * dm.d * dm.kv_heads
        * dm.head_dim + dm.d * dm.experts + dm.top_k * 3 * dm.d * dm.ff) \
        + dm.d * dm.vocab
    attention = dm.layers * batch * 2 * 2 * seq * seq * dm.heads \
        * dm.head_dim
    return 6 * weights * batch * seq + 3 * (attention // 2 if causal
                                            else attention)


def shapes(dm: Dims) -> Dict[str, tuple]:
    L, d, H, KV, hd, E, F = (dm.layers, dm.d, dm.heads, dm.kv_heads,
                             dm.head_dim, dm.experts, dm.ff)
    return {"tok": (dm.vocab, d), "out": (d, dm.vocab),
            "wq": (L, d, H, hd), "wk": (L, d, KV, hd), "wv": (L, d, KV, hd),
            "wo": (L, H, hd, d), "router": (L, d, E), "wi": (L, E, d, F),
            "wu": (L, E, d, F), "wd": (L, E, F, d), "ln1": (L, d),
            "ln2": (L, d), "final_norm": (d,)}


def init_weights(key, dm: Dims) -> Dict[str, jax.Array]:
    """Float32 weights from ``key``: each matrix a normal truncated at two
    standard deviations over the root of its fan-in; norm scales one."""
    fan_in = {"wo": dm.heads * dm.head_dim, "wd": dm.ff}
    out = {}
    for i, (name, shape) in enumerate(shapes(dm).items()):
        if name in NORMS:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jax.random.truncated_normal(
                jax.random.fold_in(key, i), -2.0, 2.0, shape,
                jnp.float32) / math.sqrt(fan_in.get(name, dm.d))
    return out


def experts(h, p, dm: Dims, num: Numerics):
    """The mixture's output for ``h (B, S, d)``, and its auxiliary loss."""
    logits = num.mm("bsd,de->bse", h, p["router"])
    gates = jax.nn.softmax(logits, -1)
    top, chosen = jax.lax.top_k(gates, dm.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(chosen, dm.experts) * top[..., None], -2)
    g = num.mm("bsd,edf->bsef", h, p["wi"])
    u = num.mm("bsd,edf->bsef", h, p["wu"])
    y = num.mm("bsef,efd->bsed", jax.nn.silu(g) * u, p["wd"])
    first = jax.nn.one_hot(chosen[..., 0], dm.experts)
    balance = dm.experts * jnp.sum(jnp.mean(gates, (0, 1))
                                   * jnp.mean(first, (0, 1)))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return (jnp.sum(weight[..., None] * y, -2),
            dm.load_balance * balance + dm.router_z * z)


def layer(x, p, dm: Dims, num: Numerics):
    h = lmref.rms_norm(x, p["ln1"], dm.eps)
    q = lmref.rope(num.mm("bsd,dhk->bshk", h, p["wq"]), dm.rope_theta)
    k = lmref.rope(num.mm("bsd,dhk->bshk", h, p["wk"]), dm.rope_theta)
    v = num.mm("bsd,dhk->bshk", h, p["wv"])
    x = x + num.mm("bshk,hkd->bsd", lmref.attention(q, k, v, num), p["wo"])
    out, aux = experts(lmref.rms_norm(x, p["ln2"], dm.eps), p, dm, num)
    return x + out, aux


def loss(params, tokens, labels, dm: Dims, num: Numerics = REFERENCE):
    """Mean next-token cross entropy over every position, plus the
    router's auxiliary terms over the depth."""
    def body(x, p):
        return jax.checkpoint(lambda x, p: layer(x, p, dm, num))(x, p)

    x, aux = jax.lax.scan(body, params["tok"][tokens],
                          {k: params[k] for k in STACKED})
    x = lmref.rms_norm(x, params["final_norm"], dm.eps)
    logits = num.mm("bsd,dv->bsv", x, params["out"])
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return (jnp.mean(jax.nn.logsumexp(logits, -1) - gold)
            + jnp.sum(aux) / dm.layers)


def leaf_names(dm: Dims):
    return lmref.leaf_names(dm, LEAVES, STACKED)


def readings(key, batches, dm: Dims, a: Adam, num: Numerics = REFERENCE):
    """``lmref.readings`` of this model."""
    return lmref.train_readings(key, batches, lambda k: init_weights(k, dm),
                                lambda p, t, y: loss(p, t, y, dm, num),
                                leaf_norms, a)
