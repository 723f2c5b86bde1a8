"""The dense GQA decoder's module of the training harness's interface
(``trainstep``): the plain float32 reference of its training step, and
what the harness needs beside it to build the program's step and read it
(:func:`dims`, :func:`program_config`, ``PROGRAM_PATHS``, ``VOCAB_AXES``,
:func:`step_flops`). A training configuration names this file under
``"reference"``.

The reference imports nothing of the program; only :func:`program_config`,
which builds the program's side, imports ``repro``, inside the function.
The reference states the arithmetic the way the published Llama-style
description does: RMSNorm before attention and before the MLP, rotary
positions on queries and keys (the two halves of each head rotated against
each other), causal attention whose heads share key/value heads in groups,
a SwiGLU MLP, a final RMSNorm, an output head tied to the embedding table,
the mean next-token cross entropy, and AdamW with global-norm clipping,
linear warm-up and decoupled weight decay.

Where Granite-3.0's published form departs from Llama's, this follows
the Llama form, as the program does: no embedding multiplier (Granite:
12), attention scores over the root of the head size (Granite: 1/64),
residual branches unscaled (Granite: 0.22), logits not divided (Granite:
8); and the decay reaches the layer-stacked RMSNorm scales, which the
program stores as matrices (``Adam.decay``). A cell of such a
configuration is held to the Llama form at its widths.

Every matrix product runs at ``Precision.HIGHEST`` (float32 on the TPU,
not the one-pass bfloat16 that ``DEFAULT`` gives). So that it fits one
chip beside nothing else, each layer is recomputed in the backward pass,
attention is taken over blocks of query rows, and the loss over blocks of
tokens. ``Numerics`` lets the same code run one precision lower (the
control): every matrix product's operands rounded to float8 (e4m3, and
their cotangents e5m2 in the backward pass, one scale per tensor).

The weights are the benchmark's, drawn here from a key
(:func:`init_weights`); the program is handed the same weights. Their
layout (layers stacked on a leading axis):

``tok (V, d)``, ``wq (L, d, H, hd)``, ``wk``/``wv (L, d, KV, hd)``,
``wo (L, H, hd, d)``, ``wi``/``wu (L, d, F)``, ``wd (L, F, d)``,
``ln1``/``ln2 (L, d)``, ``final_norm (d,)``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import roofline

HIGHEST = jax.lax.Precision.HIGHEST
STACKED = ("wq", "wk", "wv", "wo", "wi", "wu", "wd", "ln1", "ln2")
LEAVES = ("tok",) + STACKED + ("final_norm",)
# the program's parameter tree, by the reference's leaf names
PROGRAM_PATHS = {
    "tok": ("embed", "tok"), "wq": ("blocks", "attn", "wq"),
    "wk": ("blocks", "attn", "wk"), "wv": ("blocks", "attn", "wv"),
    "wo": ("blocks", "attn", "wo"), "wi": ("blocks", "ffn", "wi"),
    "wu": ("blocks", "ffn", "wu"), "wd": ("blocks", "ffn", "wd"),
    "ln1": ("blocks", "ln1"), "ln2": ("blocks", "ln2"),
    "final_norm": ("final_norm",)}
# the leaves whose vocabulary axis the program pads past the vocabulary
# (with zeros), by that axis
VOCAB_AXES = {"tok": 0}


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    eps: float = 1e-5
    rope_theta: float = 10_000.0


@dataclasses.dataclass(frozen=True)
class Adam:
    learning_rate: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    decay_steps: int
    min_lr_ratio: float
    decay: Tuple[str, ...]          # leaves weight decay applies to


def dims(cfg: dict) -> Dims:
    """The reference's sizes from the configuration file."""
    return Dims(
        vocab=cfg["vocab_size"], d=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ff=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"])


def program_config(cfg: dict):
    """The program's model configuration for ``cfg``: its registered
    architecture with the depth and numerics the file states, checked
    against every width the file gives."""
    from repro.configs import base
    job = cfg["job"]
    mc = dataclasses.replace(
        base.get_config(cfg["arch"]),
        num_layers=cfg["num_hidden_layers"], dtype=job["compute_dtype"],
        param_dtype=job["param_dtype"], remat=job["remat"] != "none",
        remat_policy=job["remat"])
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab_size": cfg["vocab_size"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "norm_eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
            "qkv_bias": cfg["attention_bias"], "family": "dense"}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise RuntimeError(f"the program's {cfg['arch']} is not the "
                           f"configuration: {got} != {want}")
    return mc


def step_flops(dm: Dims, batch: int, seq: int) -> int:
    """Model FLOPs of one training step of ``batch`` rows of ``seq``
    tokens: the yardstick of ``mfu.train``."""
    return roofline.train_step_flops(dm.layers, dm.d, dm.heads, dm.kv_heads,
                                     dm.head_dim, dm.ff, dm.vocab, batch, seq)


def shapes(dm: Dims) -> Dict[str, tuple]:
    L, d, H, KV, hd, F = (dm.layers, dm.d, dm.heads, dm.kv_heads,
                          dm.head_dim, dm.ff)
    return {"tok": (dm.vocab, d), "wq": (L, d, H, hd), "wk": (L, d, KV, hd),
            "wv": (L, d, KV, hd), "wo": (L, H, hd, d), "wi": (L, d, F),
            "wu": (L, d, F), "wd": (L, F, d), "ln1": (L, d), "ln2": (L, d),
            "final_norm": (d,)}


def fan_in(dm: Dims, name: str) -> int:
    return {"tok": dm.d, "wo": dm.heads * dm.head_dim,
            "wd": dm.ff}.get(name, dm.d)


def init_weights(key, dm: Dims) -> Dict[str, jax.Array]:
    """Float32 weights from ``key``: each matrix a normal truncated at two
    standard deviations, scaled by one over the root of its fan-in; norm
    scales one."""
    out = {}
    for i, (name, shape) in enumerate(shapes(dm).items()):
        if name in ("ln1", "ln2", "final_norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jax.random.truncated_normal(
                jax.random.fold_in(key, i), -2.0, 2.0, shape,
                jnp.float32) / math.sqrt(fan_in(dm, name))
    return out


# ---------------------------------------------------------------------------
# numerics: the reference, and the control one precision lower
# ---------------------------------------------------------------------------

def _round(x, dtype):
    """``x`` rounded to ``dtype`` under one scale that maps its largest
    magnitude to the type's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _f8(x):
    """An operand in float8 as float8 training keeps it: e4m3 forward, and
    its cotangent in e5m2 backward, each under a scale of its own."""
    return _round(x, jnp.float8_e4m3fn)


def _f8_fwd(x):
    return _f8(x), None


def _f8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


_f8.defvjp(_f8_fwd, _f8_bwd)


@dataclasses.dataclass(frozen=True)
class Numerics:
    operands: str = "float32"       # "float32" or "float8"

    def mm(self, spec: str, a, b):
        if self.operands == "float8":
            a, b = _f8(a), _f8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)


REFERENCE = Numerics()
CONTROL = Numerics("float8")


# ---------------------------------------------------------------------------
# the forward pass and the loss
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """x: (B, S, heads, hd); position ``s`` turns pair ``(i, i + hd/2)`` by
    ``s * theta ** (-2 i / hd)``."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, num: Numerics, block: int = 512):
    """Causal attention, query heads sharing key/value heads in groups
    (query head ``h`` reads key/value head ``h // (H / KV)``), over blocks
    of ``block`` query rows against every key."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    block = min(block, S)
    nb = S // block
    qb = q.reshape(B, nb, block, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    cols = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        qi, i = args
        s = num.mm("bqkgh,bskh->bkgqs", qi, k) / math.sqrt(hd)
        rows = i * block + jnp.arange(block)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return num.mm("bkgqs,bskh->bqkgh", p, v)

    out = jax.lax.map(one, (qb, jnp.arange(nb)))      # (nb, B, bq, KV, G, hd)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)


def layer(x, p, dm: Dims, num: Numerics):
    h = rms_norm(x, p["ln1"], dm.eps)
    q = rope(num.mm("bsd,dhk->bshk", h, p["wq"]), dm.rope_theta)
    k = rope(num.mm("bsd,dhk->bshk", h, p["wk"]), dm.rope_theta)
    v = num.mm("bsd,dhk->bshk", h, p["wv"])
    x = x + num.mm("bshk,hkd->bsd", attention(q, k, v, num), p["wo"])
    h = rms_norm(x, p["ln2"], dm.eps)
    g = num.mm("bsd,df->bsf", h, p["wi"])
    u = num.mm("bsd,df->bsf", h, p["wu"])
    return x + num.mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["wd"])


def loss(params, tokens, labels, dm: Dims, num: Numerics = REFERENCE,
         block: int = 1024):
    """Mean next-token cross entropy over every position."""
    x = params["tok"][tokens]
    if num.operands == "float8":
        x = _f8(x)

    def body(x, p):
        return jax.checkpoint(lambda x, p: layer(x, p, dm, num))(x, p), None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in STACKED})
    x = rms_norm(x, params["final_norm"], dm.eps)
    B, S, d = x.shape
    n = B * S
    block = min(block, n)
    xs = x.reshape(n // block, block, d)
    ys = labels.reshape(n // block, block)

    @jax.checkpoint
    def nll(args):
        xi, yi = args
        logits = num.mm("td,vd->tv", xi, params["tok"])
        gold = jnp.take_along_axis(logits, yi[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    return jnp.sum(jax.lax.map(nll, (xs, ys))) / n


# ---------------------------------------------------------------------------
# AdamW, and the readings the comparison takes
# ---------------------------------------------------------------------------

def learning_rate(a: Adam, step):
    step = jnp.asarray(step, jnp.float32)
    warm = step / max(a.warmup_steps, 1)
    prog = jnp.clip((step - a.warmup_steps)
                    / max(a.decay_steps - a.warmup_steps, 1), 0.0, 1.0)
    cos = a.min_lr_ratio + (1 - a.min_lr_ratio) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return a.learning_rate * jnp.where(step < a.warmup_steps, warm, cos)


def adamw(params, grads, m, v, step: int, a: Adam):
    """One AdamW step (``step`` counts from 1). Returns the new params, m,
    v and the clipped gradient the moments took in."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, a.grad_clip / jnp.maximum(gnorm, 1e-12))
    lr = learning_rate(a, step)
    bc1, bc2 = 1 - a.b1 ** step, 1 - a.b2 ** step
    out = {}
    for k in params:
        g = grads[k] * scale
        mk = a.b1 * m[k] + (1 - a.b1) * g
        vk = a.b2 * v[k] + (1 - a.b2) * g * g
        delta = (mk / bc1) / (jnp.sqrt(vk / bc2) + a.eps)
        if k in a.decay:
            delta = delta + a.weight_decay * params[k]
        out[k] = (params[k] - lr * delta, mk, vk, g)
    return tuple({k: o[i] for k, o in out.items()} for i in range(4))


def leaf_norms(tree: Dict[str, jax.Array], leaves=LEAVES,
               stacked=STACKED) -> jax.Array:
    """The norm of every leaf, each layer of a stacked leaf apart, in the
    order of :func:`leaf_names` (another model's ``leaves`` and
    ``stacked`` ones given)."""
    parts = []
    for k in leaves:
        x = tree[k].astype(jnp.float32)
        if k in stacked:
            parts.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                range(1, x.ndim)))))
        else:
            parts.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(parts)


def leaf_names(dm: Dims, leaves=LEAVES, stacked=STACKED) -> List[str]:
    return [f"{k}.{i}" if k in stacked else k
            for k in leaves
            for i in (range(dm.layers) if k in stacked else [0])]


def readings(key, batches: Sequence[Tuple], dm: Dims, a: Adam,
             num: Numerics = REFERENCE) -> Dict[str, np.ndarray]:
    """Train ``len(batches)`` steps from the weights of ``key`` and return
    what the comparison takes: each step's ``loss``, the per-leaf norms of
    the first step's clipped gradient (``grad``) and of the change of the
    parameters after the last step (``change``). The weights, moments and
    one gradient are all it holds on the device at a time."""
    return train_readings(key, batches, lambda k: init_weights(k, dm),
                          lambda p, t, y: loss(p, t, y, dm, num),
                          leaf_norms, a)


def train_readings(key, batches: Sequence[Tuple], init_fn, loss_fn,
                   norms_fn, a: Adam) -> Dict[str, np.ndarray]:
    """:func:`readings` of any model: its weights ``init_fn(key)``, its
    ``loss_fn(params, tokens, labels)`` and its per-leaf
    ``norms_fn(tree)``, trained with :func:`adamw`."""
    init = jax.jit(init_fn)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def update(p, g, m, v, step):
        p, m, v, clipped = adamw(p, g, m, v, step, a)
        return p, m, v, norms_fn(clipped)
    step_fn = jax.jit(update, static_argnums=4, donate_argnums=(0, 2, 3))
    p = init(key)
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, grad = [], None
    for i, (tokens, labels) in enumerate(batches):
        value, g = grad_fn(p, tokens, labels)
        p, m, v, norms = step_fn(p, g, m, v, i + 1)
        losses.append(float(value))
        if i == 0:
            grad = np.asarray(norms)
        del g
    del m, v
    def change(p, k):
        p0 = init_fn(k)
        return norms_fn({n: p[n] - p0[n] for n in p})
    change = np.asarray(jax.jit(change)(p, key))
    return {"loss": np.asarray(losses), "grad": grad, "change": change}
