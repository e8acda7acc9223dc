"""Plain OLMo training step: forward, loss, gradients and AdamW.

Written from the published description (arXiv:2402.00838 section 2.1) and
the configuration's file, not from the library:

* token embedding, then per layer ``x += Attn(LN(x))`` and
  ``x += SwiGLU(LN(x))``, then ``LN`` and the output head, which is the
  embedding table itself (tied, as published);
* ``LN`` is non-parametric LayerNorm, ``(x - mean) / sqrt(var + 1e-5)``;
* attention is causal multi-head attention with rotary embeddings (each
  head's first and second halves rotated as a pair, ``theta`` 10000) and
  ``1/sqrt(head_dim)`` scaling;
* ``SwiGLU(h) = (silu(h W_g) * (h W_i)) W_o``;
* the loss is the mean next-token cross-entropy over the real vocabulary
  (the head's padding columns never win);
* AdamW with global-norm clipping, bias correction, decoupled weight decay,
  and lazy rows for the embedding: rows of tokens absent from the batch
  keep their parameters and moments, though the tied head gives them a
  gradient (the configuration's ``lazy_embedding_rows``).

Parameters are read by path in the library's layout (``stack/slot_0/...``
with the layer as the leading axis).  ``precision`` sets the arithmetic:
``"float32"`` multiplies float32 operands at full precision and keeps
parameters as the configuration stores them; ``"low"`` (the control) rounds
every matmul operand to float8 (e4m3), parameters to float8 and moments
to bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def _dot(precision: str):
    if precision == "float32":
        def dot(spec, a, b):
            return jnp.einsum(spec, a, b,
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        return dot
    if precision == "low":
        def dot(spec, a, b):
            f8 = jnp.float8_e4m3fn
            return jnp.einsum(spec, a.astype(f8).astype(jnp.float32),
                              b.astype(f8).astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        return dot
    raise ValueError(precision)


def layer_norm(x, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rope(x, theta: float):
    """x: (B, S, H, hd); rotate (first half, second half) pairs."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params: Dict, tokens, labels, model: dict, precision: str):
    """Mean next-token cross-entropy of float32 ``params``."""
    dot = _dot(precision)
    stack = params["stack"]["slot_0"]
    x = params["embed"][tokens]

    def layer(x, p):
        h = layer_norm(x)
        q = rope(dot("bsd,dhk->bshk", h, p["attn"]["wq"]), model["rope_theta"])
        k = rope(dot("bsd,dhk->bshk", h, p["attn"]["wk"]), model["rope_theta"])
        v = dot("bsd,dhk->bshk", h, p["attn"]["wv"])
        s = dot("bqhk,bshk->bhqs", q, k) / math.sqrt(q.shape[-1])
        S = x.shape[1]
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = dot("bhqs,bshk->bqhk", a, v)
        x = x + dot("bqhk,hkd->bqd", o, p["attn"]["wo"])
        h = layer_norm(x)
        f = jax.nn.silu(dot("bsd,df->bsf", h, p["ffn"]["wg"])) * \
            dot("bsd,df->bsf", h, p["ffn"]["wi"])
        return x + dot("bsf,fd->bsd", f, p["ffn"]["wo"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stack)
    x = layer_norm(x)
    logits = dot("bsd,vd->bsv", x, params["embed"])[..., :model["vocab_size"]]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def flatten(tree: Dict, prefix: str = "") -> Dict:
    """Nested dicts -> ``{"a/b/c": leaf}`` (empty dicts drop out)."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


def unflatten(flat: Dict) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def train_step(params, m, v, count, tokens, labels, model: dict, opt: dict,
               precision: str):
    """Loss, gradients and one AdamW update of flat float32 ``params``;
    returns ``(params, m, v, loss, grad_norm, clipped_grad_norms)`` (the
    last per leaf: the gradient as AdamW takes it, after clipping)."""
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(unflatten(p), tokens, labels, model, precision))(
        params)
    params, m, v, gnorm, clipped = adamw_step(
        params, grads, m, v, count, tokens, opt, precision)
    return params, m, v, loss, gnorm, clipped


def adamw_step(params, grads, m, v, count, tokens, opt: dict,
               precision: str):
    """One AdamW update of flat dicts (``count`` is 1-based); returns
    ``(params, m, v, grad_norm, clipped_grad_norms)``, params in their stored
    dtype, moments float32 (bfloat16-rounded for ``"low"``)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                         jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    b1, b2 = opt["beta1"], opt["beta2"]
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    vocab_rows = params["embed"].shape[0]
    present = jnp.zeros((vocab_rows,), bool).at[tokens.reshape(-1)].set(True)
    p_dtype = jnp.float8_e4m3fn if precision == "low" else None
    m_dtype = jnp.bfloat16 if precision == "low" else None

    def one(path, p, g, m0, v0):
        g = g * scale
        m1 = b1 * m0 + (1 - b1) * g
        v1 = b2 * v0 + (1 - b2) * jnp.square(g)
        if m_dtype is not None:
            m1 = m1.astype(m_dtype).astype(jnp.float32)
            v1 = v1.astype(m_dtype).astype(jnp.float32)
        upd = (m1 / bc1) / (jnp.sqrt(v1 / bc2) + opt["eps"])
        decay = opt["weight_decay"] if p.ndim >= 2 else 0.0
        p1 = p - opt["lr"] * (upd + decay * p)
        if path == "embed":
            keep = present[:, None]
            p1, m1, v1 = (jnp.where(keep, p1, p), jnp.where(keep, m1, m0),
                          jnp.where(keep, v1, v0))
        store = p_dtype or jnp.dtype(opt["param_dtype"])
        return (p1.astype(store).astype(jnp.float32), m1, v1,
                jnp.sqrt(jnp.sum(jnp.square(g))))

    out = {}
    for path in params:
        out[path] = one(path, params[path], grads[path], m[path], v[path])
    pick = lambda i: {k: t[i] for k, t in out.items()}
    return pick(0), pick(1), pick(2), gnorm, pick(3)
