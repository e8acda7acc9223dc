"""Jitted wrapper: (B,S,H,hd) GQA-expanded attention via the flash kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attn import flash_attention_bh


@functools.partial(jax.jit, static_argnames=("causal", "use_pallas", "interpret",
                                             "block_q", "block_k"))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True, use_pallas: bool = True, interpret: bool = False,
    block_q: int = 256, block_k: int = 256,
) -> jax.Array:
    """q,k,v: (B, S, H, hd) with KV already expanded to H heads."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]

    def bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], hd)

    qb, kb, vb = bh(q), bh(k), bh(v)
    pad = (-hd) % 128
    if pad:
        qb = jnp.pad(qb, ((0, 0), (0, 0), (0, pad)))
        kb = jnp.pad(kb, ((0, 0), (0, 0), (0, pad)))
        vb = jnp.pad(vb, ((0, 0), (0, 0), (0, pad)))
    if not use_pallas:
        out = ref.attention(qb, kb, vb, causal=causal,
                            scale=1.0 / (hd ** 0.5))
    else:
        out = flash_attention_bh(qb, kb, vb, causal=causal,
                                 scale=1.0 / (hd ** 0.5),
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    out = out[..., :hd]
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
