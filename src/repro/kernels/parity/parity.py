"""Pallas TPU kernel: stripe XOR parity (paper's cross-page parity).

AVX 256-byte-word XOR in the paper becomes a uint32 XOR over the stripe
axis on the VPU. Grid = (n_stripes, row_tiles); each step loads a
``(1, P, rows, 128)`` slab — the P stripe members' matching vreg rows —
and writes their XOR, unrolled over the P members.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import LANES, row_tile, xor_fold


def _kernel(x_ref, out_ref):
    out_ref[...] = xor_fold(x_ref[0], 0)


def stripe_parity_striped(
    striped: jax.Array, *, max_rows: int = 32, interpret: bool = False
) -> jax.Array:
    """Parity of a pre-striped uint32[n_stripes, P, rows, 128] view ->
    [n_stripes, rows, 128]."""
    ns, P, rows, _ = striped.shape
    rt = row_tile(rows, max_rows)
    return pl.pallas_call(
        _kernel,
        grid=(ns, rows // rt),
        in_specs=[pl.BlockSpec((1, P, rt, LANES), lambda s, j: (s, 0, j, 0))],
        out_specs=pl.BlockSpec((1, rt, LANES), lambda s, j: (s, j, 0)),
        out_shape=jax.ShapeDtypeStruct((ns, rows, LANES), jnp.uint32),
        interpret=interpret,
    )(striped)
