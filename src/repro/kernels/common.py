"""Shared kernel helpers: uint32 mixing and XOR folds on (8,128)-tiled vregs.

Mosaic (the TPU Pallas lowering) has no generic ``reduce`` primitive, and a
block's last two dims must be multiples of (8, 128) or span the whole
array.  So every kernel views a block of ``L`` uint32 lanes as
``(L // 128, 128)`` — one (8, 128) vreg tile per 4 KiB block — and folds
with plain ``^`` over static slices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = np.uint32(0x9E3779B9)
SALT2 = np.uint32(0x85EBCA77)
C1 = np.uint32(0x85EBCA6B)
C2 = np.uint32(0xC2B2AE35)

SUBLANES = 8
LANES = 128


def fmix32(x):
    x = x ^ (x >> 16)
    x = x * C1
    x = x ^ (x >> 13)
    x = x * C2
    x = x ^ (x >> 16)
    return x


def xor_fold(x, axis: int = 0):
    """XOR over ``axis`` by an unrolled ``^`` chain (keeps the axis, size 1).

    Traceable both inside a Mosaic kernel (where ``lax.reduce`` does not
    lower) and in plain XLA.  Halves while the length is even — each step
    is whole-vreg XORs on leading axes and a sublane shift on the tiled
    one — then folds any odd remainder one slice at a time.
    """
    n = x.shape[axis]
    while n > 1 and n % 2 == 0:
        n //= 2
        x = (jax.lax.slice_in_dim(x, 0, n, axis=axis)
             ^ jax.lax.slice_in_dim(x, n, 2 * n, axis=axis))
    acc = jax.lax.slice_in_dim(x, 0, 1, axis=axis)
    for i in range(1, n):
        acc = acc ^ jax.lax.slice_in_dim(x, i, i + 1, axis=axis)
    return acc


def xor_reduce(x, axis: int):
    """XOR-reduce over ``axis`` (dropped), built from :func:`xor_fold`."""
    return jnp.squeeze(xor_fold(x, axis), axis=axis)


def fold_rows(h):
    """XOR a ``(rows, 128)`` tile stack down to ``(1, 128)`` lane partials.

    Whole (8, 128) vregs are folded first (leading-axis ``^``), then the
    eight sublanes; a stack shorter than one tile folds row by row.
    """
    rows = h.shape[0]
    if rows % SUBLANES == 0 and rows > SUBLANES:
        h = xor_fold(h.reshape(rows // SUBLANES, SUBLANES, LANES), 0)[0]
    return xor_fold(h, 0)


def row_tile(rows: int, max_rows: int = 32) -> int:
    """Sublane rows per grid step for a ``(rows, 128)`` block view.

    The largest multiple of 8 dividing ``rows`` up to ``max_rows``, else
    the whole block (a block dim equal to the array dim is always legal).
    """
    for t in range(max_rows - max_rows % SUBLANES, 0, -SUBLANES):
        if rows % t == 0:
            return t
    return rows


def to_rows(lanes2d):
    """``(n, L)`` uint32 lanes -> ``(n, L // 128, 128)`` vreg rows."""
    n, L = lanes2d.shape
    assert L % LANES == 0, L
    return lanes2d.reshape(n, L // LANES, LANES)


def striped_rows(lanes2d, stripe_width: int):
    """``(n_blocks, L)`` -> ``(n_stripes, P, L // 128, 128)``, zero-padding
    the last partial stripe (zeros are XOR-neutral)."""
    x = to_rows(lanes2d)
    nb = x.shape[0]
    ns = -(-nb // stripe_width)
    if ns * stripe_width != nb:
        x = jnp.pad(x, ((0, ns * stripe_width - nb), (0, 0), (0, 0)))
    return x.reshape(ns, stripe_width, *x.shape[1:])


def lane_ids(rows: int, row_offset):
    """uint32 lane indices of a ``(rows, 128)`` tile whose first row is
    global row ``row_offset`` (TPU needs >= 2-D iota)."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 1)
    return (r + row_offset) * jnp.uint32(LANES) + c
