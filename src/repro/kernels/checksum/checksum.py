"""Pallas TPU kernel: per-block position-salted fmix32 XOR-fold checksum.

The paper uses ``crc32q`` per 4 KB page; the TPU adaptation hashes uint32
lanes on the VPU (DESIGN.md §2.1). Grid = (n_blocks, row_tiles); each step
loads one ``(rows, 128)`` VMEM tile stack of a block (a 4 KiB block is a
single (8, 128) vreg tile), mixes, and XOR-accumulates 128 lane partials
into the block's output row; ops.py folds the 128 partials.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import GOLDEN, LANES, SALT2, fmix32, fold_rows, lane_ids, row_tile, to_rows


def _kernel(x_ref, out_ref, *, rt: int, block_offset: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    lanes = lane_ids(rt, (j * rt).astype(jnp.uint32))
    bid = (b + block_offset).astype(jnp.uint32)
    salt = (bid * GOLDEN) ^ (lanes * SALT2)
    partial = fold_rows(fmix32(x_ref[0] ^ salt))  # (1, 128)

    @pl.when(j == 0)
    def _init():
        out_ref[0] = partial

    @pl.when(j != 0)
    def _acc():
        out_ref[0] ^= partial


def checksum_partials(
    lanes2d: jax.Array,
    *,
    block_offset: int = 0,
    max_rows: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """uint32[n_blocks, 1, 128] partial checksums (XOR-fold outside)."""
    x = to_rows(lanes2d)
    nb, rows, _ = x.shape
    rt = row_tile(rows, max_rows)
    return pl.pallas_call(
        functools.partial(_kernel, rt=rt, block_offset=block_offset),
        grid=(nb, rows // rt),
        in_specs=[pl.BlockSpec((1, rt, LANES), lambda b, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, LANES), jnp.uint32),
        interpret=interpret,
    )(x)
