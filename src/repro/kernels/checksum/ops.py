"""Jitted wrapper for the checksum kernel."""
from __future__ import annotations

import functools

import jax

from ..common import xor_reduce
from .checksum import checksum_partials
from . import ref


@functools.partial(jax.jit, static_argnames=("block_offset", "use_pallas", "interpret"))
def block_checksums(
    lanes2d: jax.Array,
    block_offset: int = 0,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """uint32[n_blocks] checksums of a (n_blocks, L) uint32 lane view."""
    if not use_pallas:
        return ref.block_checksums(lanes2d, block_offset)
    partials = checksum_partials(
        lanes2d, block_offset=block_offset, interpret=interpret)
    # oracle = XOR_i fmix(w_i ^ salt_i); partial[c] already holds the XOR of
    # mixed lanes congruent to c mod 128, so a plain XOR-fold suffices.
    return xor_reduce(partials.reshape(partials.shape[0], -1), 1)
