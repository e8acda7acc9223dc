"""Plain per-block checksums and stripe parity, written from their spec.

The store documents its redundancy (``core/checksum.py``, ``core/parity.py``
docstrings) as:

* a leaf is viewed as little-endian uint32 lanes, zero-padded to whole
  blocks of ``L`` lanes (sub-word dtypes pack their first element into the
  low bits);
* ``checksum[b] = XOR_i fmix32(w[b, i] ^ (b * 0x9E3779B9 ^ i * 0x85EBCA77))``
  with Murmur3's 32-bit finalizer ``fmix32``;
* ``parity[s] = XOR of blocks s*P .. s*P+P-1`` (missing blocks are zero);
* ``meta = XOR_j fmix32(checksum[j] ^ j * 0x9E3779B9)``.

A leaf split over ``k`` shards along dim 0 keeps one such set per shard,
numbered from 0 within the shard, concatenated.  Every function takes the
array module ``xp`` (numpy or jax.numpy) so the same code is tested on the
CPU against the library and run on the chip at full size.
"""
from __future__ import annotations

import numpy as np

GOLDEN = 0x9E3779B9
SALT2 = 0x85EBCA77
C1 = 0x85EBCA6B
C2 = 0xC2B2AE35


def u32(xp, v):
    return xp.asarray(v, dtype=xp.uint32)


def fmix32(xp, x):
    """Murmur3's 32-bit finalizer, uint32 with wrap-around."""
    x = x ^ (x >> u32(xp, 16))
    x = x * u32(xp, C1)
    x = x ^ (x >> u32(xp, 13))
    x = x * u32(xp, C2)
    x = x ^ (x >> u32(xp, 16))
    return x


def geometry(shape, itemsize: int, lanes_per_block: int) -> tuple:
    """(lanes, n_blocks, lanes_per_block) of one shard's leaf, as the spec
    lays it out: small leaves get one block of a multiple of 128 lanes."""
    n_elems = int(np.prod(shape)) if len(shape) else 1
    lanes = -(-n_elems * itemsize // 4)
    lpb = min(lanes_per_block, max(128, -(-lanes // 128) * 128))
    return lanes, max(1, -(-lanes // lpb)), lpb


def lane_view(xp, leaf_u32, n_blocks: int, lpb: int):
    """Flat uint32 lanes -> zero-padded ``(n_blocks, lpb)`` blocks."""
    flat = leaf_u32.reshape(-1)
    pad = n_blocks * lpb - flat.shape[0]
    if pad:
        flat = xp.concatenate([flat, xp.zeros((pad,), xp.uint32)])
    return flat.reshape(n_blocks, lpb)


def block_checksums(xp, blocks, first_block: int = 0):
    """Checksums of ``blocks`` (shape ``(nb, L)``) numbered from
    ``first_block`` within their shard."""
    nb, L = blocks.shape
    bid = (xp.arange(nb, dtype=xp.uint32) + u32(xp, first_block)) * u32(xp, GOLDEN)
    lid = xp.arange(L, dtype=xp.uint32) * u32(xp, SALT2)
    h = fmix32(xp, blocks ^ (bid[:, None] ^ lid[None, :]))
    return _xor_reduce(xp, h, axis=1)


def stripe_parity(xp, blocks, stripe: int):
    """XOR parity of consecutive groups of ``stripe`` blocks."""
    nb, L = blocks.shape
    ns = -(-nb // stripe)
    if ns * stripe != nb:
        blocks = xp.concatenate(
            [blocks, xp.zeros((ns * stripe - nb, L), xp.uint32)])
    return _xor_reduce(xp, blocks.reshape(ns, stripe, L), axis=1)


def meta_checksum(xp, checksums):
    ids = xp.arange(checksums.shape[0], dtype=xp.uint32) * u32(xp, GOLDEN)
    return _xor_reduce(xp, fmix32(xp, checksums ^ ids), axis=0)


def _xor_reduce(xp, a, axis: int):
    if xp is np:
        return np.bitwise_xor.reduce(a, axis=axis)
    import jax
    return jax.lax.reduce(a, xp.uint32(0), jax.lax.bitwise_xor, (axis,))


def leaf_redundancy(xp, leaf_u32, shape, itemsize: int, lanes_per_block: int,
                    stripe: int):
    """(checksums, parity, meta) of one shard's leaf given as uint32 lanes
    (``leaf_u32`` is the leaf's bytes viewed as uint32, any shape)."""
    _, nb, lpb = geometry(shape, itemsize, lanes_per_block)
    blocks = lane_view(xp, leaf_u32, nb, lpb)
    ck = block_checksums(xp, blocks)
    return ck, stripe_parity(xp, blocks, stripe), meta_checksum(xp, ck)


def as_lanes(xp, leaf):
    """A leaf's bytes as flat little-endian uint32 lanes (zero-padded to a
    whole lane).  Two-byte dtypes put their first element in the low half."""
    if xp is np:
        raw = np.ascontiguousarray(leaf).reshape(-1).view(np.uint8)
        pad = -raw.shape[0] % 4
        if pad:
            raw = np.concatenate([raw, np.zeros((pad,), np.uint8)])
        return raw.view("<u4")
    import jax
    size = leaf.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(leaf, xp.uint32).reshape(-1)
    if size != 2:
        raise ValueError(f"no lane view for {leaf.dtype}")
    u = jax.lax.bitcast_convert_type(leaf, xp.uint16).reshape(-1)
    width = 256 if u.shape[0] % 256 == 0 else 2
    if u.shape[0] % 2:
        u = xp.concatenate([u, xp.zeros((1,), xp.uint16)])
    u = u.reshape(-1, width)
    lo = u[:, 0::2].astype(xp.uint32)
    hi = u[:, 1::2].astype(xp.uint32)
    return (lo | (hi << xp.uint32(16))).reshape(-1)
