"""Block views over state leaves (paper's "pages", §3.1).

A leaf array of any shape/dtype is reinterpreted as a 2-D uint32 lane view
``(n_blocks, lanes_per_block)`` — the unit over which checksums are computed
and parity stripes are formed. 4 KB NVM pages become ``lanes_per_block``
uint32 words (default 16384 lanes = 64 KiB), sized so one block is a clean
multiple of the TPU (8, 128) vreg tile and fits VMEM comfortably.

Bitcasting is layout-only; XLA fuses it into the consuming reduction.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bits

DEFAULT_LANES_PER_BLOCK = 16384  # 64 KiB blocks, = 128 * (8,128) vregs
DEFAULT_STRIPE_DATA_BLOCKS = 4   # paper: 4 data pages + 1 parity page


def _elems_per_word(dtype) -> int:
    isz = jnp.dtype(dtype).itemsize
    if isz > 4:
        raise ValueError(f"dtypes wider than 4 bytes unsupported: {dtype}")
    if 4 % isz:
        raise ValueError(f"itemsize must divide 4: {dtype}")
    return 4 // isz


@dataclasses.dataclass(frozen=True)
class BlockMeta:
    """Static geometry of a leaf's block view (local to a shard)."""
    shape: Tuple[int, ...]
    dtype: str
    lanes_per_block: int
    stripe_data_blocks: int

    @property
    def n_elems(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def elems_per_word(self) -> int:
        return _elems_per_word(self.dtype)

    @property
    def n_lanes(self) -> int:
        """Total uint32 lanes (before block padding)."""
        return -(-self.n_elems // self.elems_per_word)

    @property
    def n_blocks(self) -> int:
        return max(1, -(-self.n_lanes // self.lanes_per_block))

    @property
    def n_stripes(self) -> int:
        return -(-self.n_blocks // self.stripe_data_blocks)

    @property
    def n_dirty_words(self) -> int:
        return bits.n_words(self.n_blocks)

    @property
    def padded_lanes(self) -> int:
        return self.n_blocks * self.lanes_per_block

    @property
    def padded_blocks(self) -> int:
        return self.n_stripes * self.stripe_data_blocks

    @property
    def bytes_per_block(self) -> int:
        return self.lanes_per_block * 4

    @property
    def data_bytes(self) -> int:
        return self.n_elems * jnp.dtype(self.dtype).itemsize


def make_meta(
    leaf: jax.ShapeDtypeStruct | jax.Array,
    lanes_per_block: int = DEFAULT_LANES_PER_BLOCK,
    stripe_data_blocks: int = DEFAULT_STRIPE_DATA_BLOCKS,
) -> BlockMeta:
    n_lanes = -(-int(np.prod(leaf.shape) or 1) // _elems_per_word(leaf.dtype))
    # Small leaves get a single (possibly shorter) block, padded to a multiple
    # of 128 lanes so kernels keep (8,128)-aligned tiles.
    lpb = min(lanes_per_block, max(128, -(-n_lanes // 128) * 128))
    return BlockMeta(
        shape=tuple(leaf.shape),
        dtype=str(jnp.dtype(leaf.dtype).name),
        lanes_per_block=lpb,
        stripe_data_blocks=stripe_data_blocks,
    )


def _packed_rows(meta: BlockMeta) -> int:
    """Lanes per row of the sub-word packing view: 128 where the padded
    lanes allow it.  A packing view with a trailing dim of
    ``elems_per_word`` would be tiled (8, 128) on TPU — a 64x blow-up."""
    return 128 if meta.padded_lanes % 128 == 0 else 1


def to_lanes(x: jax.Array, meta: BlockMeta) -> jax.Array:
    """Bitcast + pad a leaf into its (n_blocks, lanes_per_block) uint32 view.

    Sub-word dtypes pack ``elems_per_word`` consecutive elements into one
    lane, first element in the low bits (a little-endian bitcast), built
    from strided slices so no array ever has a tiny minor dim.
    """
    epw = meta.elems_per_word
    flat = x.reshape(-1)
    pad_elems = meta.padded_lanes * epw - flat.shape[0]
    if pad_elems:
        flat = jnp.pad(flat, (0, pad_elems))
    if epw == 1:
        lanes = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    else:
        width = 32 // epw
        u = jax.lax.bitcast_convert_type(flat, jnp.dtype(f"uint{width}"))
        u = u.reshape(-1, _packed_rows(meta) * epw)
        lanes = u[:, 0::epw].astype(jnp.uint32)
        for i in range(1, epw):
            lanes = lanes | (u[:, i::epw].astype(jnp.uint32)
                             << jnp.uint32(width * i))
    return lanes.reshape(meta.n_blocks, meta.lanes_per_block)


def from_lanes(lanes: jax.Array, meta: BlockMeta) -> jax.Array:
    """Inverse of :func:`to_lanes` (used by parity reconstruction)."""
    epw = meta.elems_per_word
    dt = jnp.dtype(meta.dtype)
    if epw == 1:
        out = jax.lax.bitcast_convert_type(lanes.reshape(-1), dt)
    else:
        width = 32 // epw
        ut = jnp.dtype(f"uint{width}")
        w = lanes.reshape(-1, _packed_rows(meta))
        packed = None
        for i in range(epw):
            part = ((w >> jnp.uint32(width * i))
                    & jnp.uint32((1 << width) - 1)).astype(ut)
            # Interior padding interleaves: element i of every lane lands
            # at column lane * epw + i.
            part = jax.lax.pad(part, ut.type(0),
                               ((0, 0, 0), (i, epw - 1 - i, epw - 1)))
            packed = part if packed is None else packed | part
        out = jax.lax.bitcast_convert_type(packed, dt).reshape(-1)
    return out[: meta.n_elems].reshape(meta.shape)


def stripe_dirty_mask(meta: BlockMeta, block_dirty: jax.Array) -> jax.Array:
    """bool[n_stripes] of stripes containing at least one dirty block.

    The block->stripe reduction of Algorithm 1 (a stripe's parity is stale
    iff any member block is dirty); shared by the update programs, the
    fit check, and the accounting paths.
    """
    padded = jnp.pad(block_dirty, (0, meta.padded_blocks - meta.n_blocks))
    return jnp.any(padded.reshape(meta.n_stripes, meta.stripe_data_blocks),
                   axis=1)


def shard_slice(leaf: jax.Array, meta: BlockMeta, shards: int, shard: int):
    """View one shard's rows of a dim0-sharded global leaf.

    Sharded redundancy state is addressed in *global block space*: shard
    ``s``'s local block ``b`` is global block ``s * meta.n_blocks + b``
    (``meta`` is the shard-local geometry).  Host-side surgery on that
    space — fault injection, parity reconstruction — needs the shard's
    local lane view back.  Supported for leading-axis sharding only (the
    repo's redundancy layout); other specs raise.

    Returns ``(sub_leaf, put)`` where ``put(new_sub)`` writes the modified
    shard back into a new global leaf.
    """
    if shards == 1:
        return leaf, (lambda new: new)
    rows = meta.shape[0]
    if (leaf.shape[0] != rows * shards
            or tuple(leaf.shape[1:]) != tuple(meta.shape[1:])):
        raise ValueError(
            f"global-block addressing needs dim0-only sharding: global "
            f"{tuple(leaf.shape)} vs local {tuple(meta.shape)} x {shards}")
    lo = shard * rows
    sub = leaf[lo:lo + rows]

    def put(new):
        return leaf.at[lo:lo + rows].set(new)

    return sub, put


def global_stripe_id(meta: BlockMeta, block: int) -> int:
    """Global stripe id of a global block id (shard-local geometry ``meta``).

    Parity groups never span shards, so shard ``s`` owns stripes
    ``[s * n_stripes, (s+1) * n_stripes)`` — the one formula repair
    grouping, parity-fault placement, and clean-stripe planning must
    share (global block space as in :func:`shard_slice`).
    """
    s, b = divmod(int(block), meta.n_blocks)
    return s * meta.n_stripes + b // meta.stripe_data_blocks


def block_of_index(meta: BlockMeta, flat_elem_index) -> jax.Array:
    """Block id containing a flat element index (for sparse dirty marking)."""
    lane = flat_elem_index // meta.elems_per_word
    return lane // meta.lanes_per_block


def blocks_of_rows(meta: BlockMeta, row_ids: jax.Array) -> jax.Array:
    """Block-id ranges covered by whole leading-axis rows (embedding rows,
    MoE expert slabs, KV pages). Returns the block id of each row's first
    element; callers should also mark the block of the row's last element
    when rows straddle blocks (see :func:`row_block_mask`)."""
    if not meta.shape:
        return jnp.zeros_like(row_ids)
    row_elems = int(np.prod(meta.shape[1:])) if len(meta.shape) > 1 else 1
    first = row_ids * row_elems
    return block_of_index(meta, first)


def _row_geometry(meta: BlockMeta, row_dims: int):
    """(row_lanes, blocks_per_row) for rows over the first ``row_dims`` axes."""
    row_elems = int(np.prod(meta.shape[row_dims:])) if len(meta.shape) > row_dims else 1
    row_lanes = -(-row_elems // meta.elems_per_word) if meta.elems_per_word else row_elems
    blocks_per_row = max(1, -(-row_elems // (meta.lanes_per_block * meta.elems_per_word)) + 1)
    return row_lanes, blocks_per_row


def row_block_mask(meta: BlockMeta, row_ids: jax.Array, row_dims: int = 1) -> jax.Array:
    """bool[n_blocks] mask of all blocks touched by the given rows.

    Rows index the leaf's first ``row_dims`` axes flattened (ids < 0
    ignored); handles rows straddling multiple blocks. This is the
    domain-space -> block-space translation of the paper's dirty bits.
    """
    if not meta.shape:
        return jnp.ones((meta.n_blocks,), bool)
    row_lanes, blocks_per_row = _row_geometry(meta, row_dims)
    valid = row_ids >= 0
    safe_rows = jnp.where(valid, row_ids, 0)
    first_lane = safe_rows.astype(jnp.int64 if meta.n_lanes > 2**31 else jnp.int32) * row_lanes
    first_block = first_lane // meta.lanes_per_block
    offs = jnp.arange(blocks_per_row)
    ids = first_block[:, None] + offs[None, :]
    last_lane = first_lane + row_lanes - 1
    last_block = last_lane // meta.lanes_per_block
    in_range = ids <= last_block[:, None]
    ids = jnp.where(in_range & valid[:, None], ids, meta.n_blocks)
    mask = jnp.zeros((meta.n_blocks,), bool).at[ids.reshape(-1)].set(True, mode="drop")
    return mask


def row_mask_block_mask(meta: BlockMeta, row_mask: jax.Array,
                        row_dims: int = 1) -> jax.Array:
    """bool[n_blocks] of blocks touched by set rows of a bool row mask.

    Same semantics as ``row_block_mask(meta, nonzero(row_mask))`` but with
    no ``nonzero`` materialization: when rows pack evenly into blocks the
    translation is a plain reshape-any reduction; otherwise it is a masked
    scatter-OR over the row range — cost tracks the event shape, never the
    leaf size.
    """
    if not meta.shape:
        return jnp.full((meta.n_blocks,), jnp.any(row_mask))
    row_mask = row_mask.reshape(-1)
    nb, L = meta.n_blocks, meta.lanes_per_block
    row_lanes, blocks_per_row = _row_geometry(meta, row_dims)
    R = row_mask.shape[0]
    if row_lanes <= L and L % row_lanes == 0:
        # Rows never straddle a block boundary: block b = row // rows_per_block.
        rpb = L // row_lanes
        pad = -R % rpb
        per_block = jnp.pad(row_mask, (0, pad)).reshape(-1, rpb).any(axis=1)
        if per_block.shape[0] >= nb:
            return per_block[:nb]
        return jnp.pad(per_block, (0, nb - per_block.shape[0]))
    idt = jnp.int64 if meta.n_lanes > 2**31 else jnp.int32
    first_lane = jnp.arange(R, dtype=idt) * row_lanes
    first_block = first_lane // L
    last_block = (first_lane + row_lanes - 1) // L
    offs = jnp.arange(blocks_per_row, dtype=idt)
    ids = first_block[:, None] + offs[None, :]
    live = (ids <= last_block[:, None]) & row_mask[:, None]
    ids = jnp.where(live, ids, nb)
    return jnp.zeros((nb,), bool).at[ids.reshape(-1)].set(True, mode="drop")
