"""Pallas TPU kernel: fused, work-queue-driven checksum+parity update.

This is the Vilamb hot loop (Algorithm 1 lines 7-18) as a single data pass,
plus two TPU-native improvements over the paper's software loop:

1. **Fusion** — the paper's thread reads each dirty page once for its
   checksum and then re-reads the stripe for parity. Here one (1, P, rows, 128)
   VMEM slab per grid step yields both the parity XOR *and* all P member
   checksum partials: each dirty stripe is read exactly once (halves the
   memory term; see EXPERIMENTS.md §Perf).

2. **Work queue via scalar prefetch** — dirty-stripe ids are compacted into
   an SMEM-prefetched index vector that drives the BlockSpec ``index_map``.
   Grid steps beyond ``count`` re-address the last dirty stripe; Mosaic
   skips the DMA when the block index is unchanged and ``pl.when`` skips the
   compute, so the cost scales with the number of *dirty* stripes, not the
   total — the kernel-level realization of the paper's "work ∝ dirty pages"
   claim.

Clean stripes are never addressed, so their output rows are untouched
garbage; ops.py merges with the old arrays under the dirty masks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import GOLDEN, LANES, SALT2, fmix32, fold_rows, lane_ids, row_tile, xor_fold


def _kernel(wids_ref, count_ref, x_ref, par_ref, cks_ref, *, rt: int,
            stripe_width: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _():
        x = x_ref[0]  # (P, rt, 128) uint32: P members' matching vreg rows
        par = xor_fold(x, 0)  # (1, rt, 128)
        lanes = lane_ids(rt, (j * rt).astype(jnp.uint32))
        base = (wids_ref[i] * stripe_width).astype(jnp.uint32)
        # One (1, 128) lane-partial row per member, unrolled over P.
        partials = [
            fold_rows(fmix32(x[p] ^ (((base + p) * GOLDEN)
                                     ^ (lanes * SALT2))))
            for p in range(stripe_width)]

        @pl.when(j == 0)
        def _init():
            par_ref[...] = par
            for p, part in enumerate(partials):
                cks_ref[0, p:p + 1, :] = part

        @pl.when(j != 0)
        def _acc():
            par_ref[...] ^= par
            for p, part in enumerate(partials):
                cks_ref[0, p:p + 1, :] ^= part


def fused_update_striped(
    striped: jax.Array,
    work_ids: jax.Array,
    count: jax.Array,
    *,
    max_rows: int = 32,
    interpret: bool = False,
):
    """Run the work-queue kernel over a (n_stripes, P, rows, 128) view.

    Returns (parity_raw [ns, rows, 128], cks_partials_raw [ns, P, 128]);
    rows not in the work queue contain stale/garbage values — callers must
    merge.
    """
    ns, P, rows, _ = striped.shape
    rt = row_tile(rows, max_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ns, rows // rt),
        in_specs=[
            pl.BlockSpec((1, P, rt, LANES),
                         lambda i, j, wids, cnt: (wids[i], 0, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rt, LANES), lambda i, j, wids, cnt: (wids[i], j, 0)),
            pl.BlockSpec((1, P, LANES), lambda i, j, wids, cnt: (wids[i], 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, rt=rt, stripe_width=P),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((ns, rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((ns, P, LANES), jnp.uint32),
        ],
        interpret=interpret,
    )(work_ids, count, striped)
