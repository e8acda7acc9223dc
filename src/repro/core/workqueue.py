"""Work-queue compaction for Algorithm 1 in pure XLA.

The Pallas kernel (kernels/redundancy) realizes the paper's "work ∝ dirty
pages" claim with a scalar-prefetch work queue; this module is the same
idea expressed in plain jnp so *every* backend — including the default
CPU/XLA reference path — pays for dirty stripes, not region size:

1. **Compact** dirty-stripe ids into a fixed-capacity queue (static shape
   ``K``, padded with the out-of-range sentinel ``n_stripes``).
2. **Gather** only those stripes into a ``(K, P, L)`` slab — one fused read
   feeds both checksum and parity, like the kernel.  XLA fuses the leaf
   bitcast into the gather, so clean stripes are never even read.
3. **Compute** per-member checksums (true block-id salts) and the stripe
   XOR parity on the slab in one pass.
4. **Scatter** results back under the dirty masks; sentinel rows drop.
5. The meta-checksum is updated *incrementally* from the changed checksum
   deltas (XOR algebra makes this bitwise-exact) instead of rehashing every
   checksum.

**The capacity is a ladder, not one K.**  Padding rows still cost a
gather, a reduce and a scatter each, so a pass pays for ``K`` rows whatever
the dirty count.  :func:`queue_ladder` derives a short ladder of static
capacities from a queue's top capacity (:func:`queue_capacity`): the top
divided by :data:`RUNG_STEP` down to :data:`MIN_RUNG_STRIPES`.  Each rung
is its own compiled program; the store picks one per pass with
:func:`pick_rung` from the last pass's need (its largest leaf's
dirty-stripe count, fetched with the fit signal), with
:data:`RUNG_MARGIN` of room.

**Overflow is a host-side dispatch decision, not a device branch.**  A
``lax.cond``/``fori_loop`` realization was measured first and rejected:
XLA materializes every conditional operand (the whole lane view, parity,
checksums), which costs more than the full recompute it was meant to skip.
Instead :func:`queued_update` assumes the caller has already checked
``dirty-stripe count <= capacity`` (see ``RedundancyEngine.queue_fits``);
the store's tick — a host loop by construction — dispatches either a
queued rung or the full jitted program.  All produce bitwise-identical
results on their shared domain, so the fallback never changes semantics.

Everything here is shard-oblivious on purpose: under a mesh the engine
calls these helpers *inside* ``shard_map``, so each shard compacts its own
queue over its local stripes (capacity derived from the local stripe
count) and :func:`stripe_fits` becomes the shard-local flag the overlap
pipeline AND-folds across shards — on the host, after the batched fetch
(:func:`fold_fits_host`; see ``engine.redundancy_step_async``).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import checksum

DEFAULT_QUEUE_FRAC = 0.125   # queue capacity as a fraction of n_stripes
MIN_QUEUE_STRIPES = 4
# The ladder's bottom: below it a pass is bound by what does not scale
# with K (the parity array's copy, the stripe-id compaction), not by the
# queue's rows.
MIN_RUNG_STRIPES = 256
# Ratio of neighbouring rungs.  Every rung is one more program to trace
# and load at attach: on one TPU v5e, halving the top instead (7 rungs on
# a 2 GiB heap, 4 here) put 16% on a read-only YCSB cell's set-up with a
# warm compile cache, where 4 puts 6%.
RUNG_STEP = 4
# Room a rung keeps over the last count: the next snapshot may hold more.
RUNG_MARGIN = 1.25
# A snapshot's need where a leaf's dirty stripes exceed its own top
# capacity: past every rung, so only the full program holds it.
NO_RUNG = 2**31 - 1


def queue_capacity(n_stripes: int, frac: float,
                   min_stripes: int = MIN_QUEUE_STRIPES) -> int:
    """Static per-leaf queue capacity; 0 disables compaction.

    Compaction only pays when the queue is a strict subset of the stripes:
    a capacity >= n_stripes would gather everything and is reported as 0
    (callers then use the plain full-recompute path).
    """
    if frac <= 0.0 or n_stripes <= 1:
        return 0
    cap = max(min_stripes, math.ceil(n_stripes * frac))
    if cap >= n_stripes:
        return 0
    return cap


def queue_ladder(top: int) -> Tuple[int, ...]:
    """Compiled capacities of one queue, ascending: ``top`` divided by
    :data:`RUNG_STEP` while the quotient stays >= :data:`MIN_RUNG_STRIPES`
    (16384, 4096, 1024, 256).  A top below ``RUNG_STEP * MIN_RUNG_STRIPES``
    is its own one-rung ladder; ``top`` 0 (no queue) has none.
    """
    if top <= 0:
        return ()
    rungs = [top]
    while rungs[-1] // RUNG_STEP >= MIN_RUNG_STRIPES:
        rungs.append(rungs[-1] // RUNG_STEP)
    return tuple(reversed(rungs))


def pick_rung(ladder: Tuple[int, ...], need: int) -> int:
    """The smallest rung >= :data:`RUNG_MARGIN` x ``need``; the top rung
    where only the margin overshoots it; 0 (the full program) where
    ``need`` itself exceeds the top rung.  ``need`` is the largest queued
    leaf's (and shard's) dirty-stripe count, or :data:`NO_RUNG` where one
    exceeds its own top capacity: at rung ``K`` a leaf holds
    ``min(K, its top)`` stripes."""
    if not ladder or need > ladder[-1]:
        return 0
    want = RUNG_MARGIN * need
    return next((k for k in ladder if k >= want), ladder[-1])


def compact_stripe_ids(
    stripe_dirty: jax.Array, size: int, *, pad_repeat_last: bool = False
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compact a bool[n_stripes] mask into int32 ids of static length ``size``.

    Returns ``(ids, count, overflow)``.  Padding entries are the sentinel
    ``n_stripes`` (scatters with ``mode="drop"`` discard them) unless
    ``pad_repeat_last`` — the Pallas-kernel convention, where repeating the
    last live id lets Mosaic elide the DMA for trailing grid steps.
    ``overflow`` is True when the mask holds more than ``size`` set bits
    (``ids`` is then truncated and callers must fall back).
    """
    ns = stripe_dirty.shape[0]
    fill = 0 if pad_repeat_last else ns
    ids = jnp.nonzero(stripe_dirty, size=size, fill_value=fill)[0].astype(jnp.int32)
    count = jnp.sum(stripe_dirty, dtype=jnp.int32)
    if pad_repeat_last:
        last = ids[jnp.maximum(jnp.minimum(count, size) - 1, 0)]
        ids = jnp.where(jnp.arange(size) < count, ids, last)
    return ids, count, count > size


def stripe_dirty_count(stripe_dirty: jax.Array) -> jax.Array:
    """Number of dirty stripes (int32 scalar)."""
    return jnp.sum(stripe_dirty, dtype=jnp.int32)


def stripe_fits(stripe_dirty: jax.Array, capacity: int) -> jax.Array:
    """Device-side fit check: do the dirty stripes fit a ``capacity`` queue?

    Bool scalar, traceable.  This is the same predicate
    ``RedundancyEngine.queue_fits`` evaluates host-side; the overlap
    pipeline computes it *inside* the dispatched Algorithm-1 program and
    fetches it one tick ahead via a non-blocking async copy, so a due tick
    never pays a device->host round trip (see ``redundancy_step_async``).
    """
    return stripe_dirty_count(stripe_dirty) <= capacity


def fold_fits_host(fits_row) -> bool:
    """Host-side AND-fold of one group's fetched fit signal.

    ``fits_row`` is either the machine-local scalar or the per-shard flag
    row out of the store's batched ``(n_groups, n_devices)`` fits vector.
    The fold happens here, on already-fetched host memory — never as a
    device program: a cross-shard AND would be the one collective in an
    otherwise collective-free redundancy pipeline, and a dedicated fold
    launch per group was exactly the per-tick dispatch overhead the
    batched path removes.
    """
    return bool(np.asarray(fits_row).all())


def queued_update(
    lanes: jax.Array,
    old_cks: jax.Array,
    old_par: jax.Array,
    old_meta: jax.Array,
    bdirty: jax.Array,
    ids: jax.Array,
    stripe_width: int,
):
    """Gather→compute→scatter one compacted work queue (Alg. 1 lines 7-22).

    ``ids`` comes from :func:`compact_stripe_ids` (sentinel padding).
    Caller contract: every dirty stripe id is present in ``ids`` — i.e. the
    dirty-stripe count fit the queue capacity.  Under that contract the
    result is bitwise-identical to :func:`full_update` (given ``old_meta``
    is the true meta-checksum of ``old_cks``, the engine invariant); with a
    truncated queue it would silently leave stripes stale, so dispatchers
    must check ``queue_fits`` first.
    """
    nb, L = lanes.shape
    ns = old_par.shape[0]
    P = stripe_width
    valid_q = ids < ns                                        # live queue rows
    safe_sid = jnp.minimum(ids, ns - 1)
    block_ids = safe_sid[:, None] * P + jnp.arange(P, dtype=jnp.int32)[None, :]
    in_leaf = block_ids < nb                                  # last partial stripe
    safe_bid = jnp.minimum(block_ids, nb - 1)
    # One fused read: the (K, P, L) slab feeds parity AND member checksums.
    slab = jnp.where(in_leaf[:, :, None], lanes[safe_bid], jnp.uint32(0))
    par_rows = jax.lax.reduce(slab, jnp.uint32(0), jax.lax.bitwise_xor, (1,))
    bids = block_ids.astype(jnp.uint32)[:, :, None]
    lids = jnp.arange(L, dtype=jnp.uint32)[None, None, :]
    h = checksum.fmix32(slab ^ checksum.lane_salt(bids, lids))
    cks_rows = jax.lax.reduce(h, jnp.uint32(0), jax.lax.bitwise_xor, (2,))
    # Scatter back under the masks; sentinel / clean / padded rows drop.
    upd = valid_q[:, None] & in_leaf & bdirty[safe_bid]
    tgt_b = jnp.where(upd, block_ids, nb).reshape(-1)
    cks = old_cks.at[tgt_b].set(cks_rows.reshape(-1), mode="drop")
    tgt_s = jnp.where(valid_q, ids, ns)
    par = old_par.at[tgt_s].set(par_rows, mode="drop")
    # Incremental meta-checksum from the changed deltas only.
    old_vals = jnp.where(upd, old_cks[safe_bid], jnp.uint32(0))
    new_vals = jnp.where(upd, cks_rows, old_vals)            # no-op rows cancel
    meta = old_meta ^ checksum.meta_checksum_delta(
        old_vals.reshape(-1), new_vals.reshape(-1),
        jnp.where(upd, block_ids, 0).reshape(-1))
    return cks, par, meta


def full_update(lanes, old_cks, old_par, bdirty, sdirty, stripe_width):
    """Reference full-region masked recompute (the pre-queue semantics)."""
    from . import parity  # local import: parity has no dep on this module
    cks = jnp.where(bdirty, checksum.block_checksums(lanes), old_cks)
    par = parity.stripe_parity_masked(lanes, old_par, sdirty, stripe_width)
    return cks, par, checksum.meta_checksum(cks)
