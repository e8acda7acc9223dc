"""Jitted wrapper: dirty-mask → work queue → fused kernel → merged state."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.workqueue import compact_stripe_ids

from ..common import striped_rows, xor_reduce
from . import ref
from .redundancy import fused_update_striped


@functools.partial(
    jax.jit, static_argnames=("stripe_width", "use_pallas", "interpret"))
def fused_update(
    lanes2d: jax.Array,
    old_checksums: jax.Array,
    old_parity: jax.Array,
    block_dirty: jax.Array,
    stripe_dirty: jax.Array,
    stripe_width: int = 4,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Masked checksum+parity refresh. Semantics == ref.fused_update."""
    if not use_pallas:
        return ref.fused_update(
            lanes2d, old_checksums, old_parity, block_dirty, stripe_dirty,
            stripe_width)
    nb, L = lanes2d.shape
    striped = striped_rows(lanes2d, stripe_width)
    ns = striped.shape[0]
    # Compact dirty stripe ids into the work queue (shared helper with the
    # XLA path); pad by repeating the last live id so trailing grid steps
    # re-address the same block (DMA elided).
    ids, count, _ = compact_stripe_ids(stripe_dirty, ns, pad_repeat_last=True)
    par_raw, cks_part = fused_update_striped(
        striped, ids, count[None], interpret=interpret)
    # Fold the 128 lane partials by halving XORs over a (blocks, 128) view:
    # a generic reduce, or any fold of the (ns, P, 128) array, compiles
    # ~50x slower for TPU at a 2 GiB region.
    cks_new = xor_reduce(cks_part.reshape(ns * stripe_width, -1), 1)[:nb]
    cks = jnp.where(block_dirty, cks_new, old_checksums)
    par = jnp.where(stripe_dirty[:, None], par_raw.reshape(ns, L), old_parity)
    return cks, par
