"""RedundancyEngine — the paper's contribution as a composable JAX module.

Modes (Table 1 of the paper):
  * ``none``   — No-Redundancy baseline.
  * ``sync``   — Pangolin-analogue: checksum+parity updated inside the step,
                 incrementally from the old/new value diff.
  * ``vilamb`` — the paper: dirty bits accumulate during steps; a periodic
                 ``redundancy_step`` (Algorithm 1) amortizes the update.

The engine is machine-local by construction (paper §3.3): when given a mesh
and per-leaf PartitionSpecs, every redundancy computation runs under
``shard_map`` on shard-local blocks with **zero collectives**; checksum,
parity, bitvector, and meta-checksum arrays are sharded alongside their
leaf.  That includes the ∝-dirty work-queue variant (each shard owns a
fixed-capacity queue sized from its local stripe count) and the overlap
form, whose per-shard fit flags are AND-folded on the host after the fetch
— never on device (see ``redundancy_step_async``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import bits, blocks, checksum, parity, workqueue
from .blocks import BlockMeta, DEFAULT_LANES_PER_BLOCK, DEFAULT_STRIPE_DATA_BLOCKS
from .state import LeafRedundancy, RedundancyState, empty_leaf_red, leaf_red_struct

# Dirty-event sentinel: "every block of this leaf was (potentially) written".
ALL = "__all__"
DirtyEvent = Union[str, jax.Array]  # ALL or bool row-mask over leading axis


@dataclasses.dataclass(frozen=True)
class RedundancyConfig:
    mode: str = "vilamb"                 # none | sync | vilamb
    period_steps: int = 8                # paper's update period T (in steps)
    scrub_period_steps: int = 64
    lanes_per_block: int = DEFAULT_LANES_PER_BLOCK
    stripe_data_blocks: int = DEFAULT_STRIPE_DATA_BLOCKS
    use_kernels: bool = False            # Pallas fused-update kernel
    kernel_interpret: bool = False       # Pallas interpreter (CPU tests)
    # XLA work-queue compaction: per-leaf queue capacity as a fraction of the
    # leaf's stripe count (<= 0 disables; see core/workqueue.py).  Overflow
    # (checked host-side via queue_fits) falls back to the full masked
    # recompute, so semantics never change.
    work_queue_frac: float = workqueue.DEFAULT_QUEUE_FRAC

    def __post_init__(self):
        assert self.mode in ("none", "sync", "vilamb"), self.mode


def _local_shape(shape, spec: Optional[P], mesh: Optional[Mesh]):
    """Per-shard local shape of a leaf under ``spec`` on ``mesh``.

    Raises (AssertionError on an undivisible dim, KeyError on an unknown
    mesh axis) rather than guessing — :func:`repro.remesh.validate_remesh`
    relies on that to vet a target geometry *before* queueing a migration.
    """
    if mesh is None or spec is None:
        return tuple(shape)
    out = []
    for i, dim in enumerate(shape):
        ax = spec[i] if i < len(spec) else None
        if ax is None:
            out.append(dim)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        k = int(np.prod([mesh.shape[a] for a in axes]))
        assert dim % k == 0, f"dim {dim} not divisible by mesh axes {axes} ({k})"
        out.append(dim // k)
    return tuple(out)


def _leaf_axes(spec: Optional[P]) -> Tuple[str, ...]:
    """All mesh axes a leaf is sharded over (flattened, order of appearance)."""
    if spec is None:
        return ()
    out = []
    for ax in spec:
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            out.append(a)
    return tuple(out)


class RedundancyEngine:
    """Builds jitted redundancy ops for a named dict of state leaves."""

    def __init__(
        self,
        leaf_structs: Mapping[str, Any],
        config: RedundancyConfig = RedundancyConfig(),
        mesh: Optional[Mesh] = None,
        specs: Optional[Mapping[str, P]] = None,
    ):
        self.config = config
        self.mesh = mesh
        self.specs = dict(specs or {})
        self.metas: Dict[str, BlockMeta] = {}
        # Global leaf shapes (as handed in); metas below are shard-local.
        self.global_leaf_structs = {
            name: jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype)
            for name, leaf in leaf_structs.items()}
        for name, leaf in leaf_structs.items():
            lshape = _local_shape(leaf.shape, self.specs.get(name), mesh)
            self.metas[name] = blocks.make_meta(
                jax.ShapeDtypeStruct(lshape, leaf.dtype),
                lanes_per_block=config.lanes_per_block,
                stripe_data_blocks=config.stripe_data_blocks,
            )
        self._kernel_ops = None
        if config.use_kernels:
            from repro.kernels.redundancy import ops as kops
            self._kernel_ops = kops
        # Static per-leaf work-queue capacities (0 = plain full recompute).
        self._queue_caps = {
            name: 0 if config.use_kernels else workqueue.queue_capacity(
                meta.n_stripes, config.work_queue_frac)
            for name, meta in self.metas.items()
        }
        # The compiled capacities (rungs) of the queued overlap program,
        # ascending; empty without a queue.  Derived from the largest
        # leaf's capacity: at rung K each queued leaf takes min(K, its own
        # capacity) (_rung_cap), so the top rung is the leaves' own.
        self.queue_ladder: Tuple[int, ...] = workqueue.queue_ladder(
            max(self._queue_caps.values(), default=0))
        self._queue_fits_jit = None

    # ------------------------------------------------------------------ utils
    def shard_factor(self, name: str) -> int:
        """Number of shards a leaf's redundancy arrays concatenate (1 = local)."""
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in _leaf_axes(self.specs.get(name))]) or 1)

    def _mck_out(self, x: jax.Array) -> jax.Array:
        """Normalize a meta-checksum for storage: scalar machine-local,
        ``(1,)`` per shard under a mesh (global ``(k,)``, one honest
        checksum-of-checksums per shard — a replicated scalar would need a
        collective to agree)."""
        return x.reshape((1,)) if self.mesh is not None else x

    def red_spec(self, name: str) -> LeafRedundancy:
        """PartitionSpecs for a leaf's redundancy arrays (dim0-sharded).

        ``meta_ck`` is sharded like the checksums it covers: one scalar per
        shard (global shape ``(shard_factor,)``) so each shard verifies its
        own checksum page without collectives.
        """
        axes = _leaf_axes(self.specs.get(name))
        s = P(axes if axes else None)
        return LeafRedundancy(checksums=s, parity=s, dirty=s, shadow=s, meta_ck=s)

    def red_structs(self, global_: bool = True) -> RedundancyState:
        """ShapeDtypeStructs of the redundancy state (global shapes)."""
        out = {}
        for name, meta in self.metas.items():
            st = leaf_red_struct(meta)
            if global_:
                k = self.shard_factor(name)
                st = LeafRedundancy(
                    checksums=jax.ShapeDtypeStruct((meta.n_blocks * k,), jnp.uint32),
                    parity=jax.ShapeDtypeStruct(
                        (meta.n_stripes * k, meta.lanes_per_block), jnp.uint32),
                    dirty=jax.ShapeDtypeStruct((meta.n_dirty_words * k,), jnp.uint32),
                    shadow=jax.ShapeDtypeStruct((meta.n_dirty_words * k,), jnp.uint32),
                    meta_ck=jax.ShapeDtypeStruct(
                        (k,) if self.mesh is not None else (), jnp.uint32),
                )
            out[name] = st
        return out

    def red_shardings(self) -> Dict[str, LeafRedundancy]:
        assert self.mesh is not None
        return {
            name: jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                               self.red_spec(name),
                               is_leaf=lambda x: isinstance(x, P))
            for name in self.metas
        }

    def _wrap(self, fn: Callable, leaf_in_specs, red_in: bool, extra_specs=()):
        """shard_map a per-shard-local function when a mesh is present."""
        if self.mesh is None:
            return fn
        in_specs = list(leaf_in_specs)
        if red_in:
            in_specs.append({n: self.red_spec(n) for n in self.metas})
        in_specs.extend(extra_specs)
        out_specs = {n: self.red_spec(n) for n in self.metas}
        return shard_map(
            fn, mesh=self.mesh, in_specs=tuple(in_specs),
            out_specs=out_specs, check_vma=False,
        )

    def _leaf_specs_dict(self) -> Dict[str, P]:
        return {n: self.specs.get(n, P()) for n in self.metas}

    # ------------------------------------------------------------- primitives
    def queue_capacity(self, name: str) -> int:
        """Static work-queue capacity (stripes) for a leaf; 0 = no queue."""
        return self._queue_caps[name]

    def _rung_cap(self, name: str, rung: Optional[int]) -> int:
        """A leaf's queue capacity at ``rung`` (None = the top)."""
        cap = self._queue_caps[name]
        return cap if rung is None or not cap else min(rung, cap)

    def queue_rows(self, rung: Optional[int] = None) -> int:
        """Queue rows one queued pass at ``rung`` pays for, over its leaves
        and shards (padding included): the ``K`` of every queue."""
        return sum(self._rung_cap(n, rung) * self.shard_factor(n)
                   for n in self.metas)

    @property
    def has_queue(self) -> bool:
        """Whether the queued Algorithm-1 variant exists for this engine.

        Mesh or machine-local alike: under a mesh every shard runs its own
        fixed-capacity queue (capacity from the *local* stripe count) inside
        ``shard_map``, and the fit predicate is evaluated per shard.
        """
        return any(self._queue_caps.values())

    def queue_fits(self, red: RedundancyState) -> bool:
        """Host-side overflow check: do all live dirty stripes fit the queues?

        The fit half of :meth:`queue_check`; False where no leaf has a
        queue.
        """
        return self.has_queue and self.queue_check(red)[0]

    def queue_check(self, red: RedundancyState) -> Tuple[bool, int]:
        """``(fits, dirty stripes)`` of the live view, in one transfer.

        One tiny jitted popcount pass over the bitvectors (O(n_blocks) bits,
        no data read) — the cost that buys dispatching the ∝-dirty queued
        program instead of the full one.  Under a mesh the per-shard
        dirty-stripe counts are each checked against the shard-local
        capacity (the queues are per shard); ``fits`` is False where no
        leaf has a queue.  This exact check is the blocking path's — the
        overlap pipeline computes the same predicate and count inside the
        dispatched program instead.  The stripe count is what the pass
        about to run covers (Algorithm 1's work).
        """
        if self._queue_fits_jit is None:
            def fits(red_l):
                oks, total = [], jnp.int32(0)
                for name, meta in self.metas.items():
                    r = red_l[name]
                    k = self.shard_factor(name)
                    bd = bits.unpack_rows(jnp.bitwise_or(r.dirty, r.shadow),
                                          k, meta.n_blocks)
                    counts = jax.vmap(lambda m: workqueue.stripe_dirty_count(
                        self._stripe_dirty(meta, m)))(bd)
                    total = total + jnp.sum(counts, dtype=jnp.int32)
                    cap = self._queue_caps[name]
                    if cap:
                        oks.append(jnp.all(counts <= cap))
                ok = jnp.all(jnp.stack(oks)) if oks else jnp.asarray(False)
                return ok, total
            self._queue_fits_jit = jax.jit(fits)
        ok, total = jax.device_get(self._queue_fits_jit(red))
        return bool(ok), int(total)

    @property
    def alg1_stripe_bytes(self) -> int:
        """Algorithm 1's bytes for one dirty stripe: its P data blocks read,
        its parity block and P checksums written."""
        block = self.config.lanes_per_block * 4
        p = self.config.stripe_data_blocks
        return p * block + block + p * 4

    def _update_leaf(self, meta: BlockMeta, lanes, old: LeafRedundancy,
                     bdirty, sdirty, cap: int):
        """Masked checksum+parity+meta refresh (Alg. 1 lines 7-22).

        Three interchangeable bitwise-identical realizations: the Pallas
        fused kernel, the XLA work-queue compaction of capacity ``cap``
        (cost ∝ ``cap``; caller guarantees the fit), or the full-region
        masked recompute (``cap`` 0).
        """
        if self._kernel_ops is not None:
            cks, par = self._kernel_ops.fused_update(
                lanes, old.checksums, old.parity, bdirty, sdirty,
                meta.stripe_data_blocks, interpret=self.config.kernel_interpret)
            return cks, par, checksum.meta_checksum(cks)
        if cap:
            ids, _, _ = workqueue.compact_stripe_ids(sdirty, cap)
            return workqueue.queued_update(
                lanes, old.checksums, old.parity, old.meta_ck, bdirty, ids,
                meta.stripe_data_blocks)
        return workqueue.full_update(
            lanes, old.checksums, old.parity, bdirty, sdirty,
            meta.stripe_data_blocks)

    def _stripe_dirty(self, meta: BlockMeta, bdirty):
        return blocks.stripe_dirty_mask(meta, bdirty)

    # -------------------------------------------------------------- init
    def init(self, leaves: Mapping[str, jax.Array]) -> RedundancyState:
        """Full redundancy computation (file-creation time in the paper)."""
        def local(ls):
            out = {}
            for name, meta in self.metas.items():
                lanes = blocks.to_lanes(ls[name], meta)
                cks = checksum.block_checksums(lanes)
                par = parity.stripe_parity(lanes, meta.stripe_data_blocks)
                out[name] = LeafRedundancy(
                    checksums=cks, parity=par,
                    dirty=jnp.zeros((meta.n_dirty_words,), jnp.uint32),
                    shadow=jnp.zeros((meta.n_dirty_words,), jnp.uint32),
                    meta_ck=self._mck_out(checksum.meta_checksum(cks)),
                )
            return out
        fn = self._wrap(local, [self._leaf_specs_dict()], red_in=False)
        return jax.jit(fn)(dict(leaves))

    # -------------------------------------------------------------- marking
    def mark_dirty(
        self, red: RedundancyState, events: Mapping[str, DirtyEvent]
    ) -> RedundancyState:
        """OR dirty events into the bitvectors (run inside the train step).

        Events are domain-space: ``ALL`` for dense leaves, or a bool row-mask
        over the leaf's leading axis (embedding rows / experts / KV pages) —
        converted to shard-local block masks under shard_map.
        """
        events = dict(events)

        def local(red_l, evs):
            out = dict(red_l)
            for name, ev in evs.items():
                meta = self.metas[name]
                r = red_l[name]
                if isinstance(ev, str) and ev == ALL:
                    mask = jnp.ones((meta.n_blocks,), bool)
                elif (ev.ndim == 1 and len(meta.shape) >= 1
                      and ev.shape[0] == meta.shape[0]
                      and meta.n_blocks == meta.shape[0]):
                    # Fast path: rows map 1:1 to blocks (4 KiB-page heaps,
                    # KV pages) — the event mask IS the block mask.
                    mask = ev
                else:
                    # Direct row-mask -> block-mask reduction: no full-event
                    # nonzero sort, cost tracks the event shape.
                    mask = blocks.row_mask_block_mask(meta, ev, row_dims=ev.ndim)
                out[name] = dataclasses.replace(r, dirty=bits.mark(r.dirty, mask))
            return out

        if self.mesh is None:
            return local(red, events)
        ev_specs = {}
        for name, ev in events.items():
            if isinstance(ev, str):
                ev_specs[name] = None
            else:
                spec = self.specs.get(name, P())
                lead = [spec[i] if i < len(spec) else None for i in range(ev.ndim)]
                ev_specs[name] = P(*lead)
        # split static ALL markers from array events for shard_map
        arr_events = {n: e for n, e in events.items() if not isinstance(e, str)}
        all_names = [n for n, e in events.items() if isinstance(e, str)]

        def local2(red_l, arr_evs):
            evs = dict(arr_evs)
            for n in all_names:
                evs[n] = ALL
            return local(red_l, evs)

        fn = shard_map(
            local2, mesh=self.mesh,
            in_specs=({n: self.red_spec(n) for n in self.metas},
                      {n: ev_specs[n] for n in arr_events}),
            out_specs={n: self.red_spec(n) for n in self.metas},
            check_vma=False,
        )
        return fn(red, arr_events)

    # -------------------------------------------------- Algorithm 1 (vilamb)
    def _alg1_parts(self, ls, red_l, queued: bool, want_fits: bool,
                    rung: Optional[int] = None):
        """Shared Algorithm-1 body (traceable): per-leaf masked update.

        Lines 2-4: snapshot ``dirty | shadow`` (include leftover shadow
        from a crash); lines 7-18 + 22: masked checksum + parity recompute
        with the meta-checksum refreshed incrementally on the work-queue
        path.  Returns ``({name: (cks, par, meta_ck, snapshot)}, fits,
        counts)`` — the blocking and overlap entry points differ only in
        how they fold these into dirty/shadow outputs.  ``fits`` (the
        device-side queue-fit predicate over every queued leaf, against
        its capacity at ``rung`` when queued, else at the top) and
        ``counts`` are only evaluated when requested.  ``counts`` is int32
        ``[stripes, need]``: the dirty stripes this pass covers, summed
        over leaves, and the smallest rung that would hold the snapshot —
        the largest queued leaf's count, or ``workqueue.NO_RUNG`` where a
        leaf's count exceeds its own top capacity.
        """
        parts: Dict[str, Tuple] = {}
        fits = []
        stripes = need = jnp.int32(0)
        for name, meta in self.metas.items():
            r = red_l[name]
            snapshot = jnp.bitwise_or(r.dirty, r.shadow)
            bdirty = bits.unpack(snapshot, meta.n_blocks)
            sdirty = self._stripe_dirty(meta, bdirty)
            cap = self._rung_cap(name, rung if queued else None)
            if want_fits and cap:
                fits.append(workqueue.stripe_fits(sdirty, cap))
            if want_fits:
                n = workqueue.stripe_dirty_count(sdirty)
                stripes = stripes + n
                top = self._queue_caps[name]
                if top:
                    need = jnp.maximum(need, jnp.where(
                        n <= top, n, jnp.int32(workqueue.NO_RUNG)))
            lanes = blocks.to_lanes(ls[name], meta)
            cks, par, meta_ck = self._update_leaf(
                meta, lanes, r, bdirty, sdirty, cap if queued else 0)
            parts[name] = (cks, par, meta_ck, snapshot)
        fits_all = jnp.all(jnp.stack(fits)) if fits else jnp.asarray(True)
        return parts, fits_all, jnp.stack([stripes, need])

    def _alg1(self, leaves, red: RedundancyState, queued: bool
              ) -> RedundancyState:
        def local(ls, red_l):
            parts, _, _ = self._alg1_parts(ls, red_l, queued,
                                           want_fits=False)
            out = {}
            for name, (cks, par, meta_ck, snapshot) in parts.items():
                # Lines 19-20: in the paper a fence orders "redundancy
                # written" before "shadow cleared". Inside one jitted step
                # the returned state is atomic; crash-atomicity across steps
                # is provided by the checkpoint layer persisting (data, cks,
                # par, shadow) together. Clearing shadow (line 6 cleared
                # dirty) is therefore safe.
                out[name] = LeafRedundancy(
                    checksums=cks, parity=par,
                    dirty=jnp.zeros_like(snapshot),
                    shadow=jnp.zeros_like(snapshot),
                    meta_ck=self._mck_out(meta_ck),
                )
            return out

        fn = self._wrap(local, [self._leaf_specs_dict()], red_in=True)
        return fn(dict(leaves), red)

    def redundancy_step(
        self, leaves: Mapping[str, jax.Array], red: RedundancyState
    ) -> RedundancyState:
        """One invocation of the paper's background update thread.

        Per leaf: snapshot dirty→shadow, clear dirty, recompute checksums of
        dirty blocks and parity of stripes containing a dirty block, clear
        shadow, refresh the meta-checksum. Fences become data dependencies.
        This is the reference full-region path — safe at any dirty fraction.
        """
        return self._alg1(leaves, red, queued=False)

    def redundancy_step_queued(
        self, leaves: Mapping[str, jax.Array], red: RedundancyState
    ) -> RedundancyState:
        """Work-queue Algorithm 1: cost ∝ dirty stripes, not region size.

        Bitwise-identical to :meth:`redundancy_step` **iff** every leaf's
        dirty-stripe count fits its queue capacity — check
        :meth:`queue_fits` (host-side) before dispatching, as
        ``ProtectedStore.tick`` does.  A truncated queue would silently
        leave stripes stale, so never call this unguarded.
        """
        return self._alg1(leaves, red, queued=True)

    flush = redundancy_step  # battery/preemption flush = forced update pass

    # ------------------------------------------- Algorithm 1, overlap form
    def redundancy_step_async(
        self, leaves: Mapping[str, jax.Array], red: RedundancyState,
        queued: bool = False, rung: Optional[int] = None,
    ) -> Tuple[RedundancyState, jax.Array, jax.Array]:
        """Algorithm 1 restructured for sync-free overlapped dispatch.

        ``rung`` (queued only; None = the top) is the queue capacity this
        program compiles, one of :attr:`queue_ladder`.

        Same snapshot-merge and per-leaf math as :meth:`redundancy_step` /
        :meth:`redundancy_step_queued` — one donated in-place program — but
        returning ``(red_out, fits, counts)`` so no host check guards
        adoption:

        * ``fits`` is the device-computed queue-fit predicate
          (``queue_fits`` without the host round trip, against the queues
          at ``rung``); the dispatcher fetches it via a non-blocking async
          copy and uses it retrospectively as the overflow flag for *this*
          pass, and one tick ahead as the speculation signal for the next.
        * The returned state is valid **unconditionally**.  Under
          ``queued=True`` the scattered checksums/parity are correct fresh
          values for every stripe that made the queue; ``red_out.shadow``
          is ``where(overflowed, snapshot, 0)``, so on overflow everything
          the truncated queue may have missed stays conservatively marked
          (epoch A survives in shadow) until the dispatcher runs the
          full-recompute fallback.  ``red_out.dirty`` is the fresh epoch-B
          bitmap the foreground's next ``on_write`` marks into.
          :meth:`redundancy_step_queued`'s "never unguarded" contract is
          thus discharged on device.
        * ``counts`` is int32 ``[stripes, need]``, fetched with ``fits`` in
          the same transfer: the dirty stripes in the snapshot (the work
          this pass covers when it does not overflow) and the smallest
          queue capacity that holds every leaf's share of them
          (:meth:`_alg1_parts`), from which the dispatcher sizes the next
          pass's rung.

        Under a mesh the whole body runs per shard inside ``shard_map``
        (zero collectives): each shard compacts its own queue, and ``fits``
        is the **per-shard** flag array (global shape ``(n_devices,)``,
        sharded over every mesh axis), and ``counts`` one row per device,
        ``(n_devices, 2)``.  The overflow
        select is per shard too — only the shards whose local queue
        overflowed keep their snapshot marked.  Dispatchers never fold the flags on device: the
        store stacks them into its batched fits vector and AND-folds the
        fetched row on the host at resolution
        (``repro.core.workqueue.fold_fits_host``), so this program — and
        the batched multi-group program wrapping it — stays
        collective-free.
        """
        def local(ls, red_l):
            parts, fits_all, counts = self._alg1_parts(
                ls, red_l, queued, want_fits=True, rung=rung)
            overflowed = (jnp.logical_not(fits_all) if queued
                          else jnp.asarray(False))
            out: RedundancyState = {}
            for name, (cks, par, meta_ck, snapshot) in parts.items():
                out[name] = LeafRedundancy(
                    checksums=cks, parity=par,
                    dirty=jnp.zeros_like(snapshot),
                    shadow=jnp.where(overflowed, snapshot,
                                     jnp.zeros_like(snapshot)),
                    meta_ck=self._mck_out(meta_ck),
                )
            if self.mesh is not None:
                fits_all = fits_all.reshape((1,))
                counts = counts.reshape((1, 2))
            return out, fits_all, counts

        if self.mesh is None:
            return local(dict(leaves), red)
        axes = tuple(self.mesh.axis_names)
        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(self._leaf_specs_dict(),
                      {n: self.red_spec(n) for n in self.metas}),
            out_specs=({n: self.red_spec(n) for n in self.metas}, P(axes),
                       P(axes)),
            check_vma=False,
        )
        return fn(dict(leaves), red)

    # ----------------------------------------------------- sync (Pangolin)
    def sync_update(
        self,
        old_leaves: Mapping[str, jax.Array],
        new_leaves: Mapping[str, jax.Array],
        red: RedundancyState,
    ) -> RedundancyState:
        """Pangolin-analogue inline update from the old/new diff.

        Valid only when redundancy was up-to-date before the step (sync-mode
        invariant). Reads 2x the changed data, nothing else — the paper's
        micro-buffer diff advantage (§4.2).
        """
        def local(ols, nls, red_l):
            out = {}
            for name, meta in self.metas.items():
                r = red_l[name]
                o = blocks.to_lanes(ols[name], meta)
                n = blocks.to_lanes(nls[name], meta)
                cks = r.checksums ^ checksum.checksum_diff(o, n)
                par = r.parity ^ parity.parity_diff(o, n, meta.stripe_data_blocks)
                out[name] = LeafRedundancy(
                    checksums=cks, parity=par, dirty=r.dirty, shadow=r.shadow,
                    meta_ck=self._mck_out(checksum.meta_checksum(cks)),
                )
            return out

        fn = self._wrap(
            local, [self._leaf_specs_dict(), self._leaf_specs_dict()], red_in=True)
        return fn(dict(old_leaves), dict(new_leaves), red)

    def sync_update_rows(
        self,
        name: str,
        r: LeafRedundancy,
        rows: jax.Array,
        old_rows: jax.Array,
        new_rows: jax.Array,
    ) -> LeafRedundancy:
        """Sparse Pangolin update when rows map 1:1 to blocks.

        The 4 KiB-page-heap fast path (benchmarks, KV pages with
        row-per-block geometry): cost is O(touched rows), not O(leaf).
        ``rows`` must be unique; rows sharing a stripe XOR-accumulate their
        parity deltas through one segment-XOR scatter (not last-write-wins),
        and the meta-checksum is updated incrementally from the touched rows.
        """
        meta = self.metas[name]
        assert self.mesh is None, "row fast path is host/local only"
        assert len(meta.shape) >= 1 and meta.n_blocks == meta.shape[0], (
            f"{name}: rows do not map 1:1 to blocks")
        S = meta.stripe_data_blocks
        old_lanes = jax.lax.bitcast_convert_type(old_rows, jnp.uint32)
        new_lanes = jax.lax.bitcast_convert_type(new_rows, jnp.uint32)
        old_lanes = old_lanes.reshape(old_lanes.shape[0], -1)
        new_lanes = new_lanes.reshape(new_lanes.shape[0], -1)
        bids = rows.astype(jnp.uint32)
        lids = jnp.arange(old_lanes.shape[1], dtype=jnp.uint32)[None, :]
        salt = checksum.lane_salt(bids[:, None], lids)
        dck = jax.lax.reduce(
            checksum.fmix32(old_lanes ^ salt) ^ checksum.fmix32(new_lanes ^ salt),
            jnp.uint32(0), jax.lax.bitwise_xor, (1,))
        old_cks = r.checksums[rows]
        new_cks = old_cks ^ dck
        cks = r.checksums.at[rows].set(new_cks)
        par = parity.scatter_xor_stripes(
            r.parity, (rows // S).astype(jnp.int32), old_lanes ^ new_lanes)
        meta_ck = r.meta_ck ^ checksum.meta_checksum_delta(old_cks, new_cks, rows)
        return dataclasses.replace(
            r, checksums=cks, parity=par, meta_ck=meta_ck)

    # ------------------------------------------------------------- scrubbing
    def scrub(
        self, leaves: Mapping[str, jax.Array], red: RedundancyState
    ) -> Dict[str, jax.Array]:
        """Verification pass over clean blocks (paper §3.4).

        Returns per-leaf bool[n_blocks] mismatch masks. The double-check
        protocol (re-verify cleanliness after a mismatch) is enforced here by
        evaluating cleanliness and checksums on the same immutable snapshot —
        the host-level loop re-runs scrub after quiescing if any mismatch
        fires, mirroring the paper's second check.
        """
        def local(ls, red_l):
            out = {}
            for name, meta in self.metas.items():
                r = red_l[name]
                clean = ~bits.unpack(jnp.bitwise_or(r.dirty, r.shadow), meta.n_blocks)
                lanes = blocks.to_lanes(ls[name], meta)
                fresh = checksum.block_checksums(lanes)
                out[name] = clean & (fresh != r.checksums)
            return out

        if self.mesh is None:
            return local(dict(leaves), red)
        out_specs = {
            n: P(_leaf_axes(self.specs.get(n)) or None) for n in self.metas
        }
        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(self._leaf_specs_dict(), {n: self.red_spec(n) for n in self.metas}),
            out_specs=out_specs, check_vma=False,
        )
        return fn(dict(leaves), red)

    def verify_window_fn(self, name: str, window: int,
                         want_slab: bool = False) -> Callable:
        """Bounded patrol probe over one leaf (the scrub patroller's core).

        Returns an **unjitted** callable ``fn(leaf, r, start)`` — callers
        own jit + caching (``start`` is traced, so one compile per
        ``(leaf, window, want_slab)`` serves every cursor position).  Per
        shard it checksums the ``window`` local blocks at ``[start,
        start + window)`` and compares against the stored per-block
        checksums, exactly like :meth:`scrub` but over a bounded slab — the
        per-tick byte budget is ``window * meta.bytes_per_block`` per
        shard.  Outputs (global shapes, dim0 = shard):

        * ``mism``  bool ``(k, window)`` — clean-and-mismatching (corrupt),
        * ``clean`` bool ``(k, window)`` — outside the vulnerability window
          and inside the block range (checksum comparison meaningful),
        * ``slab``  uint32 ``(k, window, lanes_per_block)`` (only when
          ``want_slab``) — the raw lanes read anyway, exported so the
          caller can fold cross-shard parity from the same pass.

        Window positions past ``n_blocks`` are clamped and reported
        not-clean.  Under a mesh the body runs per shard inside
        ``shard_map`` with **zero collectives** (the PR 5 program rule);
        machine-local it is the plain function with ``k == 1``.
        """
        meta = self.metas[name]
        spec = self.specs.get(name, P())

        def local(leaf, r, start):
            lanes = blocks.to_lanes(leaf, meta)
            ids = jnp.arange(window, dtype=jnp.int32) + start
            valid = ids < meta.n_blocks
            safe = jnp.clip(ids, 0, meta.n_blocks - 1)
            slab = lanes[safe]
            # Position-salted: block_offset makes the windowed checksums
            # comparable to the stored full-leaf ones at the same ids.
            fresh = checksum.block_checksums(slab, block_offset=start)
            live = bits.unpack(jnp.bitwise_or(r.dirty, r.shadow),
                               meta.n_blocks)
            clean = valid & ~live[safe]
            mism = clean & (fresh != r.checksums[safe])
            out = (mism.reshape(1, window), clean.reshape(1, window))
            if want_slab:
                out += (slab.reshape(1, window, meta.lanes_per_block),)
            return out

        if self.mesh is None:
            return local
        axes = _leaf_axes(spec)
        s2 = P(axes) if axes else P(None)
        out_specs = (s2, s2) + ((s2,) if want_slab else ())
        return shard_map(
            local, mesh=self.mesh,
            in_specs=(spec, self.red_spec(name), P()),
            out_specs=out_specs, check_vma=False,
        )

    def live_words_fn(self, name: str) -> Callable:
        """``fn(r) -> dirty | shadow`` for one leaf — the patroller's
        per-tick write sample (global packed words, ``(k * n_dirty_words,)``
        under a mesh).  Unjitted; a tiny elementwise OR, collective-free
        by construction."""
        def fn(r):
            return jnp.bitwise_or(r.dirty, r.shadow)
        return fn

    def shard_lanes_fn(self, name: str) -> Callable:
        """``fn(leaf) -> uint32 (k, n_blocks, lanes_per_block)`` — every
        shard's block-lane view stacked along a fresh leading axis.

        The cross-shard parity primitive: XOR-folding the result over dim0
        (in a separate tiny cross-shard host program)
        yields one parity row per *local* block covering the same-indexed
        block of every shard.  Per shard the body is a pure reshape —
        collective-free; machine-local it returns ``(1, nb, L)``.
        """
        meta = self.metas[name]
        spec = self.specs.get(name, P())

        def local(leaf):
            lanes = blocks.to_lanes(leaf, meta)
            return lanes.reshape(1, meta.n_blocks, meta.lanes_per_block)

        if self.mesh is None:
            return local
        axes = _leaf_axes(spec)
        return shard_map(
            local, mesh=self.mesh, in_specs=(spec,),
            out_specs=P(axes) if axes else P(None), check_vma=False,
        )

    def verify_meta(self, red: RedundancyState) -> Dict[str, jax.Array]:
        """Check the checksum-of-checksums (detects corrupted checksum pages).

        Under a mesh each shard verifies its own checksum page against its
        own ``meta_ck`` entry inside ``shard_map``; the per-leaf result is
        the AND over shards (a cold-path fold over ``shard_factor`` bools).
        """
        if self.mesh is None:
            return {
                name: checksum.meta_checksum(r.checksums) == r.meta_ck
                for name, r in red.items()
            }

        def local(red_l):
            return {
                name: (checksum.meta_checksum(r.checksums)
                       == r.meta_ck.reshape(())).reshape((1,))
                for name, r in red_l.items()
            }

        out_specs = {
            n: P(_leaf_axes(self.specs.get(n)) or None) for n in self.metas
        }
        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=({n: self.red_spec(n) for n in self.metas},),
            out_specs=out_specs, check_vma=False,
        )
        per_shard = fn({n: red[n] for n in self.metas})
        return {name: jnp.all(v) for name, v in per_shard.items()}

    # -------------------------------------------------------------- recovery
    def recover_block(
        self, leaf: jax.Array, r: LeafRedundancy, name: str, block_id
    ) -> Tuple[jax.Array, jax.Array]:
        """Reconstruct one corrupted block from its stripe.

        Returns (repaired_leaf, ok). ``ok`` is False when the stripe is
        vulnerable (any *other* member dirty/shadow-set) — the paper's §3.3
        recoverability rule. The paper left recovery unimplemented; we do not.

        ``block_id`` is in global block space; under a mesh it addresses
        shard ``block_id // meta.n_blocks``, whose local lane view is
        sliced out for the rebuild (dim0 sharding, see
        :func:`repro.core.blocks.shard_slice`).
        """
        meta = self.metas[name]
        k = self.shard_factor(name)
        par_row = r.parity[blocks.global_stripe_id(meta, block_id)]
        shard, block_id = divmod(int(block_id), meta.n_blocks)
        sub, put = blocks.shard_slice(leaf, meta, k, shard)
        nw = meta.n_dirty_words
        live = jnp.bitwise_or(r.dirty, r.shadow)[shard * nw:(shard + 1) * nw]
        sid = block_id // meta.stripe_data_blocks
        member_ids = sid * meta.stripe_data_blocks + jnp.arange(meta.stripe_data_blocks)
        in_range = member_ids < meta.n_blocks
        dmask = bits.unpack(live, meta.n_blocks)
        member_dirty = jnp.where(
            in_range, dmask[jnp.clip(member_ids, 0, meta.n_blocks - 1)], False)
        others_clean = jnp.all(~member_dirty | (member_ids == block_id))
        lanes = blocks.to_lanes(sub, meta)
        rebuilt = parity.reconstruct_block(
            lanes, par_row, meta.stripe_data_blocks, block_id, sid)
        new_lanes = lanes.at[block_id].set(
            jnp.where(others_clean, rebuilt, lanes[block_id]))
        return put(blocks.from_lanes(new_lanes, meta)), others_clean

    # ------------------------------------------------------------ accounting
    def vulnerable_masks(self, red: RedundancyState) -> Dict[str, jax.Array]:
        """Per-leaf bool[n_blocks] of blocks inside the vulnerability window.

        ``dirty | shadow`` unpacked — the exact block set whose redundancy
        is stale (paper §3.3): corruptions landing here are the knob-bounded
        accepted loss; everything outside must be scrub-detectable.  The
        counts in :meth:`dirty_stats` are reductions of these masks.  Under
        a mesh the mask is in global block space (per-shard bitvectors
        unpacked shard by shard, shard ``s`` local block ``b`` at index
        ``s * n_blocks + b`` — the same layout scrub masks use).
        """
        out: Dict[str, jax.Array] = {}
        for name, meta in self.metas.items():
            r = red[name]
            out[name] = bits.unpack_rows(
                jnp.bitwise_or(r.dirty, r.shadow),
                self.shard_factor(name), meta.n_blocks).reshape(-1)
        return out

    def dirty_stats(self, red: RedundancyState) -> Dict[str, Dict[str, jax.Array]]:
        """Dirty/vulnerable-stripe counts (feeds §4.7 battery + §4.8 MTTDL).

        Totals are global (local geometry x shard count) so flush sizing and
        MTTDL see the whole region under a mesh.
        """
        out = {}
        for name, meta in self.metas.items():
            r = red[name]
            k = self.shard_factor(name)
            live = jnp.bitwise_or(r.dirty, r.shadow)
            bdirty = bits.unpack_rows(live, k, meta.n_blocks)
            sdirty = jax.vmap(lambda m: self._stripe_dirty(meta, m))(bdirty)
            out[name] = {
                "dirty_blocks": jnp.sum(bdirty, dtype=jnp.int32),
                "vulnerable_stripes": jnp.sum(sdirty, dtype=jnp.int32),
                "total_blocks": meta.n_blocks * k,
                "total_stripes": meta.n_stripes * k,
            }
        return out
