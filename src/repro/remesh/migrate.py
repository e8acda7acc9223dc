"""Incremental re-striping of protected state onto a new device mesh.

Real deployments resize: a pod gains devices (grow) or loses a rack
(shrink).  Re-attaching the store on the new mesh would stop the world for
a full-leaf redundancy recompute; this module instead migrates **online**,
riding the same bounded-window discipline as the online shard rebuild
(:mod:`repro.scrub.rebuild`):

1. **Start** (one tick): every leaf is ``device_put`` onto the new mesh
   (value-identical — data never transforms, only its sharding), and
   zero-initialised new-geometry redundancy is laid out per the new
   shardings.  Zeros are safe capital: Algorithm 1 recomputes checksums
   *from data* for dirty blocks and whole-stripe parity *from data* for
   dirty stripes, so windows fill the arrays in without ever reading the
   zeros as truth.  The ``meta_ck`` seed is the checksum-of-checksums of
   the zero page (consistent by construction, kept consistent by every
   windowed update).
2. **Migrate** (bounded ticks): per leaf, a cursor walks the new *local*
   block space; each tick marks one window of ``remesh_bytes_per_tick``
   bytes dirty in the new bitvectors and dispatches the new engine's
   Algorithm-1 program (work-queue variant when the window fits, full
   fallback otherwise — counted in ``RemeshStatus.overflowed``).  Cost per
   tick tracks the window, never the leaf: the pinned bound is
   ``ticks == max_leaf ceil(n_blocks / window)``.
3. **Adopt** (the tick the last window lands): the OLD redundancy —
   frozen during migration except for ``on_write`` marks, and therefore
   crash-authoritative throughout — is read once, and every old
   ``dirty | shadow`` mark is translated into new-geometry dirty marks
   (:func:`translate_marks`), so writes that raced the migration re-enter
   the normal pipeline instead of leaving stale new redundancy.  Blocks
   the old cross-shard parity layer could not vouch quiescent at
   migration start (``xvalid`` False) are conservatively re-marked too —
   the freshness tracking seeds the handover (``RemeshStatus.
   xpar_seeded`` counts the rows it vouched for).  Then the store swaps
   wholesale: mesh, engines/groups, jit caches, and a **fresh patroller**
   under a bumped ``geometry_version`` — cross-shard parity folded across
   the old shard count is meaningless on the new one, so old images are
   discarded, never reinterpreted.

Crash story: until adoption the old red is the only truth — a crash
persists value-identical leaves plus old-geometry redundancy, and restart
recovers on the old mesh exactly as if the remesh had never been asked
for.  The ``remesh_migrate`` crash phase fires after every window with
that old view.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import checksum
from repro.core.engine import RedundancyEngine, _local_shape
from repro.core.blocks import make_meta
from repro.core.state import LeafRedundancy
from repro.faults.inject import bits_to_mask
from repro.scrub.rebuild import pack_mask_np


class RemeshError(RuntimeError):
    """Base class for elastic-remesh failures."""


class RemeshInProgressError(RemeshError):
    """A remesh is already queued or actively migrating."""


class RemeshGeometryError(RemeshError):
    """The requested mesh cannot host the attached leaves (uneven split,
    unknown axis, missing mesh, or an unsupported group mode)."""


@dataclasses.dataclass
class RemeshStatus:
    """Progress of one elastic remesh (surfaced on ``TickReport.remesh``).

    ``total_blocks``/``migrated`` count new-geometry *local* blocks (each
    window covers the same local range on every new shard in parallel);
    ``overflowed`` counts windows whose marks missed the work queue (full
    fallback ran); ``xpar_seeded`` counts old cross-shard-parity rows that
    vouched quiescence at start — rows it could not vouch re-enter the new
    geometry conservatively dirty at adoption."""
    from_shape: Tuple[int, ...]
    to_shape: Tuple[int, ...]
    total_blocks: int
    started_step: int
    migrated: int = 0
    xpar_seeded: int = 0
    ticks: int = 0
    overflowed: int = 0
    done: bool = False


def validate_remesh(store, new_mesh, specs: Mapping[str, Any]) -> None:
    """Typed pre-flight: every attached leaf must split evenly onto
    ``new_mesh`` under its spec, and every protected group must be a mode
    migration supports (``vilamb``/``none`` — ``sync`` keeps redundancy
    inline with writes and has no frozen-old-red migration story)."""
    if new_mesh is None or store.mesh is None:
        raise RemeshGeometryError(
            "elastic remesh needs a mesh on both sides (store.mesh and "
            "new_mesh); use attach() for machine-local stores")
    for g in store.groups.values():
        if g.policy.mode == "sync":
            raise RemeshGeometryError(
                f"group {g.label}: sync-mode leaves cannot remesh online "
                "(inline redundancy has no frozen-old-geometry window)")
    structs = getattr(store, "_structs", None)
    if not structs:
        raise RemeshGeometryError("store has no attached leaves to remesh")
    for name, st in structs.items():
        spec = specs.get(name)
        try:
            _local_shape(st.shape, spec, new_mesh)
        except (AssertionError, KeyError) as e:
            raise RemeshGeometryError(
                f"{name}: shape {tuple(st.shape)} does not re-stripe onto "
                f"mesh {dict(new_mesh.shape)} under spec {spec} ({e})"
            ) from e


def translate_marks(old_mask: np.ndarray, old_lanes_per_block: int,
                    new_lanes_per_block: int, new_n_blocks: int,
                    new_k: int) -> np.ndarray:
    """Translate per-block marks between block geometries through the one
    invariant space both share: global uint32 words of the flattened leaf
    (dim0 sharding keeps every shard's rows word-contiguous globally).

    ``old_mask`` is bool ``(k_old, nb_old)``; old shard ``s`` local block
    ``b`` covers global words ``[(s*nb_old + b) * L_old, ... + L_old)``.
    Returns bool ``(new_k, new_n_blocks)`` marking every new block whose
    word range intersects a marked old block — conservative by
    construction (a partial overlap marks the whole new block)."""
    old_mask = np.asarray(old_mask, bool)
    out = np.zeros((new_k * new_n_blocks,), bool)
    gb = np.flatnonzero(old_mask.reshape(-1))
    if gb.size:
        w0 = gb.astype(np.int64) * int(old_lanes_per_block)
        w1 = w0 + int(old_lanes_per_block)
        b0 = w0 // int(new_lanes_per_block)
        b1 = -(-w1 // int(new_lanes_per_block))          # ceil div
        np.clip(b0, 0, out.size, out=b0)
        np.clip(b1, 0, out.size, out=b1)
        for a, b in zip(b0, b1):
            out[a:b] = True
    return out.reshape(new_k, new_n_blocks)


class RemeshMigrator:
    """One in-progress mesh geometry change, paced over ticks.

    Construction blocks once per leaf for the ``device_put`` move (the
    moved arrays surface through ``TickReport.repaired`` — callers adopt
    them like any rebuild paste) and lays out zeroed new-geometry
    redundancy.  Each :meth:`step_once` marks one bounded window dirty in
    the new bitvectors and dispatches the new engine's Algorithm-1
    program; :meth:`adopt` performs the wholesale handover.
    """

    def __init__(self, store, new_mesh, new_specs: Mapping[str, Any],
                 leaves: Mapping[str, jax.Array], red, step: int):
        self.store = store
        self.new_mesh = new_mesh
        self.new_specs = dict(new_specs)
        pol = store.policy

        # New-geometry engines, one per protected group (same resolved
        # config — only mesh/specs change).
        self.new_engines: Dict[str, RedundancyEngine] = {}
        for g in store._protected():
            self.new_engines[g.label] = RedundancyEngine(
                {n: store._structs[n] for n in g.names}, g.engine.config,
                mesh=new_mesh,
                specs={n: self.new_specs[n] for n in g.names
                       if n in self.new_specs})

        # Move every attached leaf onto the new mesh (value-identical).
        self.moved: Dict[str, jax.Array] = {}
        for name in store._structs:
            if name not in leaves:
                continue
            self.moved[name] = jax.device_put(
                leaves[name],
                NamedSharding(new_mesh, self.new_specs.get(name, P())))

        # Zero-initialised new redundancy, pinned to the new shardings.
        # meta_ck seeds as the checksum-of-checksums of the zero page so
        # the incremental (queued) updates stay consistent from the first
        # window; everything else really is zeros (never read as truth —
        # only dirty blocks/stripes are ever recomputed-from-data into it).
        self.new_red: Dict[str, LeafRedundancy] = {}
        budget = (int(pol.remesh_bytes_per_tick)
                  or 4 * int(pol.patrol_bytes_per_tick))
        self.wb: Dict[str, int] = {}
        self.cur: Dict[str, int] = {}
        self.done_mask: Dict[str, np.ndarray] = {}
        total = 0
        for label, eng in self.new_engines.items():
            shardings = eng.red_shardings()
            for name, meta in eng.metas.items():
                kn = eng.shard_factor(name)
                nb = meta.n_blocks
                ck0 = jnp.asarray(checksum.meta_checksum(
                    jnp.zeros((nb,), jnp.uint32)), jnp.uint32)
                self.new_red[name] = jax.device_put(
                    LeafRedundancy(
                        checksums=jnp.zeros((nb * kn,), jnp.uint32),
                        parity=jnp.zeros(
                            (meta.n_stripes * kn, meta.lanes_per_block),
                            jnp.uint32),
                        dirty=jnp.zeros((meta.n_dirty_words * kn,),
                                        jnp.uint32),
                        shadow=jnp.zeros((meta.n_dirty_words * kn,),
                                         jnp.uint32),
                        meta_ck=jnp.full((kn,), ck0, jnp.uint32)),
                    shardings[name])
                self.wb[name] = (max(1, min(nb, budget
                                            // max(1, meta.bytes_per_block)))
                                 if budget else nb)
                self.cur[name] = 0
                self.done_mask[name] = np.zeros((nb,), bool)
                total += nb

        # Freshness seed from the old cross-shard parity layer: rows it
        # vouched quiescent at start need no conservative re-mark at
        # adoption; rows it could not (or leaves it never covered, when
        # the patroller tracked them) re-enter the new geometry dirty.
        self._stale0: Dict[str, np.ndarray] = {}
        seeded = 0
        pat = store.patroller
        if pat is not None:
            for name, xp in pat.xpar.items():
                if name in self.new_red and xp.xvalid is not None:
                    self._stale0[name] = ~np.asarray(xp.xvalid, bool)
                    seeded += int(np.asarray(xp.xvalid).sum())

        def mesh_dims(m):
            return tuple(int(m.shape[a]) for a in m.axis_names)

        self.status = RemeshStatus(
            from_shape=mesh_dims(store.mesh), to_shape=mesh_dims(new_mesh),
            total_blocks=total, started_step=int(step), xpar_seeded=seeded)
        self._jits: Dict[Any, Callable] = {}

    # ------------------------------------------------------------------ tick
    def step_once(self, leaves, out, report, step: Optional[int]) -> None:
        """Mark + dispatch one bounded window per unfinished leaf; fires
        the ``remesh_migrate`` crash phase with the still-authoritative
        OLD red view.  ``step`` is None from a stepless drain; the phase
        then omits the kwarg so the crash machine's counter fills it."""
        self.status.ticks += 1
        marks: Dict[str, Dict[str, jax.Array]] = {}
        for label, eng in self.new_engines.items():
            for name, meta in eng.metas.items():
                nb = meta.n_blocks
                if self.cur[name] >= nb:
                    continue
                wb = self.wb[name]
                start = min(self.cur[name], max(0, nb - wb))
                ids = np.arange(start, start + wb)
                fresh = ids[~self.done_mask[name][ids]]
                self.done_mask[name][ids] = True
                self.status.migrated += int(fresh.size)
                window = np.zeros((nb,), bool)
                window[ids] = True
                marks.setdefault(label, {})[name] = jnp.asarray(
                    pack_mask_np(window, meta.n_dirty_words))
                self.cur[name] = start + wb
        for label, wmap in marks.items():
            eng = self.new_engines[label]
            names = tuple(eng.metas)
            red_sub = {n: self.new_red[n] for n in names}
            red_sub = self._mark_fn(label, tuple(sorted(wmap)))(red_sub, wmap)
            queued = eng.has_queue and eng.queue_fits(red_sub)
            if eng.has_queue and not queued:
                self.status.overflowed += 1
            self.new_red.update(self._update_fn(label, queued)(
                {n: leaves[n] for n in names}, red_sub))
        if all(self.cur[n] >= eng.metas[n].n_blocks
               for eng in self.new_engines.values() for n in eng.metas):
            self.status.done = True
        report.remesh = self.status
        self.store._phase("remesh_migrate", red=dict(out),
                          **({} if step is None else {"step": int(step)}),
                          migrated=self.status.migrated,
                          ticks=self.status.ticks)

    # ------------------------------------------------------------- adoption
    def adopt(self, out, report) -> None:
        """Wholesale handover: translate old live marks into new dirty,
        swap mesh/engines/groups/jit caches, bump ``geometry_version``,
        rebuild the patroller fresh, and replace ``out``'s entries with
        the new-geometry redundancy."""
        from repro.core.store import _Group
        store = self.store
        for g in store._protected():
            old_eng = g.engine
            new_eng = self.new_engines[g.label]
            for name in g.names:
                old_meta = old_eng.metas[name]
                new_meta = new_eng.metas[name]
                k_old = old_eng.shard_factor(name)
                k_new = new_eng.shard_factor(name)
                r_old = out[name]
                live = bits_to_mask(
                    np.asarray(r_old.dirty) | np.asarray(r_old.shadow),
                    old_meta.n_blocks, shards=k_old
                ).reshape(k_old, old_meta.n_blocks)
                stale = self._stale0.get(name)
                if stale is not None:
                    live = live | stale[None, :]
                new_mask = translate_marks(
                    live, old_meta.lanes_per_block,
                    new_meta.lanes_per_block, new_meta.n_blocks, k_new)
                if new_mask.any():
                    words = np.concatenate([
                        pack_mask_np(new_mask[s], new_meta.n_dirty_words)
                        for s in range(k_new)])
                    r_new = self.new_red[name]
                    self.new_red[name] = dataclasses.replace(
                        r_new, dirty=jax.device_put(
                            jnp.asarray(words),
                            new_eng.red_shardings()[name].dirty))
        store.mesh = self.new_mesh
        store._specs = dict(self.new_specs)
        groups = {}
        for label, g in store.groups.items():
            eng = self.new_engines.get(label) if g.engine is not None else None
            # Carry the freshness clocks: the deadline counts from the
            # oldest unprotected write, and a migration moves data without
            # updating redundancy for post-start writes — a fresh _Group's
            # default clocks (step 0 / now) would both fire a spurious
            # steps-deadline right after adoption AND silently extend the
            # wall-clock deadline by the whole migration.
            groups[label] = _Group(label, g.policy, g.names, eng,
                                   last_update_step=g.last_update_step,
                                   last_update_time=g.last_update_time)
        store.groups = groups
        for n, meta in list(store._none_metas.items()):
            lshape = _local_shape(store._structs[n].shape,
                                  self.new_specs.get(n), self.new_mesh)
            store._none_metas[n] = make_meta(
                jax.ShapeDtypeStruct(lshape, store._structs[n].dtype),
                lanes_per_block=store.policy.lanes_per_block,
                stripe_data_blocks=store.policy.stripe_data_blocks)
        store._jit_update = {}
        store._jit_scrub = {}
        store._jit_misc = {}
        store.geometry_version += 1
        store.patroller = None
        if store.policy.patrol_bytes_per_tick > 0 and any(
                g.policy.mode == "vilamb" for g in store._protected()):
            from repro.scrub import ScrubPatroller
            store.patroller = ScrubPatroller(store)
        out.update(self.new_red)
        report.remesh = self.status

    # ------------------------------------------------------------- programs
    def _mark_fn(self, label: str, names: Tuple[str, ...]) -> Callable:
        """OR the same packed local block mask into every new shard's
        dirty words (per-shard under shard_map, collective-free — the
        window covers the same local range on every shard)."""
        key = ("mark", label, names)
        fn = self._jits.get(key)
        if fn is None:
            eng = self.new_engines[label]

            def local(red_l, wmap):
                o = dict(red_l)
                for n, w in wmap.items():
                    o[n] = dataclasses.replace(
                        red_l[n], dirty=red_l[n].dirty | w)
                return o

            specs = {n: eng.red_spec(n) for n in eng.metas}
            fn = self._jits[key] = jax.jit(shard_map(
                local, mesh=self.new_mesh,
                in_specs=(specs, {n: P() for n in names}),
                out_specs=specs, check_vma=False))
        return fn

    def _update_fn(self, label: str, queued: bool) -> Callable:
        """Jitted new-engine Algorithm-1 program (donates the migrating
        red — the migrator owns it exclusively until adoption)."""
        key = ("update", label, queued)
        fn = self._jits.get(key)
        if fn is None:
            eng = self.new_engines[label]
            step = (eng.redundancy_step_queued if queued
                    else eng.redundancy_step)
            fn = self._jits[key] = jax.jit(step, donate_argnums=(1,))
        return fn
