"""ProtectedStore — the library facade that owns the redundancy lifecycle.

The paper presents Vilamb as a *user-space library* with one tunable knob
between performance and redundancy freshness. This module is that library
surface: callers hand over any pytree of protected state and interact with
exactly three calls —

  * ``store.attach(pytree, specs=...)``   declare what is protected and how
  * ``store.on_write(red, events=...)``   inside the (jitted) mutation step
  * ``store.tick(leaves, red, step)``     once per host step; schedules
    Algorithm-1 updates, scrubbing with the paper's double-check, straggler
    back-off, and freshness deadlines internally

plus ``flush`` for the preemption/battery path.  Policies are declarative
and **per leaf group** (Tvarak's heterogeneous-region argument): params may
run ``sync`` (Pangolin-analogue inline diff) while optimizer moments and KV
pages run ``vilamb`` with different periods.  Each distinct resolved policy
compiles down to one :class:`~repro.core.engine.RedundancyEngine`.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import fnmatch
import queue
import statistics
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import flatten_dict

from . import bits
from . import policy as policy_mod
from . import trace
from . import workqueue
from .blocks import (DEFAULT_LANES_PER_BLOCK, DEFAULT_STRIPE_DATA_BLOCKS,
                     BlockMeta, make_meta)
from .engine import ALL, RedundancyConfig, RedundancyEngine, _local_shape
from .state import LeafRedundancy, RedundancyState, leaf_red_struct

MODES = ("none", "sync", "vilamb")


def _async_tick_default() -> bool:
    """Default for ``RedundancyPolicy.async_tick``: the overlap pipeline,
    unless ``REPRO_ASYNC_TICK=0`` — the CI lever that re-runs the suite on
    the blocking tick (scripts/ci.sh) without touching call sites that
    pass the knob explicitly."""
    import os
    return os.environ.get("REPRO_ASYNC_TICK", "1").lower() not in (
        "0", "false", "no")


# --------------------------------------------------------------------- policy
@dataclasses.dataclass(frozen=True)
class LeafPolicy:
    """Redundancy policy for one leaf group.

    ``max_vulnerable_steps`` / ``max_vulnerable_seconds`` make the paper's
    tunable knob explicit: an upper bound on how long blocks may stay
    vulnerable (dirty, redundancy stale) before an update is forced — even
    when the straggler governor has stretched the period, and regardless of
    where the step counter sits in the modulo schedule.  0 disables.
    """
    mode: str = "vilamb"                 # none | sync | vilamb
    period_steps: int = 8                # Algorithm-1 period T (vilamb)
    scrub_period_steps: int = 0          # 0 = no scheduled scrubbing
    max_vulnerable_steps: int = 0        # freshness deadline, in steps
    max_vulnerable_seconds: float = 0.0  # freshness deadline, wall clock
    # Work-queue capacity knob (fraction of each leaf's stripes); None
    # inherits the store-wide RedundancyPolicy.work_queue_frac, <= 0
    # disables compaction for this group.
    work_queue_frac: Optional[float] = None

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"unknown redundancy mode {self.mode!r} (want one of {MODES})")


@dataclasses.dataclass(frozen=True)
class RedundancyPolicy:
    """Declarative store-wide policy: per-leaf rules + shared geometry.

    ``rules`` are ``(fnmatch_pattern, LeafPolicy)`` pairs, first match wins;
    unmatched leaves get ``default``.  Leaves resolving to an equal
    LeafPolicy form one group backed by one RedundancyEngine.
    """
    default: LeafPolicy = LeafPolicy()
    rules: Tuple[Tuple[str, LeafPolicy], ...] = ()
    # Shared block geometry / kernel selection (RedundancyConfig fields).
    lanes_per_block: int = DEFAULT_LANES_PER_BLOCK
    stripe_data_blocks: int = DEFAULT_STRIPE_DATA_BLOCKS
    use_kernels: bool = False
    kernel_interpret: bool = False
    # Default XLA work-queue capacity (fraction of a leaf's stripe count);
    # per-group override via LeafPolicy.work_queue_frac.
    work_queue_frac: float = workqueue.DEFAULT_QUEUE_FRAC
    # Straggler governor: stretch periods under sustained slowdown, shrink
    # back once step times renormalize (the seed's watchdog never recovered).
    straggler_factor: float = 3.0
    straggler_window: int = 20
    straggler_recovery_steps: int = 10
    period_cap: int = 4096
    # Overlap pipeline (docs/perf.md): a due tick costs the foreground one
    # dispatch, never a device->host round trip.  ``async_tick=False`` or
    # ``pipeline_depth=0`` reverts to the blocking tick (exact host-side
    # queue_fits dispatch); depth counts in-flight updates per group — 1 is
    # the implemented maximum, deeper requests coalesce.  Mesh-sharded
    # groups overlap too: the per-shard fit flags come back inside the
    # batched update program's stacked fits vector and are AND-folded on
    # the host at resolution.  Defaults to the env lever
    # ``REPRO_ASYNC_TICK`` (scripts/ci.sh runs the suite both ways).
    async_tick: bool = dataclasses.field(default_factory=_async_tick_default)
    pipeline_depth: int = 1
    # Off-thread tick resolver (docs/api.md): with the overlap pipeline
    # on, the device->host fit fetch + AND-fold for each batched
    # Algorithm-1 dispatch runs on a dedicated daemon thread; the
    # foreground tick swaps epochs, dispatches the one batched program
    # (asynchronously — jax never blocks on execution there), and adopts
    # results the resolver has already folded to plain host bools.
    # settle/flush and the deadline/scrub/governor forced-resolve paths
    # join (wait for the resolver, which implies the fit signal landed).
    # ``flush`` and a remesh adoption shut the thread down cleanly; it is
    # re-created lazily on the next overlapped dispatch.  False resolves
    # inline on the tick thread via the non-blocking fetch started at
    # dispatch time (the PR3..PR8 behavior) — bitwise-identical either
    # way.
    dispatcher_thread: bool = True
    # AOT-compile every Algorithm-1 variant a group can dispatch at attach
    # time, so the first overlapped dispatch never hides a compile stall.
    precompile: bool = True
    # Scrub patroller + online shard rebuild (repro.scrub; docs/api.md).
    # ``patrol_bytes_per_tick`` > 0 enables a continuous low-priority
    # verify cursor over block space: each probe checksums at most that
    # many bytes *per shard* per tick (the per-device stall bound — shards
    # scan in parallel).  Detected corruption is repaired from parity at a
    # paced ``patrol_repair_per_tick`` blocks per tick.  A wholesale-corrupt
    # shard (>= ``shard_loss_threshold`` of a probe window's clean blocks
    # mismatching, at least ``shard_loss_min_blocks`` of them) triggers an
    # online rebuild from cross-shard parity, paced by
    # ``rebuild_bytes_per_tick`` (0 = 4x the patrol budget).  Priority:
    # foreground writes > due redundancy ticks > rebuild > patrol — with a
    # starvation floor: after ``patrol_max_starved_ticks`` consecutive
    # probe-less ticks (every tick busy) one probe dispatches anyway, so
    # wall-to-wall update traffic cannot silently stall detection forever
    # (0 disables the floor; ``TickReport.patrol_starved_ticks`` shows the
    # current streak).
    patrol_bytes_per_tick: int = 0
    patrol_repair_per_tick: int = 1
    patrol_max_starved_ticks: int = 32
    rebuild_bytes_per_tick: int = 0
    shard_loss_threshold: float = 0.5
    shard_loss_min_blocks: int = 4
    # Elastic remesh (repro.remesh; docs/api.md): ``store.remesh(new_mesh)``
    # re-stripes every protected leaf onto a grown/shrunk mesh over bounded
    # per-tick migration windows of ``remesh_bytes_per_tick`` bytes per leaf
    # (0 = 4x the patrol budget; if that is also 0 the whole leaf migrates
    # in one window).  Priority: foreground > due ticks > rebuild > remesh
    # > patrol.
    remesh_bytes_per_tick: int = 0
    # Degraded reads (``store.read_verified``): bounded retry/backoff when a
    # block cannot be immediately verified or reconstructed — a transiently
    # vulnerable stripe may settle within the retry budget.  The backoff is
    # exponential (base * 2**attempt) with a hard per-delay cap, a seeded
    # jitter fraction that only ever *shrinks* delays, and a cumulative
    # total budget — repro.health.backoff.backoff_schedule, the same
    # schedule the health governor's dispatch-retry rung uses.
    read_retry_attempts: int = 3
    read_retry_backoff_s: float = 0.0
    read_retry_backoff_cap_s: float = 0.0    # 0 = uncapped
    read_retry_total_s: float = 0.0          # 0 = unbudgeted
    read_retry_jitter_frac: float = 0.0
    # Freshness-SLO health governor (repro.health; docs/api.md): a
    # HealthPolicy (or True for defaults) arms per-group breakers
    # (HEALTHY -> DEGRADED -> CRITICAL, hysteresis on recovery) and the
    # escalation ladder — wedged-dispatch retry, margin-forced blocking
    # resolve, on_write backpressure, temporary sync escalation — that
    # *enforces* max_vulnerable_steps/_seconds instead of best-effort.
    # None (default) keeps the governor off: zero tick overhead.
    health: Optional[Any] = None

    def leaf_policy(self, name: str) -> LeafPolicy:
        for pattern, lp in self.rules:
            if fnmatch.fnmatchcase(name, pattern):
                return lp
        return self.default

    @classmethod
    def single(cls, mode: str, period_steps: int = 8,
               scrub_period_steps: int = 0, max_vulnerable_steps: int = 0,
               max_vulnerable_seconds: float = 0.0, **kw) -> "RedundancyPolicy":
        """The old global ``RedundancyConfig.mode`` as a one-group policy."""
        return cls(default=LeafPolicy(
            mode=mode, period_steps=period_steps,
            scrub_period_steps=scrub_period_steps,
            max_vulnerable_steps=max_vulnerable_steps,
            max_vulnerable_seconds=max_vulnerable_seconds), **kw)

    @classmethod
    def from_spec(cls, spec: str, default_mode: str = "vilamb",
                  period_steps: int = 8, scrub_period_steps: int = 0,
                  max_vulnerable_steps: int = 0, **kw) -> "RedundancyPolicy":
        """Parse ``"params/*=sync,m/*=vilamb:16,v/*=none"`` into rules.

        Each clause is ``pattern=mode[:period]``; omitted periods inherit
        ``period_steps``.  An empty spec yields a single-mode policy.
        """
        rules: List[Tuple[str, LeafPolicy]] = []
        for clause in filter(None, (c.strip() for c in spec.split(","))):
            pattern, _, rhs = clause.partition("=")
            if not rhs:
                raise ValueError(f"bad policy clause {clause!r} "
                                 "(want pattern=mode[:period])")
            mode, _, per = rhs.partition(":")
            rules.append((pattern.strip(), LeafPolicy(
                mode=mode.strip(), period_steps=int(per) if per else period_steps,
                scrub_period_steps=scrub_period_steps,
                max_vulnerable_steps=max_vulnerable_steps)))
        return cls(default=LeafPolicy(
            mode=default_mode, period_steps=period_steps,
            scrub_period_steps=scrub_period_steps,
            max_vulnerable_steps=max_vulnerable_steps), rules=tuple(rules), **kw)


# ------------------------------------------------------------------- governor
class StragglerGovernor:
    """Period back-off with recovery.

    Under sustained slowdown (a step > ``factor`` x the rolling median) the
    update period is stretched (doubled, capped) so redundancy never stalls
    the critical path.  After ``recovery_steps`` consecutive normal steps
    the stretch is halved back toward the configured period — the seed's
    watchdog doubled forever.
    """

    def __init__(self, factor: float = 3.0, window: int = 20,
                 recovery_steps: int = 10, max_scale: int = 512):
        self.factor = factor
        self.recovery_steps = recovery_steps
        self.max_scale = max_scale
        self.times: collections.deque = collections.deque(maxlen=window)
        self.scale = 1
        self._calm = 0

    def observe(self, dt: float) -> int:
        """Record one step time; returns the current period multiplier."""
        self.times.append(dt)
        if len(self.times) < self.times.maxlen:
            return self.scale
        med = statistics.median(self.times)
        if dt > self.factor * med:
            self.scale = min(self.scale * 2, self.max_scale)
            self._calm = 0
        elif self.scale > 1:
            self._calm += 1
            if self._calm >= self.recovery_steps:
                self.scale = max(1, self.scale // 2)
                self._calm = 0
        return self.scale


# ----------------------------------------------------------------------- tick
@dataclasses.dataclass
class TickReport:
    """What one ``tick`` did (host-side observability)."""
    step: int
    updated: Tuple[str, ...] = ()          # group labels that ran Algorithm 1
    deadline_fired: Tuple[str, ...] = ()   # subset forced by freshness deadline
    scrubbed: Tuple[str, ...] = ()
    mismatches: int = 0
    alarms: int = 0
    # Overlap pipeline observability: due ticks folded into a still-in-flight
    # update, and groups whose speculative queued dispatch overflowed (the
    # full-recompute fallback ran on resolution).
    coalesced: Tuple[str, ...] = ()
    overflowed: Tuple[str, ...] = ()
    # Scrub patroller / rebuild (repro.scrub).  ``repaired`` maps leaf name
    # -> replacement leaf array the caller MUST adopt (parity rebuilds and
    # shard-rebuild writes happen functionally; the store cannot mutate the
    # caller's arrays).  ``unrecoverable`` carries structured
    # repro.core.repairs.UnrecoverableBlock records; ``rebuild`` is the
    # active repro.scrub.RebuildStatus (None = no rebuild running).
    patrolled: Tuple[str, ...] = ()
    patrol_mismatches: int = 0
    # Consecutive ticks the patrol has gone without dispatching a probe
    # (busy foreground); resets on dispatch, forced past
    # ``RedundancyPolicy.patrol_max_starved_ticks``.
    patrol_starved_ticks: int = 0
    repaired: Dict[str, Any] = dataclasses.field(default_factory=dict)
    unrecoverable: Tuple[Any, ...] = ()
    rebuild: Optional[Any] = None
    # Active elastic-remesh migration (repro.remesh.RemeshStatus; None = no
    # remesh running).  On the adoption tick this is the final status with
    # ``done=True`` and the returned red is already the new geometry.
    remesh: Optional[Any] = None
    # Health governor observability (repro.health.HealthReport; None when
    # the governor is disabled): per-group breaker states, escalation-
    # ladder actions, vulnerability ages, and freshness violations.
    health: Optional[Any] = None


def _ready(x) -> bool:
    """Non-blocking readiness probe for a dispatched jax array."""
    try:
        return bool(x.is_ready())
    except AttributeError:      # non-jax stand-ins (tests) are always ready
        return True


def _fits_host(x) -> bool:
    """Host fold of a fetched fit signal: scalar (machine-local) or
    per-shard flag array alike."""
    return workqueue.fold_fits_host(x)


@dataclasses.dataclass
class _Pending:
    """One in-flight overlapped Algorithm-1 update (per group).

    ``red`` holds the program's output arrays (futures until the device
    finishes); ``fits`` is the batch's stacked device-computed queue-fit
    vector (row ``fits_index`` belongs to this group; per-shard columns
    under a mesh), with a host copy already in flight
    (``copy_to_host_async`` — or, when the backend lacks it, pre-fetched
    into ``fits_host`` at dispatch time so resolution never pays a
    synchronous device round trip).  Resolution adopts the outputs into
    the live view, feeds the fit row forward as the next speculation
    signal and, for a queued dispatch that overflowed, triggers the
    full-recompute fallback.

    With the off-thread dispatcher, ``launched`` is the batch's shared
    event, set once the resolver thread has fetched + folded the batch's
    fit signal into ``fits_host`` — ``None`` means the dispatch ran in
    inline mode (no thread; the fold happens lazily at resolution).  A
    resolver failure lands in ``error`` and re-raises at resolution.
    """
    red: Optional[Dict[str, Any]]
    fits: Any
    queued: bool
    step: int
    coalesced: int = 0
    launched: Optional[threading.Event] = None
    fits_index: int = 0
    fits_host: Optional[bool] = None
    # The batch's stacked ``[stripes, need]`` counts (``fits``' layout
    # plus a trailing pair; engine.redundancy_step_async) and this group's
    # host row, published with ``fits_host``.
    counts: Any = None
    counts_host: Optional[np.ndarray] = None
    error: Optional[BaseException] = None
    # Health-governor bookkeeping: wall-clock dispatch timestamp (wedged-
    # dispatch detection) and the group's freshness clocks as they stood
    # *before* this dispatch — abandoning a wedged update rolls back to
    # these, so the deadline keeps counting from the oldest unprotected
    # write.
    dispatched_at: float = dataclasses.field(default_factory=time.monotonic)
    prev_step: int = 0
    prev_time: float = 0.0


def _launched(p: "_Pending") -> bool:
    """Has the pending's resolver job finished (fit signal folded on the
    host)?  ``launched`` is None in inline mode — always done, the fold
    happens lazily at resolution instead."""
    ev = p.launched
    return ev is None or ev.is_set()


def _pending_ready(p: "_Pending") -> bool:
    """Non-blocking: resolver done AND the fit signal is resolvable
    without a device sync.  (The governor's wedged-dispatch rung probes
    this: a fetch stuck behind a wedged device counts as wedged too.)

    Thread mode never probes the device array: the resolver event being
    set means ``fits_host``/``error`` are already published, and the
    array's ``is_ready`` notification can go missing outright when a
    blocking transfer runs concurrently on another thread (observed on
    the CPU backend) — gating on it would stall resolution behind a
    phantom in-flight signal.  ``_ready`` still runs over the published
    value so the crash machine's forced-in-flight override keeps
    simulating a wedge."""
    ev = p.launched
    if ev is not None:
        return ev.is_set() and _ready(p.fits_host)
    return _ready(p.fits)


def _fits_host_pending(p: "_Pending") -> bool:
    """Host fold of a pending's fit row out of the batch's stacked fits
    vector: ``fits_host`` if the dispatch-time fallback fetch ran, else a
    host memory read of row ``fits_index`` (per-shard columns AND-fold on
    the host — no device program, no collective)."""
    if p.fits_host is not None:
        return bool(p.fits_host)
    arr = np.asarray(p.fits)
    return workqueue.fold_fits_host(arr[p.fits_index] if arr.ndim else arr)


def _counts_host(counts) -> Tuple[int, int]:
    """``(stripes, need)`` of one group's fetched ``[stripes, need]`` row
    or rows: the dirty stripes summed over shards, the need of the
    largest shard (a rung must hold every shard's queue)."""
    c = np.asarray(counts).reshape(-1, 2)
    return int(c[:, 0].sum()), int(c[:, 1].max())


def _counts_host_pending(p: "_Pending") -> Tuple[int, int]:
    """:func:`_counts_host` of this group's row of the batch's stacked
    counts: ``counts_host`` once published, else a host memory read of
    the landed copy."""
    if p.counts_host is not None:
        return _counts_host(p.counts_host)
    return _counts_host(np.asarray(p.counts)[p.fits_index])


def _queued_variant(rung: int) -> str:
    """The batched program's variant name of a queued pass at ``rung``."""
    return f"async_queued@{rung}"


def _publish(pendings, fits_host, counts_host) -> None:
    """Fold a batch's fetched fits and counts into its pendings."""
    for i, p in enumerate(pendings):
        f = fits_host[i] if fits_host.ndim else fits_host
        p.fits_host = workqueue.fold_fits_host(f)
        p.counts_host = counts_host[i]


class _Dispatcher:
    """Dedicated resolver thread for overlapped Algorithm-1 dispatches.

    A plain FIFO worker: jobs (device->host fit fetch + fold closures
    over already-dispatched batches) run in submission order, so
    per-batch resolution order is preserved and the foreground tick
    never blocks on device execution or a host round trip.  ``stop``
    drains the queue (sentinel goes in behind any queued jobs) and
    joins — a clean shutdown can never drop a fetch.
    """

    def __init__(self):
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self.thread = threading.Thread(
            target=self._run, name="repro-dispatch", daemon=True)
        self.thread.start()

    def _run(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            job()

    def submit(self, job: Callable[[], None]) -> None:
        self._q.put(job)

    def stop(self) -> None:
        if self.thread.is_alive():
            self._q.put(None)
            self.thread.join()


@dataclasses.dataclass
class _Group:
    label: str
    policy: LeafPolicy
    names: Tuple[str, ...]
    engine: Optional[RedundancyEngine]     # None for mode == "none"
    last_update_step: int = 0
    last_update_time: float = dataclasses.field(default_factory=time.monotonic)
    # Overlap-pipeline state: at most one in-flight update, plus the
    # speculation signal (did the last consumed snapshot fit the queues?).
    # Pessimistic start: the full program is always correct, and the first
    # due tick after attach often carries a large dirty set; the first
    # resolved fit signal (or a flush's exact check) flips it.
    pending: Optional[_Pending] = None
    predicted_fits: bool = False
    # The queue capacity (rung of ``engine.queue_ladder``) of the in-flight
    # or last overlapped pass, 0 = the full program; whether that pass
    # re-dispatches an overflowed one; and the need (``workqueue.pick_rung``)
    # of the last resolved pass that no due tick coalesced into (None =
    # unknown: the next pass takes the top).
    rung: int = 0
    redispatch: bool = False
    last_need: Optional[int] = None


# ---------------------------------------------------------------------- store
class ProtectedStore:
    """Pytree-native facade owning the full redundancy lifecycle.

    One store wraps one protected state pytree (train params+opt, serve KV
    caches, a raw heap) and hides mode branches, scheduling, double-check
    scrubbing, straggler back-off, and flush behind three calls.
    """

    def __init__(self, policy: Optional[RedundancyPolicy] = None,
                 mesh: Any = None):
        self.policy = policy or RedundancyPolicy()
        self.mesh = mesh
        self.groups: Dict[str, _Group] = {}
        self.corruption_alarms = 0
        self._none_metas: Dict[str, BlockMeta] = {}
        self._governor = StragglerGovernor(
            factor=self.policy.straggler_factor,
            window=self.policy.straggler_window,
            recovery_steps=self.policy.straggler_recovery_steps)
        self._jit_update: Dict[Tuple[Any, Any], Any] = {}
        self._jit_scrub: Dict[str, Any] = {}
        self._jit_misc: Dict[Tuple[Any, str], Any] = {}
        # Off-thread dispatcher (RedundancyPolicy.dispatcher_thread):
        # created lazily at the first overlapped dispatch, shut down by
        # flush and at a remesh handover.
        self._dispatcher: Optional[_Dispatcher] = None
        # Scrub patroller (repro.scrub) — built by attach() when the policy
        # enables it (patrol_bytes_per_tick > 0) and a vilamb group exists.
        self.patroller: Optional[Any] = None
        # Freshness-SLO health governor (repro.health) — built by attach()
        # when policy.health is set; None = off, zero tick overhead.
        self._health: Optional[Any] = None
        # Elastic remesh (repro.remesh): a queued geometry-change request,
        # the active migrator, and the mesh-geometry epoch counter (bumped
        # at every remesh adoption; cross-shard parity images carry the
        # epoch they were folded under).
        self._remesh_request: Optional[Tuple[Any, Dict[str, Any]]] = None
        self._remesh: Optional[Any] = None
        self.geometry_version = 0
        # Leaves pasted/moved by a settle/flush-time background drain
        # (satellite of the rebuild lifecycle): callers adopt via
        # ``take_repaired``.
        self._drained: Dict[str, Any] = {}
        # Lifecycle phase hooks (repro.faults): host-level observation
        # points for crash-consistency replay.  Empty list = zero overhead
        # on every hot path (a single truthiness check).
        self._phase_hooks: List[Callable[[str, Dict[str, Any]], None]] = []
        # Always-on host counters (repro.core.trace): Algorithm-1 passes,
        # the dirty stripes they covered (counted on the device, added at
        # resolution) and the queue rows the queued ones paid for, patrol
        # probes, and ``wait.<site>.{n,s,max_ms}`` for every place the tick
        # thread blocks.
        self.counters: Dict[str, float] = dict.fromkeys((
            "update.passes_queued", "update.passes_full", "update.overflowed",
            "update.stripes", "update.queue_rows", "update.alg1_bytes",
            "patrol.probes_ready", "patrol.probes_forced",
            "patrol.blocks_scanned"), 0)

    # -------------------------------------------------------------- phase hooks
    def add_phase_hook(self, fn: Callable[[str, Dict[str, Any]], None]) -> None:
        """Register ``fn(phase, info)`` to fire at lifecycle phases.

        Phases (see ``repro.faults.crashpoints.CRASH_PHASES``): ``on_write``,
        ``dispatcher_enqueue`` (the tick is about to dispatch the batched
        multi-group program and hand its fit fetch to the resolver
        thread), ``dispatch`` (per-group, right after the overlapped
        batch was dispatched and the epoch-swapped live view adopted),
        ``coalesce`` (due tick folded into the in-flight update),
        ``dispatcher_join`` (about to block on the resolver thread's
        fetched fit signal), ``adopt`` / ``adopt_forced`` (lazy vs
        deadline/scrub-forced resolution), ``blocking_update``, ``scrub``,
        ``tick``, ``flush``, ``settle``.  ``info['red']`` is the
        live redundancy view at that instant — the state a crash would
        persist.  Hooks are host-level: they never fire while tracing, so
        an ``on_write`` embedded in a jitted step is silently skipped.
        Exceptions raised by a hook propagate (the crash machine's process-
        death emulation relies on this).
        """
        self._phase_hooks.append(fn)

    def remove_phase_hook(self, fn) -> None:
        self._phase_hooks.remove(fn)

    def _phase(self, name: str, **info) -> None:
        if not self._phase_hooks:
            return
        red = info.get("red")
        if red is not None:
            for leaf in jax.tree_util.tree_leaves(red):
                if isinstance(leaf, jax.core.Tracer):
                    return                  # under trace: host hooks skip
        for fn in list(self._phase_hooks):
            fn(name, info)

    # ------------------------------------------------------------ construction
    def attach(self, tree: Any, specs: Optional[Mapping[str, Any]] = None
               ) -> "ProtectedStore":
        """Declare the protected pytree (arrays or ShapeDtypeStructs).

        Nested dicts are flattened to ``a/b/c`` paths — the namespace the
        policy rules match against.  ``specs`` optionally maps those paths
        to PartitionSpecs for sharded (machine-local) redundancy.  Returns
        ``self`` for chaining: ``red = store.attach(state).init(state)``.
        """
        flat = flatten_dict(tree)
        structs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in flat.items()}
        specs = dict(specs or {})
        # Remembered for elastic remesh: re-striping onto a new mesh reuses
        # the declared global structs and PartitionSpecs.
        self._structs = structs
        self._specs = dict(specs)
        by_policy: Dict[LeafPolicy, List[str]] = {}
        for name in structs:
            by_policy.setdefault(self.policy.leaf_policy(name), []).append(name)
        self.groups = {}
        self._none_metas = {}
        for i, (lp, names) in enumerate(by_policy.items()):
            label = f"g{i}:{lp.mode}"
            engine = None
            if lp.mode == "none":
                for n in names:
                    lshape = _local_shape(structs[n].shape, specs.get(n), self.mesh)
                    self._none_metas[n] = make_meta(
                        jax.ShapeDtypeStruct(lshape, structs[n].dtype),
                        lanes_per_block=self.policy.lanes_per_block,
                        stripe_data_blocks=self.policy.stripe_data_blocks)
            else:
                cfg = RedundancyConfig(
                    mode=lp.mode, period_steps=lp.period_steps,
                    scrub_period_steps=lp.scrub_period_steps,
                    lanes_per_block=self.policy.lanes_per_block,
                    stripe_data_blocks=self.policy.stripe_data_blocks,
                    use_kernels=self.policy.use_kernels,
                    kernel_interpret=self.policy.kernel_interpret,
                    work_queue_frac=(
                        lp.work_queue_frac if lp.work_queue_frac is not None
                        else self.policy.work_queue_frac))
                engine = RedundancyEngine(
                    {n: structs[n] for n in names}, cfg, mesh=self.mesh,
                    specs={n: specs[n] for n in names if n in specs})
            self.groups[label] = _Group(label, lp, tuple(names), engine)
        self._jit_update = {}
        self._jit_scrub = {}
        self._jit_misc = {}
        self._stop_dispatcher()
        if self.policy.precompile:
            self.warmup()
        self.patroller = None
        if self.policy.patrol_bytes_per_tick > 0 and any(
                g.policy.mode == "vilamb" for g in self._protected()):
            # Runtime import: repro.scrub builds on repro.core submodules.
            from repro.scrub import ScrubPatroller
            self.patroller = ScrubPatroller(self)
        self._health = None
        if self.policy.health:
            # Runtime import: repro.health builds on repro.core submodules.
            from repro.health import HealthGovernor, HealthPolicy
            hp = self.policy.health
            self._health = HealthGovernor(
                self, hp if isinstance(hp, HealthPolicy) else None)
        return self

    @classmethod
    def from_engine(cls, engine: RedundancyEngine, mode: str = "vilamb",
                    period_steps: Optional[int] = None,
                    scrub_period_steps: int = 0) -> "ProtectedStore":
        """Wrap a pre-built single-mode engine (deprecation-shim path).

        The engine keeps its geometry (lanes/stripes/kernels); the store adds
        the lifecycle around it.
        """
        cfg = engine.config
        pol = RedundancyPolicy.single(
            mode, period_steps=period_steps if period_steps is not None
            else cfg.period_steps,
            scrub_period_steps=scrub_period_steps,
            lanes_per_block=cfg.lanes_per_block,
            stripe_data_blocks=cfg.stripe_data_blocks,
            use_kernels=cfg.use_kernels, kernel_interpret=cfg.kernel_interpret,
            work_queue_frac=cfg.work_queue_frac)
        store = cls(pol, mesh=engine.mesh)
        if mode == "none":
            store.groups = {}
            store._none_metas = dict(engine.metas)
        else:
            store.groups = {"g0:" + mode: _Group(
                "g0:" + mode, pol.default, tuple(engine.metas), engine)}
        return store

    # ---------------------------------------------------------------- structure
    @property
    def metas(self) -> Dict[str, BlockMeta]:
        out = dict(self._none_metas)
        for g in self.groups.values():
            if g.engine is not None:
                out.update(g.engine.metas)
        return out

    @property
    def protected_metas(self) -> Dict[str, BlockMeta]:
        """Metas of leaves that actually carry redundancy arrays."""
        out: Dict[str, BlockMeta] = {}
        for g in self.groups.values():
            if g.engine is not None:
                out.update(g.engine.metas)
        return out

    def leaf_policy(self, name: str) -> LeafPolicy:
        for g in self.groups.values():
            if name in g.names:
                return g.policy
        raise KeyError(name)

    def engine_for(self, name: str) -> Optional[RedundancyEngine]:
        for g in self.groups.values():
            if name in g.names:
                return g.engine
        return None

    def shard_factor(self, name: str) -> int:
        """Shards a leaf's redundancy arrays concatenate (1 = machine-local).

        Global block space for sharded leaves: shard ``s``'s local block
        ``b`` is global block ``s * meta.n_blocks + b`` — the indexing
        scrub masks, ``vulnerable_masks``, fault injection, and
        ``recover_block`` share.
        """
        eng = self.engine_for(name)
        return 1 if eng is None else eng.shard_factor(name)

    def _protected(self) -> List[_Group]:
        return [g for g in self.groups.values() if g.engine is not None]

    @property
    def has_sync(self) -> bool:
        return any(g.policy.mode == "sync" for g in self._protected())

    @property
    def has_periodic(self) -> bool:
        return any(g.policy.mode == "vilamb" for g in self._protected())

    @property
    def protects(self) -> bool:
        return bool(self._protected())

    def red_structs(self, global_: bool = True) -> RedundancyState:
        out: RedundancyState = {}
        for g in self._protected():
            out.update(g.engine.red_structs(global_))
        return out

    def red_shardings(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for g in self._protected():
            out.update(g.engine.red_shardings())
        return out

    def expand_events(self, sparse_events: Mapping[str, Any]) -> Dict[str, Any]:
        """Suffix-keyed sparse events -> full-path events, defaulting ALL.

        ``{"moe/wi": mask}`` fans out to every protected leaf whose path
        suffix (after the first ``/``) matches; unmatched leaves are marked
        fully dirty — the conservative choice for dense updates.
        """
        events: Dict[str, Any] = {}
        for g in self._protected():
            for name in g.names:
                _, _, suffix = name.partition("/")
                ev = sparse_events.get(suffix)
                events[name] = ev if ev is not None else ALL
        return events

    # ----------------------------------------------------------------- lifecycle
    def init(self, tree: Any) -> RedundancyState:
        """Full redundancy computation (paper: file-creation time)."""
        leaves = flatten_dict(tree)
        red: RedundancyState = {}
        for g in self._protected():
            red.update(g.engine.init({n: leaves[n] for n in g.names}))
        return red

    def on_write(self, red: RedundancyState,
                 events: Optional[Mapping[str, Any]] = None,
                 old: Optional[Mapping[str, jax.Array]] = None,
                 new: Optional[Mapping[str, jax.Array]] = None,
                 row_diffs: Optional[Mapping[str, Tuple]] = None
                 ) -> RedundancyState:
        """Record writes; traceable — call inside the jitted mutation step.

        Per leaf group: ``vilamb`` ORs ``events`` (dirty marks) into the
        bitvectors; ``sync`` applies the Pangolin inline diff from
        ``old``/``new`` (or the sparse ``row_diffs`` fast path
        ``{name: (rows, old_rows, new_rows)}`` when rows map 1:1 to blocks);
        ``none`` passes through.  Leaves absent from ``events`` are left
        unmarked — use :meth:`expand_events` for dense default-ALL marking.
        """
        if self._health is not None:
            # Rung-3 admission control: while some breaker is CRITICAL the
            # governor throttles (spin) or rejects (BackpressureError)
            # foreground writes so the device can drain.  No-op under a jax
            # trace and while every breaker is below CRITICAL.
            self._health.admit(red)
        events = dict(events or {})
        row_diffs = dict(row_diffs or {})
        out = dict(red)
        for g in self._protected():
            red_sub = {n: out[n] for n in g.names}
            if g.policy.mode == "vilamb":
                evs = {n: events[n] for n in g.names if n in events}
                if evs:
                    out.update(g.engine.mark_dirty(red_sub, evs))
            elif g.policy.mode == "sync":
                if all(n in row_diffs for n in g.names):
                    for n in g.names:
                        rows, o, v = row_diffs[n]
                        out[n] = g.engine.sync_update_rows(n, out[n], rows, o, v)
                elif old is not None and new is not None:
                    out.update(g.engine.sync_update(
                        {n: old[n] for n in g.names},
                        {n: new[n] for n in g.names}, red_sub))
                else:
                    raise ValueError(
                        f"sync leaves {g.names} need old=/new= (or row_diffs=) "
                        "in on_write")
        if self._phase_hooks:
            self._phase("on_write", red=dict(out))
        return out

    # --------------------------------------------------- dispatch machinery
    def _async_group(self, g: _Group) -> bool:
        """Does this group take the overlap-pipelined tick path?

        Mesh-sharded groups qualify too: their per-shard fit flags ride
        the batched program's stacked fits vector, whose host copy starts
        at launch time — the AND-fold over shards is a host memory read
        at resolution, exactly like the machine-local scalar.
        """
        return (g.engine is not None and g.policy.mode == "vilamb"
                and self.policy.async_tick and self.policy.pipeline_depth > 0)

    def _build_update(self, label: str, variant: str):
        """Un-lowered jitted Algorithm-1 program for one group.

        Variants: ``full`` / ``queued`` — the blocking programs (input red
        donated in place; used by ``flush`` and the blocking tick);
        ``async_full`` — the overlap program ``(leaves, red) -> (red, fits,
        counts)`` that settle's fallback runs (the tick's overlap passes,
        queued ones included, run in :meth:`_build_update_many`'s batched
        program).  The overlap programs donate
        **nothing**: on this backend a donated dispatch blocks the host
        until its donated inputs are defined, so in-place updates would
        re-serialize the very pipeline the overlap exists to free.  The
        old epoch's arrays instead stay alive as the double buffer (the
        foreground keeps dispatching against them) and the program's
        outputs are adopted at resolution.
        """
        eng = self.groups[label].engine
        if variant == "full":
            return jax.jit(eng.redundancy_step, donate_argnums=(1,))
        if variant == "queued":
            return jax.jit(eng.redundancy_step_queued, donate_argnums=(1,))
        assert variant == "async_full", variant
        return jax.jit(eng.redundancy_step_async)

    def _update_fn(self, label: str, variant: str):
        key = (label, variant)
        fn = self._jit_update.get(key)
        if fn is None:
            fn = self._jit_update[key] = self._build_update(label, variant)
        return fn

    def _build_update_many(self, labels: Tuple[str, ...],
                           variants: Tuple[str, ...]):
        """One jitted program running every due group's overlap Algorithm-1
        pass and stacking the fit signals into a single vector.  A group's
        variant is ``async_full`` or ``async_queued@<K>``: the queued pass
        at rung ``K`` of its engine's ``queue_ladder``
        (:func:`_queued_variant`).

        This is the tentpole of the sharded-overlap fix: a due tick used to
        launch one update program *plus* one AND-fold program per group —
        each launch serializing a full per-device dispatch on the host.
        Batched, the tick costs one launch total, and the fits come back as
        one stacked ``(n_groups,)`` vector (``(n_groups, n_devices)`` under
        a mesh — pinned to per-device columns so the program still lowers
        collective-free; the AND-fold over shards happens on the host at
        resolution, where the row is already fetched memory).  The groups'
        int32 ``[stripes, need]`` counts come back beside it, in the same
        layout with a trailing pair.
        """
        engines = [self.groups[l].engine for l in labels]
        rungs = []
        for v in variants:
            kind, _, k = v.partition("@")
            assert (kind, bool(k)) in (("async_full", False),
                                       ("async_queued", True)), v
            rungs.append(int(k) if k else 0)
        mesh = engines[0].mesh

        def many(subs, reds):
            outs, fits, counts = [], [], []
            for eng, k, sub, rd in zip(engines, rungs, subs, reds):
                o, f, n = eng.redundancy_step_async(
                    sub, rd, queued=k > 0, rung=k or None)
                outs.append(o)
                fits.append(f)
                counts.append(n)
            stacked, counts = jnp.stack(fits), jnp.stack(counts)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                # Per-shard flag columns stay device-local: each device
                # holds its own column of every group's row — stacking is
                # a local concat, never a collective.
                cols = NamedSharding(mesh, P(None, tuple(mesh.axis_names)))
                stacked = jax.lax.with_sharding_constraint(stacked, cols)
                counts = jax.lax.with_sharding_constraint(counts, cols)
            return tuple(outs), stacked, counts

        return jax.jit(many)

    def _update_many_fn(self, labels: Tuple[str, ...],
                        variants: Tuple[str, ...]):
        key = (tuple(labels), tuple(variants))
        fn = self._jit_update.get(key)
        if fn is None:
            fn = self._jit_update[key] = self._build_update_many(
                key[0], key[1])
        return fn

    def warmup(self) -> "ProtectedStore":
        """AOT-compile every Algorithm-1 variant each group can dispatch.

        Runs at ``attach`` time (``RedundancyPolicy.precompile``) so the
        first due tick never hides a compile stall: both the queued and the
        full program are ready before the first overlapped dispatch.  This
        was the `fig1_insert` threads8 collapse — warmup traffic fit the
        work queue, steady state overflowed, and the full variant's ~200 ms
        compile landed inside the measured loop.

        Mesh-sharded groups are warmed too, lowered against the group's
        declared shardings (leaves per their PartitionSpecs, redundancy per
        ``red_shardings``); callers of a precompiled mesh store must hand
        ``tick``/``flush`` arrays sharded that way — pass
        ``precompile=False`` to keep fully flexible jit dispatch instead.
        Returns ``self`` for chaining.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        for g in self._protected():
            if g.policy.mode != "vilamb":
                continue
            eng = g.engine
            if eng.mesh is None:
                leaf_structs = {
                    n: jax.ShapeDtypeStruct(eng.metas[n].shape,
                                            jnp.dtype(eng.metas[n].dtype))
                    for n in g.names}
                red_structs = {n: leaf_red_struct(eng.metas[n])
                               for n in g.names}
            else:
                leaf_structs = {
                    n: jax.ShapeDtypeStruct(
                        s.shape, s.dtype,
                        sharding=NamedSharding(eng.mesh,
                                               eng.specs.get(n, P())))
                    for n, s in eng.global_leaf_structs.items()}
                red_structs = jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sh),
                    eng.red_structs(global_=True), eng.red_shardings(),
                    is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            # Async groups also warm the blocking pair: flush (the
            # latency-critical preemption path) still dispatches it; and
            # the single-group full overlap program, settle's fallback.
            # Their queued overlap passes run in the batched program.
            variants = (("async_full", "full", "queued")
                        if self._async_group(g) else ("full", "queued"))
            for variant in variants:
                if "queued" in variant and not eng.has_queue:
                    continue
                key = (g.label, variant)
                if key in self._jit_update:
                    continue
                self._jit_update[key] = self._build_update(
                    g.label, variant).lower(leaf_structs, red_structs).compile()
            if self._async_group(g):
                # The tick launches through the batched multi-group program
                # (a singleton batch when one group is due); AOT-lower the
                # full variant and every rung of the queued one too, so no
                # overlapped dispatch hides a compile stall.
                for variant in ("async_full", *map(_queued_variant,
                                                   eng.queue_ladder)):
                    mkey = ((g.label,), (variant,))
                    if mkey in self._jit_update:
                        continue
                    self._jit_update[mkey] = self._build_update_many(
                        mkey[0], mkey[1]).lower(
                        (leaf_structs,), (red_structs,)).compile()
                # Warm the epoch-swap helper too (it compiles on first use
                # otherwise — a ~50 ms stall inside the first overlapped
                # dispatch).  A real call on the tiny bitvectors both
                # compiles it and keeps the fast C++ dispatch path.
                if eng.mesh is None:
                    words = {n: bits.zeros(eng.metas[n].n_blocks)
                             for n in g.names}
                else:
                    shardings = eng.red_shardings()
                    words = {
                        n: jax.device_put(
                            jnp.zeros((eng.metas[n].n_dirty_words
                                       * eng.shard_factor(n),), jnp.uint32),
                            shardings[n].dirty)
                        for n in g.names}
                jax.block_until_ready(self._swap_fn(g.label)(words, words))
        return self

    def _dispatch_blocking(self, g: _Group, sub, red_sub):
        """Blocking dispatch (flush / legacy ``async_tick=False`` tick):
        queued program when the live dirty stripes fit the work queues — an
        exact, host-side ``queue_fits`` round trip (per-shard counts under
        a mesh) — full recompute otherwise; bitwise-identical either way.
        The exact fit answer doubles as a free speculation seed for later
        overlapped dispatches, and its dirty-stripe count (same transfer)
        feeds the pass counters.  A group without queues takes the round
        trip too, for that count alone."""
        with trace.waited(self.counters, "queue_fits"):
            fits, stripes = g.engine.queue_check(red_sub)
        queued = g.engine.has_queue and fits
        g.predicted_fits = queued or not g.engine.has_queue
        g.last_need = None
        self._count_pass(g, queued, stripes)
        return self._update_fn(g.label, "queued" if queued else "full")(
            sub, red_sub)

    def _count_pass(self, g: _Group, queued: bool, stripes: Optional[int],
                    rung: Optional[int] = None) -> None:
        """Count one Algorithm-1 pass; ``stripes`` None = overflowed (its
        work is redone by the fallback, which counts itself).  A queued
        pass adds the queue rows its ``rung`` (None = top) paid for,
        overflowed or not."""
        c = self.counters
        trace.add(c, "update.passes_queued" if queued
                  else "update.passes_full")
        if queued:
            trace.add(c, "update.queue_rows", g.engine.queue_rows(rung))
        if stripes is None:
            trace.add(c, "update.overflowed")
            return
        trace.add(c, "update.stripes", stripes)
        trace.add(c, "update.alg1_bytes",
                  stripes * g.engine.alg1_stripe_bytes)

    def _next_rung(self, g: _Group, overflowed: bool, retry: bool) -> int:
        """Queue capacity of ``g``'s next overlapped pass; 0 = the full
        program.  The smallest rung of ``engine.queue_ladder`` with
        ``workqueue.RUNG_MARGIN`` of room over the last resolved need
        (host bookkeeping: no device round trip), the top where no need
        is known, full where the need exceeds the top or the last
        snapshot did not fit the top queues.  After an overflow the need
        is the overflowed snapshot's own; if the re-dispatch overflows
        too, the full program covers it, so one burst costs at most two
        passes more than its cover."""
        ladder = g.engine.queue_ladder
        if not ladder or not g.predicted_fits:
            return 0
        if overflowed and g.redispatch:
            return 0
        if g.last_need is None or retry:
            return 0 if overflowed else ladder[-1]
        return workqueue.pick_rung(ladder, g.last_need)

    def _swap_fn(self, label: str):
        """One-dispatch epoch swap for the live view: per leaf, the epoch-A
        snapshot (``dirty | shadow``, becomes the live ``shadow``) and a
        fresh zero epoch-B bitmap (becomes the live ``dirty``).

        Not donated: its inputs are usually still being produced by the
        step just dispatched, and a donated dispatch would block on them.

        Under a mesh the outputs are pinned to the bitvectors' shardings:
        the fresh epoch-B zeros are a constant, so GSPMD would otherwise
        freely re-shard them (replicated) and the precompiled update
        program would reject the mismatched live view.
        """
        key = (label, "swap")
        fn = self._jit_misc.get(key)
        if fn is None:
            g = self.groups[label]
            names = g.names

            def swap(dirty, shadow):
                snaps = {n: jnp.bitwise_or(dirty[n], shadow[n]) for n in names}
                fresh = {n: jnp.zeros_like(dirty[n]) for n in names}
                return snaps, fresh

            kw = {}
            if g.engine is not None and g.engine.mesh is not None:
                sh = {n: g.engine.red_shardings()[n].dirty for n in names}
                kw["out_shardings"] = (sh, sh)
            fn = self._jit_misc[key] = jax.jit(swap, **kw)
        return fn

    def _swap_many_fn(self, labels: Tuple[str, ...]):
        """Epoch swap for a whole dispatch batch in one program.

        A singleton batch delegates to the per-group :meth:`_swap_fn` (so
        its warmed ``(label, "swap")`` cache entry keeps serving the
        common case); a multi-group batch compiles one fused program —
        returns a tuple over groups of ``(snaps, fresh)``.
        """
        if len(labels) == 1:
            base = self._swap_fn(labels[0])
            return lambda dirties, shadows: (base(dirties[0], shadows[0]),)
        key = (tuple(labels), "swap_many")
        fn = self._jit_misc.get(key)
        if fn is None:
            groups = [self.groups[l] for l in labels]

            def swap_many(dirties, shadows):
                return tuple(
                    ({n: jnp.bitwise_or(d[n], s[n]) for n in g.names},
                     {n: jnp.zeros_like(d[n]) for n in g.names})
                    for g, d, s in zip(groups, dirties, shadows))

            kw = {}
            if groups[0].engine is not None and groups[0].engine.mesh is not None:
                shs = tuple(
                    ({n: g.engine.red_shardings()[n].dirty for n in g.names},
                     {n: g.engine.red_shardings()[n].dirty for n in g.names})
                    for g in groups)
                kw["out_shardings"] = shs
            fn = self._jit_misc[key] = jax.jit(swap_many, **kw)
        return fn

    def _submit(self, job: Callable[[], None]) -> None:
        """Run ``job`` on the dispatcher thread (lazily created), or inline
        when ``RedundancyPolicy.dispatcher_thread`` is off."""
        if not self.policy.dispatcher_thread:
            job()
            return
        d = self._dispatcher
        if d is None or not d.thread.is_alive():
            d = self._dispatcher = _Dispatcher()
        d.submit(job)

    def _stop_dispatcher(self) -> None:
        """Drain + join the dispatcher thread (flush / remesh handover).
        Queued fetches complete first, so no pending is ever dropped."""
        d, self._dispatcher = self._dispatcher, None
        if d is not None:
            d.stop()

    def sync_inflight(self) -> "ProtectedStore":
        """Wait until every pending's resolver job has run and its fit
        signal is device-complete (test/replay determinism hook — the
        crash machine and the sharded drivers use it to force 'adopt,
        never coalesce' schedules independent of machine load)."""
        for g in self._protected():
            p = g.pending
            if p is None:
                continue
            if p.launched is not None:
                p.launched.wait()
            if p.error is None and p.fits is not None:
                jax.block_until_ready(p.fits)
        return self

    def _dispatch_async_many(self,
                             items: List[Tuple[_Group, bool, int, float]],
                             get_leaves, out: Dict[str, Any], step: int
                             ) -> Dict[str, LeafRedundancy]:
        """Overlapped batched dispatch with an off-thread resolver.

        Every due group's speculative queued-or-full program runs as ONE
        jitted multi-group launch with a single stacked fits vector —
        collapsing the per-group dispatch overhead (the dominant
        per-due-tick host cost on a sharded store) into one program
        launch.  The device->host fit fetch + AND-fold then runs on the
        dispatcher thread, so the tick never touches the device again
        for this batch.  Nothing is donated and nothing waits on
        execution: the returned **live view**
        carries the old epoch's checksums/parity (kept alive as the double
        buffer), a fresh zero epoch-B dirty bitmap for the foreground's
        next ``on_write``, and ``shadow`` = snapshot A — so scrub,
        recovery, accounting, and a crash-persisted checkpoint all keep
        treating the in-flight blocks as vulnerable until resolution
        adopts the result.  The host copy of the fits vector is owned by
        the resolver job (inline mode: ``copy_to_host_async`` at dispatch
        time, with an eager fallback fetch when the backend lacks it), so
        ``_resolve`` never pays a synchronous device round trip.
        """
        labels = tuple(g.label for g, *_ in items)
        variants = tuple(_queued_variant(g.rung) if q else "async_full"
                         for g, q, *_ in items)
        lv = get_leaves()
        subs = tuple({n: lv[n] for n in g.names} for g, *_ in items)
        red_subs = tuple({n: out[n] for n in g.names} for g, *_ in items)
        swaps = self._swap_many_fn(labels)(
            tuple({n: rs[n].dirty for n in rs} for rs in red_subs),
            tuple({n: rs[n].shadow for n in rs} for rs in red_subs))
        # The batched program is dispatched HERE, on the tick thread: jax's
        # dispatch is asynchronous (nothing below blocks on execution), and
        # dispatching before returning is what makes a caller's later
        # donation of the captured leaf/red buffers safe — the runtime
        # already holds usage references.  Handing the *dispatch* to the
        # thread was measured and rejected: a donating caller (train step,
        # decode step) deletes the captured buffers before the thread gets
        # to shard them.
        outs, fits, counts = self._update_many_fn(labels, variants)(
            subs, red_subs)
        ev = threading.Event() if self.policy.dispatcher_thread else None
        pendings = []
        for i, (g, queued, prev_step, prev_time) in enumerate(items):
            # prev_* carry the freshness clocks as they stood when the
            # tick collected this group — before the tick bumped them:
            # the governor's wedged-dispatch abandon rolls back to these.
            # dispatched_at stamps the handoff — a fetch stuck behind a
            # wedged device counts as wedged from the moment the
            # foreground handed it off.
            p = _Pending(red=outs[i], fits=fits, queued=queued, step=step,
                         launched=ev, fits_index=i, counts=counts,
                         prev_step=prev_step, prev_time=prev_time)
            g.pending = p
            pendings.append(p)

        if ev is not None:
            # Off-thread resolver: the dedicated thread rides out device
            # execution (the fetch blocks *it*, not the tick) and
            # publishes the folded per-group fit bits and stripe counts;
            # ``_resolve`` then only reads plain Python values.
            def resolve_job(fits=fits, counts=counts, pendings=pendings,
                            ev=ev):
                try:
                    with trace.span("resolver.fetch"):
                        host = jax.device_get((fits, counts))
                    _publish(pendings, *map(np.asarray, host))
                except BaseException as e:   # surfaces at resolution
                    for p in pendings:
                        p.error = e
                finally:
                    ev.set()

            self._submit(resolve_job)
        elif hasattr(fits, "copy_to_host_async"):
            # Inline mode (PR3..PR8 behavior): start the non-blocking
            # device->host copies now; resolution folds the landed rows.
            fits.copy_to_host_async()
            counts.copy_to_host_async()
        else:
            # Backend without a non-blocking device->host copy: fetch
            # HERE, at dispatch time — the resolve-side read must stay a
            # host memory read, never a synchronous round trip.
            _publish(pendings, np.asarray(fits), np.asarray(counts))
        view: Dict[str, LeafRedundancy] = {}
        for (g, *_), (snaps, fresh), rs in zip(items, swaps, red_subs):
            view.update({n: dataclasses.replace(
                            rs[n], dirty=fresh[n], shadow=snaps[n])
                         for n in g.names})
        return view

    def _resolve(self, g: _Group, red_sub, *, wait: bool):
        """Adopt an in-flight update into the live view, if resolvable.

        Returns ``(red_sub', overflowed, deferred)``; ``(None, False, 0)``
        when the update is still in flight (resolver thread still waiting
        on the device, or the device still computing) and ``wait`` is
        False.  Reading the fit row here is a host memory read, not a
        device sync: the resolver thread folded the batch's stacked fits
        vector to plain bools (inline mode: the non-blocking host copy
        started at dispatch time), one tick (or more) ago — ``wait``
        blocks (joins the resolver, which implies the signal landed) only
        when a deadline, scrub, or the governor forces settled state.  A
        dispatch or fetch that threw re-raises here.
        Adoption takes the program's checksums/parity/meta plus its
        ``shadow = overflowed ? snapshot : 0`` select — so a mispredicted
        queued dispatch (``overflowed``) keeps epoch A conservatively
        marked with no host-side merge; the caller then runs the
        full-recompute fallback.  The live dirty bitmap (epoch B, with
        every mark since dispatch) is carried over from the caller.
        ``deferred`` counts due ticks coalesced while the update was
        outstanding.
        """
        p = g.pending
        if p is None:
            return red_sub, False, 0
        if not wait and not _pending_ready(p):
            return None, False, 0
        with (trace.waited(self.counters, "resolve") if wait
              else contextlib.nullcontext()):
            if p.launched is not None:
                p.launched.wait()        # join: no-op unless wait forced it
            if p.error is not None:
                g.pending = None
                raise p.error
            fits = _fits_host_pending(p)
            overflowed = p.queued and not fits
            stripes, need = _counts_host_pending(p)
            self._count_pass(g, p.queued, None if overflowed else stripes,
                             g.rung)
        # A pass that overflowed a rung below the top says nothing of the
        # top: the re-dispatch sizes its rung from the need instead.
        if not overflowed or g.rung == g.engine.queue_ladder[-1]:
            g.predicted_fits = fits
        # The need of a snapshot that due ticks coalesced into understates
        # the next one: it is unknown.
        g.last_need = None if p.coalesced else need
        out = {n: dataclasses.replace(p.red[n], dirty=red_sub[n].dirty)
               for n in g.names}
        g.pending = None
        return out, overflowed, p.coalesced

    def _drain_background(self, leaves: Dict[str, Any], out: Dict[str, Any],
                          step: Optional[int] = None) -> Dict[str, Any]:
        """Run any active shard rebuild (then remesh migration) to
        completion, synchronously — settle/flush call this before adopting
        so a checkpoint taken mid-rebuild/mid-remesh never persists a
        half-pasted shard or a half-migrated geometry.

        Mutates ``out`` (dirty marks; wholesale red swap on a remesh
        adoption) and returns the possibly-replaced leaves.  Pasted/moved
        leaves are also stashed for :meth:`take_repaired` — the caller of
        settle/flush must adopt them (the store cannot mutate caller
        arrays)."""
        # ``step`` stays Optional all the way down: "caller did not supply
        # a step" is a distinct state from "step 0" (right after attach),
        # and the crash-phase hooks fill in the machine's true current
        # step only when the kwarg is absent — coercing None to 0 here
        # used to stamp rebuild/remesh phases and reports with a bogus
        # step 0.
        step_i = 0 if step is None else int(step)
        pat = self.patroller
        if pat is not None and pat.rebuild is not None:
            rep = TickReport(step=step_i)
            while pat.rebuild is not None:
                pat.rebuild.step_once(leaves, out, rep, step)
                if pat.rebuild.status.done:
                    recs = pat.rebuild.unrecoverable()
                    pat.unrecoverable.extend(recs)
                    pat.rebuild = None
            leaves.update(rep.repaired)
            self._drained.update(rep.repaired)
        if self._remesh is not None:
            rep = TickReport(step=step_i)
            while self._remesh is not None:
                self._remesh_step(leaves, out, rep, step)
            leaves.update(rep.repaired)
            self._drained.update(rep.repaired)
        return leaves

    def take_repaired(self) -> Dict[str, Any]:
        """Leaves replaced by a settle/flush-time background drain (rebuild
        paste windows, remesh migration) since the last call.  Callers that
        settle/flush mid-rebuild/mid-remesh MUST adopt these — the drained
        paste went into these arrays, not the caller's."""
        out, self._drained = self._drained, {}
        return out

    def settle(self, red: RedundancyState,
               leaves: Optional[Mapping[str, jax.Array]] = None,
               step: Optional[int] = None) -> RedundancyState:
        """Adopt every in-flight async update into ``red`` (blocking).

        No new periodic pass is scheduled (that is ``flush``).  With
        ``leaves`` provided, any active shard rebuild / remesh migration is
        drained first (outstanding paste windows complete — a checkpoint
        taken now never sees a half-pasted shard; adopt the drained leaves
        via :meth:`take_repaired`), and a mispredicted speculative queued
        update is repaired immediately with the full-recompute fallback;
        without them, its blocks simply stay marked (shadow) for the next
        pass — conservative either way.  Ticks coalesced behind the
        in-flight update fold into the next due tick.  Pass ``step`` when
        known (it may legitimately be 0): background drain windows stamp
        their reports/phases with it — ``None`` means "unknown", never
        step 0.  Joins the dispatcher for every pending (launch, then fit
        signal) — the ``dispatcher_join`` crash phase fires per joined
        group.
        """
        with trace.span("settle"):
            return self._settle(red, leaves, step)

    def _settle(self, red: RedundancyState, leaves, step: Optional[int]
                ) -> RedundancyState:
        out = dict(red)
        if leaves is not None:
            leaves = self._drain_background(dict(leaves), out, step=step)
        for g in self._protected():
            if g.pending is None:
                continue
            if self._phase_hooks:
                info = {} if step is None else {"step": int(step)}
                self._phase("dispatcher_join", red=dict(out), group=g.label,
                            **info)
            red_sub, overflowed, _ = self._resolve(
                g, {n: out[n] for n in g.names}, wait=True)
            out.update(red_sub)
            if overflowed and leaves is not None:
                self._full_fallback(g, leaves, out)
        if self._phase_hooks:
            self._phase("settle", red=dict(out))
        return out

    def _full_fallback(self, g: _Group, leaves, out: Dict[str, Any]) -> None:
        """Full-recompute repair of an overflowed speculative pass, adopted
        into ``out`` now, through the *non-donating* overlap program:
        settle also backs read-only paths (scrub), whose callers keep using
        their own red — the donating blocking program would invalidate it.
        Bitwise-identical to the blocking full program (queued=False never
        overflows, so its dirty/shadow outputs are zeros too)."""
        repaired, fits, counts = self._update_fn(g.label, "async_full")(
            {n: leaves[n] for n in g.names}, {n: out[n] for n in g.names})
        with trace.waited(self.counters, "fallback"):
            fits, counts = jax.device_get((fits, counts))
        g.predicted_fits = _fits_host(fits)
        stripes, g.last_need = _counts_host(counts)
        self._count_pass(g, False, stripes)
        out.update(repaired)

    def _scrub_fn(self, label: str):
        fn = self._jit_scrub.get(label)
        if fn is None:
            fn = jax.jit(self.groups[label].engine.scrub)
            self._jit_scrub[label] = fn
        return fn

    def tick(self, leaves, red: RedundancyState,
             step: int, *, step_time: Optional[float] = None,
             scrub_period: Optional[int] = None
             ) -> Tuple[RedundancyState, TickReport]:
        """One host-step heartbeat: schedule Algorithm 1 + scrubbing.

        Owns the whole schedule the call sites used to hand-roll: the
        ``step % T`` update cadence per vilamb group (stretched by the
        straggler governor, bounded by the freshness deadline), and
        scrubbing with the paper's double-check (re-verify on an immutable
        snapshot after quiescing before raising an alarm).  ``step_time``
        feeds the governor.  ``scrub_period`` overrides every group's
        scrub cadence (legacy ``scrub_every`` knob).

        ``leaves`` may be the flat leaf mapping or a zero-arg callable
        returning it — the callable form skips building the mapping on the
        (majority of) steps where nothing is due.

        On the default overlap-pipelined path (``RedundancyPolicy
        .async_tick``) a due tick costs the foreground only a dispatch —
        never a device->host round trip: the update program is launched
        speculatively (queued vs full chosen by the previous tick's
        device-computed fit signal, fetched via a non-blocking copy), and
        dirty epochs are double-buffered — the returned state carries the
        previous epoch's checksums/parity with a fresh dirty bitmap and
        the consumed snapshot held in ``shadow``, so the foreground's next
        step depends only on already-defined arrays and never waits on the
        in-flight update.  Results are adopted lazily on a later tick (or
        eagerly on ``flush``/``scrub``/``settle``); a mispredicted queued
        dispatch keeps its snapshot marked (the program's shadow select)
        and the full-recompute fallback runs at resolution
        (``report.overflowed``).  At most one update per group is in
        flight; due ticks arriving meanwhile coalesce
        (``report.coalesced``).

        Note: callers must always adopt the returned state — it is the
        only live lineage (the blocking path donates the Algorithm-1
        input; the overlapped path tracks the epoch buffers through it).
        """
        with trace.span("tick"):
            return self._tick(leaves, red, step, step_time, scrub_period)

    def _tick(self, leaves, red: RedundancyState, step: int,
              step_time: Optional[float], scrub_period: Optional[int]
              ) -> Tuple[RedundancyState, TickReport]:
        step = int(step)
        if step_time is not None:
            self._governor.observe(step_time)
        report = TickReport(step=step)
        out = dict(red)
        updated, deadline, scrubbed, coalesced, overflowed = [], [], [], [], []
        # Batched dispatch: the group loop only *decides* (resolve/coalesce/
        # bookkeeping); every group due for an overlapped dispatch lands in
        # to_dispatch and launches as ONE multi-group program after the
        # loop.  Scrubs run last (scrub_groups) so they see the
        # post-dispatch live view exactly as the per-group loop did.
        to_dispatch: List[Tuple[_Group, bool, int, float]] = []
        scrub_groups: List[_Group] = []
        # One clock read and one leaf materialization serve the whole tick.
        now = time.monotonic()
        materialized: Optional[Mapping[str, jax.Array]] = (
            None if callable(leaves) else leaves)

        def get_leaves():
            nonlocal materialized
            if materialized is None:
                materialized = leaves()
            return materialized

        def sub_of(g):
            lv = get_leaves()
            return {n: lv[n] for n in g.names}

        hg = self._health
        if hg is not None:
            hg.begin_tick(step, now)
        # During an active remesh migration the foreground group loop is
        # skipped wholesale: the OLD red stays frozen (authoritative for a
        # crash) while writes keep marking it via on_write, and the
        # migrator recomputes redundancy from current data window by
        # window — a due tick dispatched against the old geometry would
        # race the migration for no benefit.
        with trace.span("tick.schedule"):
            for g in (() if self._remesh is not None else self._protected()):
                lp = g.policy
                if step < g.last_update_step:
                    # The step counter restarted (new serve wave / fresh run
                    # on a long-lived store): rebase so deadlines keep their
                    # meaning.
                    g.last_update_step = 0
                sp = (scrub_period if scrub_period is not None
                      else lp.scrub_period_steps)
                scrub_due = bool(sp and policy_mod.should_scrub(step, sp))
                if lp.mode == "vilamb":
                    margin = sync_esc = retry = False
                    if hg is not None:
                        # Escalation-ladder rung 1: a wedged in-flight update
                        # is abandoned (freshness clocks roll back to
                        # pre-dispatch) and re-dispatched below after a
                        # bounded backoff.  The retry flag forces the
                        # dispatch this tick: ``due`` is step-aligned, so
                        # waiting for the next period boundary would let the
                        # breaker cool down between retries.
                        retry = hg.check_pending(g)
                        sync_esc = hg.is_sync_escalated(g.label)
                        margin = hg.within_margin(g, step, now)
                    eff = min(lp.period_steps * self._governor.scale,
                              self.policy.period_cap)
                    due = policy_mod.should_update(step, eff)
                    overdue = (
                        (lp.max_vulnerable_steps > 0
                         and step - g.last_update_step
                         >= lp.max_vulnerable_steps)
                        or (lp.max_vulnerable_seconds > 0
                            and now - g.last_update_time
                            >= lp.max_vulnerable_seconds))
                    if self._async_group(g) and not sync_esc:
                        # Overlap pipeline: resolve lazily (blocking only when
                        # a deadline or a scrub forces settled state), then
                        # keep the pipeline primed with at most one in-flight
                        # update.
                        had_pending = g.pending is not None
                        # Rung 2: within the governor's deadline margin the
                        # tick stops speculating — resolve blocking and
                        # re-dispatch, meeting the deadline early instead of
                        # missing it.
                        forced = overdue or scrub_due or margin
                        if had_pending and forced and self._phase_hooks:
                            # The crash point right before the tick joins the
                            # dispatcher (launch, then fit signal).
                            self._phase("dispatcher_join", red=dict(out),
                                        group=g.label, step=step)
                        res, ovf, deferred = self._resolve(
                            g, {n: out[n] for n in g.names}, wait=forced)
                        if res is None:
                            # Still in flight: fold this due tick into it.  The
                            # deadline clock keeps running, so a wedged device
                            # eventually forces a blocking resolve via overdue.
                            if due:
                                g.pending.coalesced += 1
                                coalesced.append(g.label)
                                updated.append(g.label)
                                if self._phase_hooks:
                                    self._phase("coalesce", red=dict(out),
                                                group=g.label, step=step)
                        else:
                            out.update(res)
                            if had_pending and self._phase_hooks:
                                self._phase(
                                    "adopt_forced" if forced
                                    else "adopt", red=dict(out), group=g.label,
                                    step=step, overflowed=ovf)
                            if (had_pending and margin
                                    and not (overdue or scrub_due)
                                    and hg is not None):
                                hg.note_forced_resolve(g.label, step)
                            if ovf:
                                # Speculation missed: the queued program
                                # could not cover the snapshot (its blocks
                                # stayed marked via the shadow select).  Run
                                # the always-correct full program now.
                                overflowed.append(g.label)
                            if (ovf or due or overdue or deferred or margin
                                    or retry):
                                # Snapshot the freshness clocks *before* the
                                # bump below: the governor's wedged-dispatch
                                # abandon rolls back to these, and the batched
                                # dispatch only runs after this loop.
                                g.rung = self._next_rung(g, ovf, retry)
                                g.redispatch = bool(ovf)
                                to_dispatch.append(
                                    (g, g.rung > 0,
                                     g.last_update_step, g.last_update_time))
                                g.last_update_step = step
                                g.last_update_time = now
                                if due or overdue or margin:
                                    updated.append(g.label)
                                if overdue and not due:
                                    deadline.append(g.label)
                    elif sync_esc or due or overdue or margin:
                        if g.pending is not None:
                            # Rung 4 engaged with an update still in flight
                            # (e.g. escalation via a reported violation): adopt
                            # it first — a stale pending resolved *after* the
                            # blocking pass would clobber newer checksums.
                            if self._phase_hooks:
                                self._phase("dispatcher_join", red=dict(out),
                                            group=g.label, step=step)
                            red_sub, _, _ = self._resolve(
                                g, {n: out[n] for n in g.names}, wait=True)
                            out.update(red_sub)
                        with trace.span("tick.dispatch"):
                            out.update(self._dispatch_blocking(
                                g, sub_of(g),
                                {n: out[n] for n in g.names}))
                        g.last_update_step = step
                        g.last_update_time = now
                        updated.append(g.label)
                        if self._phase_hooks:
                            self._phase("blocking_update", red=dict(out),
                                        group=g.label, step=step)
                        if overdue and not due:
                            deadline.append(g.label)
                if scrub_due:
                    scrub_groups.append(g)
        if to_dispatch:
            # The tentpole: every due group launches in ONE batched
            # multi-group program with one stacked fits vector, its fit
            # fetch handed to the resolver thread — the foreground's cost
            # is the epoch swap plus one asynchronous dispatch.
            if self._phase_hooks:
                self._phase("dispatcher_enqueue", red=dict(out), step=step,
                            groups=tuple(g.label for g, *_ in to_dispatch))
            with trace.span("tick.dispatch"):
                out.update(self._dispatch_async_many(
                    to_dispatch, get_leaves, out, step))
            if self._phase_hooks:
                for g, *_ in to_dispatch:
                    self._phase("dispatch", red=dict(out), group=g.label,
                                step=step, queued=g.pending.queued)
        if scrub_groups:
            with trace.span("tick.scrub"):
                for g in scrub_groups:
                    mm, alarms = self._scrub_group(g, sub_of(g), out)
                    scrubbed.append(g.label)
                    report.mismatches += mm
                    report.alarms += alarms
                    if self._phase_hooks:
                        self._phase("scrub", red=dict(out), group=g.label,
                                    step=step, mismatches=mm)
        report.updated = tuple(updated)
        report.deadline_fired = tuple(deadline)
        report.scrubbed = tuple(scrubbed)
        report.coalesced = tuple(coalesced)
        report.overflowed = tuple(overflowed)
        # Elastic remesh slots between rebuild and patrol in the priority
        # ladder: a queued request starts only once no rebuild is active or
        # pending (loss recovery first), and while a migration runs the
        # patroller is skipped entirely (its parity geometry is tied to the
        # old mesh; a fresh patroller is built at adoption).
        ran_remesh = False
        with (trace.span("remesh") if self.remeshing
              else contextlib.nullcontext()):
            if (self._remesh is None and self._remesh_request is not None
                    and (self.patroller is None
                         or (self.patroller.rebuild is None
                             and not self.patroller._pending_loss))):
                self._remesh_start(get_leaves(), out, step, report)
            if self._remesh is not None:
                lv = dict(get_leaves())
                lv.update(report.repaired)      # moved leaves, if started now
                self._remesh_step(lv, out, report, step)
                ran_remesh = True
            if hg is not None and ran_remesh:
                # The group loop was suspended this tick (old-geometry red is
                # authoritative until adoption) — the one window the ladder
                # above cannot cover.  When a group's freshness margin expired
                # mid-migration, drain the remaining windows synchronously
                # (remesh_drain, rung 2: the SLO beats the bounded per-tick
                # window), then run blocking updates post-adoption.  With
                # remesh_drain=False the migration keeps its bound and end_tick
                # reports the violation instead — never silent either way.
                forced = hg.remesh_overdue(step, now)
                if forced and self._remesh is not None and hg.hp.remesh_drain:
                    lv = dict(get_leaves())
                    lv.update(report.repaired)
                    while self._remesh is not None:
                        self._remesh_step(lv, out, report, step)
                if forced and self._remesh is None:
                    lv = dict(get_leaves())
                    lv.update(report.repaired)   # moved leaves (new geometry)
                    extra = []
                    for g in self._protected():
                        if g.policy.mode != "vilamb" or g.label not in forced:
                            continue
                        with trace.span("tick.dispatch"):
                            out.update(self._dispatch_blocking(
                                g, {n: lv[n] for n in g.names},
                                {n: out[n] for n in g.names}))
                        g.last_update_step = step
                        g.last_update_time = now
                        extra.append(g.label)
                        hg.note_remesh_drain(g.label, step)
                    report.updated = report.updated + tuple(extra)
                    report.deadline_fired = (report.deadline_fired
                                             + tuple(extra))
                    updated.extend(extra)
        if self.patroller is not None and not ran_remesh:
            # Low-priority background duty, after every foreground decision:
            # the patroller sees the post-dispatch live view (in-flight
            # blocks are shadow-marked, so probes conservatively skip them)
            # and only dispatches a probe on quiet ticks (no update
            # dispatched) — rebuild, being loss recovery, runs every tick
            # within its byte budget.  It may repair/rebuild leaves
            # (report.repaired — callers adopt) and mark rebuilt blocks
            # dirty in ``out``.
            # A queued (not yet started) remesh also counts as busy: the
            # ladder puts remesh above patrol, so probes defer while a
            # geometry change is waiting on an active rebuild to finish.
            with trace.span("patrol"):
                self.patroller.on_tick(
                    get_leaves, out, step, report,
                    busy=bool(updated) or self._remesh_request is not None)
        if hg is not None:
            # Age audit + breaker transitions; attaches report.health and
            # raises FreshnessViolationError only when the ladder is
            # exhausted and a deadline is still blown (violation_mode).
            hg.end_tick(report, step, now)
        if self._phase_hooks:
            self._phase("tick", red=dict(out), step=step, report=report)
        return out, report

    def flush(self, leaves: Mapping[str, jax.Array], red: RedundancyState,
              step: Optional[int] = None) -> RedundancyState:
        """Battery/preemption flush: force Algorithm 1 on every vilamb group
        now (paper §3.3).  Sync groups are up-to-date by construction.
        Any active shard rebuild / remesh migration is drained first
        (outstanding paste windows complete before anything is adopted —
        adopt the pasted leaves via :meth:`take_repaired`), then any
        in-flight async update is resolved, so the result is
        bitwise-identical to the blocking path's flush.  Pass ``step`` when
        known so the steps-based freshness deadline does not fire a
        spurious pass right after the flush."""
        with trace.span("flush"):
            return self._flush(leaves, red, step)

    def _flush(self, leaves, red: RedundancyState,
               step: Optional[int]) -> RedundancyState:
        out = dict(red)
        leaves = self._drain_background(dict(leaves), out, step=step)
        now = time.monotonic()
        for g in self._protected():
            if g.policy.mode == "vilamb":
                if g.pending is not None:
                    # Eager resolution; an overflowed speculative dispatch
                    # left its blocks marked (shadow), so the forced pass
                    # below covers them.
                    if self._phase_hooks:
                        info = {} if step is None else {"step": int(step)}
                        self._phase("dispatcher_join", red=dict(out),
                                    group=g.label, **info)
                    red_sub, _, _ = self._resolve(
                        g, {n: out[n] for n in g.names}, wait=True)
                    out.update(red_sub)
                out.update(self._dispatch_blocking(
                    g, {n: leaves[n] for n in g.names},
                    {n: out[n] for n in g.names}))
                g.last_update_time = now
                if step is not None:
                    g.last_update_step = int(step)
        # Quiescent point: every pending is resolved, so the dispatcher
        # thread has nothing left to do — shut it down cleanly (the
        # battery/preemption flush is exactly where a lingering thread
        # would outlive the process's useful life).  It is re-created
        # lazily on the next overlapped dispatch.
        self._stop_dispatcher()
        if self._phase_hooks:
            self._phase("flush", red=dict(out),
                        **({} if step is None else {"step": int(step)}))
        return out

    # --------------------------------------------------------- elastic remesh
    def remesh(self, new_mesh: Any,
               specs: Optional[Mapping[str, Any]] = None) -> None:
        """Queue an elastic geometry change: grow/shrink the device mesh by
        incrementally re-striping every protected leaf (repro.remesh).

        No stop-the-world re-attach: the migration runs over bounded
        per-tick windows (``RedundancyPolicy.remesh_bytes_per_tick``)
        starting on the next ``tick`` once no shard rebuild is active or
        pending, surfacing a ``RemeshStatus`` through ``TickReport.remesh``
        with a pinned tick bound of ``ceil(n_blocks / window)`` per leaf.
        ``specs`` optionally overrides per-leaf PartitionSpecs for the new
        mesh (default: the specs declared at ``attach`` — valid whenever
        the new mesh keeps the same axis names).

        Raises :class:`repro.remesh.RemeshInProgressError` when a remesh is
        already queued or running, and
        :class:`repro.remesh.RemeshGeometryError` when a leaf cannot be
        evenly re-striped onto the new mesh (dim not divisible by the new
        shard factor) or a group mode does not support migration.
        """
        from repro.remesh import RemeshInProgressError, validate_remesh
        if self._remesh is not None or self._remesh_request is not None:
            raise RemeshInProgressError(
                "a remesh is already queued or in progress")
        new_specs = dict(self._specs) if hasattr(self, "_specs") else {}
        new_specs.update(specs or {})
        validate_remesh(self, new_mesh, new_specs)
        self._remesh_request = (new_mesh, new_specs)

    @property
    def remeshing(self) -> bool:
        """True while a remesh is queued or actively migrating."""
        return self._remesh is not None or self._remesh_request is not None

    def _remesh_start(self, leaves: Mapping[str, jax.Array], out, step: int,
                      report) -> None:
        """Begin the queued migration: settle in-flight overlapped updates
        against the OLD geometry (their outputs are old-sharded), then
        build the migrator — one ``device_put`` of every leaf onto the new
        mesh (value-identical; surfaced via ``report.repaired`` so the
        caller adopts the moved arrays) plus zero-initialised new-geometry
        redundancy the per-tick windows fill in."""
        from repro.remesh import RemeshMigrator
        new_mesh, new_specs = self._remesh_request
        self._remesh_request = None
        for g in self._protected():
            if g.pending is None:
                continue
            if self._phase_hooks:
                self._phase("dispatcher_join", red=dict(out), group=g.label,
                            step=step)
            red_sub, ovf, _ = self._resolve(
                g, {n: out[n] for n in g.names}, wait=True)
            out.update(red_sub)
            if ovf:
                self._full_fallback(g, leaves, out)
        # The migration swaps engines and jit caches at adoption; the old
        # geometry's dispatcher (and any compiled programs its queued jobs
        # closed over) must not leak across the handover.
        self._stop_dispatcher()
        self._remesh = RemeshMigrator(self, new_mesh, new_specs,
                                      leaves, out, step)
        report.repaired.update(self._remesh.moved)
        report.remesh = self._remesh.status

    def _remesh_step(self, leaves, out, report, step: Optional[int]) -> None:
        """One bounded migration window; adopts the new geometry (red swap,
        group/engine swap, fresh patroller, ``geometry_version`` bump) on
        the tick the last window completes."""
        m = self._remesh
        m.step_once(leaves, out, report, step)
        if m.status.done:
            m.adopt(out, report)
            self._remesh = None

    def redundancy_step(self, leaves: Mapping[str, jax.Array],
                        red: RedundancyState) -> RedundancyState:
        """Traceable flush (no jit caching/donation) — embed in outer jits.

        Bypasses the overlap pipeline: do not interleave with ``tick`` while
        an async update is in flight (``settle`` first) — the later adoption
        would roll checksums back over this pass's unmarked blocks.
        """
        out = dict(red)
        for g in self._protected():
            if g.policy.mode == "vilamb":
                out.update(g.engine.redundancy_step(
                    {n: leaves[n] for n in g.names},
                    {n: out[n] for n in g.names}))
        return out

    # ------------------------------------------------------- verify + recover
    def _scrub_group(self, g: _Group, sub, red) -> Tuple[int, int]:
        """Scrub one group given its leaf sub-dict (double-check protocol)."""
        fn = self._scrub_fn(g.label)
        red_sub = {n: red[n] for n in g.names}
        mm = fn(sub, red_sub)
        with trace.waited(self.counters, "scrub"):
            total = int(sum(int(v.sum()) for v in jax.tree.leaves(mm)))
        alarms = 0
        if total:
            # Double-check (paper §3.4): quiesce in-flight work, re-verify on
            # an immutable snapshot before raising the alarm.
            with trace.waited(self.counters, "scrub"):
                jax.block_until_ready(sub)
                mm = fn(sub, red_sub)
                total = int(sum(int(v.sum()) for v in jax.tree.leaves(mm)))
            if total:
                alarms = 1
                self.corruption_alarms += 1
        return total, alarms

    def scrub(self, leaves: Mapping[str, jax.Array], red: RedundancyState
              ) -> Dict[str, jax.Array]:
        """Per-leaf mismatch masks over clean blocks (no double-check).

        In-flight async updates are settled first (including the full
        fallback on a queued misprediction) so the masks match what the
        blocking path would report.  The caller's ``red`` is left as-is —
        it stays a conservative view (in-flight blocks marked) until the
        next tick/flush adopts results.
        """
        red = self.settle(red, leaves)
        out: Dict[str, jax.Array] = {}
        for g in self._protected():
            out.update(self._scrub_fn(g.label)(
                {n: leaves[n] for n in g.names},
                {n: red[n] for n in g.names}))
        return out

    def scrub_check(self, leaves: Mapping[str, jax.Array],
                    red: RedundancyState) -> int:
        """Scrub all protected groups with the double-check protocol.

        Settles in-flight async updates first — calling this mid-flight
        yields the same mismatch count as the blocking path.
        """
        red = self.settle(red, leaves)
        total = 0
        for g in self._protected():
            mm, _ = self._scrub_group(g, {n: leaves[n] for n in g.names}, red)
            total += mm
        return total

    def verify_meta(self, red: RedundancyState) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        for g in self._protected():
            out.update(g.engine.verify_meta({n: red[n] for n in g.names}))
        return out

    def recover_block(self, leaf: jax.Array, r: Any, name: str, block_id):
        engine = self.engine_for(name)
        if engine is None:
            raise KeyError(f"{name} is not parity-protected")
        return engine.recover_block(leaf, r, name, block_id)

    def read_verified(self, leaves: Mapping[str, jax.Array],
                      red: RedundancyState, name: str,
                      block_ids: Sequence[int]) -> Dict[str, Any]:
        """Degraded-mode verified read: per requested **global** block,
        return data that is provably current — never stale or in-flight
        garbage — even while a shard is lost or a remesh is migrating.

        Per block, in order: (1) a block inside the vulnerability window
        (``dirty | shadow``) returns the current data — writes land in the
        data array before redundancy, so the array itself is the newest
        truth (unless the block's write was in flight when its shard died,
        a named pre-loss casualty); (2) a clean block whose checksum
        verifies returns the current data; (3) a mismatching block is
        reconstructed — from the active rebuild's cross-shard-parity image
        when its shard is the lost one, else from its XOR stripe via
        ``recover_block`` — and the reconstruction is admitted only if it
        verifies against the stored checksum.  Unverifiable blocks retry
        with backoff (``read_retry_attempts`` / ``read_retry_backoff_s`` —
        a transiently vulnerable stripe may settle); when the budget is
        exhausted a typed :class:`repro.core.UnrecoverableReadError` is
        raised carrying ``UnrecoverableBlock`` records (reason
        ``read_timeout``).

        Returns ``{global_block_id: uint32 lane row (lanes_per_block,)}``.
        A host-side cold path (one blocking fetch per attempt): correctness
        over throughput, by design.
        """
        from . import blocks as blocks_mod
        from . import checksum as checksum_mod
        from .repairs import UnrecoverableBlock, UnrecoverableReadError
        from repro.faults.inject import bits_to_mask

        eng = self.engine_for(name)
        if eng is None:
            raise KeyError(f"{name} is not parity-protected")
        meta = self.metas[name]
        k = self.shard_factor(name)
        rows_local = (eng.global_leaf_structs[name].shape[0] // k
                      if eng.mesh is not None else meta.shape[0])
        want = [int(b) for b in block_ids]
        for b in want:
            if not 0 <= b < k * meta.n_blocks:
                raise IndexError(f"{name}: global block {b} out of range "
                                 f"(0..{k * meta.n_blocks - 1})")
        attempts = max(1, int(self.policy.read_retry_attempts))
        # Exponential, capped, jittered, budget-bounded retry delays — the
        # same schedule the health governor's dispatch-retry rung uses
        # (base 0 = the backwards-compatible no-sleep default).
        from repro.health.backoff import backoff_schedule
        delays = backoff_schedule(
            attempts - 1, float(self.policy.read_retry_backoff_s),
            cap=float(self.policy.read_retry_backoff_cap_s),
            total=float(self.policy.read_retry_total_s),
            jitter_frac=float(self.policy.read_retry_jitter_frac))
        results: Dict[int, np.ndarray] = {}

        def shard_lanes(arr: np.ndarray, s: int) -> np.ndarray:
            sub = arr[s * rows_local:(s + 1) * rows_local] if k > 1 else arr
            return np.asarray(blocks_mod.to_lanes(jnp.asarray(sub), meta))

        def ck_of(lane_row: np.ndarray, lb: int) -> int:
            return int(np.asarray(checksum_mod.block_checksums(
                jnp.asarray(lane_row[None, :]), block_offset=lb))[0])

        for attempt in range(attempts):
            pending = [b for b in want if b not in results]
            if not pending:
                break
            if attempt and delays[attempt - 1] > 0:
                time.sleep(delays[attempt - 1])
            arr = np.asarray(leaves[name])
            r = red[name]
            live = bits_to_mask(
                np.asarray(r.dirty) | np.asarray(r.shadow), meta.n_blocks,
                shards=k).reshape(k, meta.n_blocks)
            cks = np.asarray(r.checksums).reshape(k, meta.n_blocks)
            reb = self.patroller.rebuild if self.patroller else None
            if reb is not None and reb.name != name:
                reb = None
            lanes_cache: Dict[int, np.ndarray] = {}
            for b in pending:
                s, lb = divmod(b, meta.n_blocks)
                on_lost = reb is not None and reb.shard == s
                if s not in lanes_cache:
                    lanes_cache[s] = shard_lanes(arr, s)
                row = lanes_cache[s][lb]
                if live[s, lb]:
                    # In the vulnerability window: the data array holds the
                    # newest write — UNLESS that write was in flight when
                    # the shard died (pre-loss mark): its data died with
                    # the shard and the live bytes are scribble.
                    if not (on_lost and bool(reb.preloss[lb])):
                        results[b] = row.copy()
                    continue
                if ck_of(row, lb) == int(cks[s, lb]):
                    results[b] = row.copy()
                    continue
                # Mismatch: reconstruct, admit only verified bytes.
                if (on_lost and bool(reb.eligible[lb])
                        and not bool(reb.written[lb])):
                    cand = np.asarray(reb.recon)[lb]
                    if ck_of(cand, lb) == int(cks[s, lb]):
                        results[b] = cand.copy()
                        continue
                leaf2, ok = eng.recover_block(leaves[name], r, name, b)
                if bool(ok):
                    cand = shard_lanes(np.asarray(leaf2), s)[lb]
                    if ck_of(cand, lb) == int(cks[s, lb]):
                        results[b] = cand.copy()
        missing = [b for b in want if b not in results]
        if missing:
            recs = tuple(UnrecoverableBlock(
                name, blocks_mod.global_stripe_id(meta, b), (b,),
                "read_timeout") for b in missing)
            raise UnrecoverableReadError(name, recs)
        return {b: results[b] for b in want}

    def repair(self, leaves: Mapping[str, jax.Array], red: RedundancyState,
               mismatches: Mapping[str, jax.Array],
               details: Optional[List[Any]] = None) -> Tuple[Dict, int, int]:
        """Parity-rebuild every detected-corrupt block; see failure module.

        ``details`` (optional list) collects structured
        :class:`repro.core.repairs.UnrecoverableBlock` records for every
        refused stripe."""
        from repro.ckpt.failure import repair_corruption
        return repair_corruption(self, leaves, red, mismatches,
                                 details=details)

    def declare_shard_lost(self, name: str, shard: int,
                           red: Optional[RedundancyState] = None) -> None:
        """Tell the patroller a shard of ``name`` is lost (operator signal).

        The patroller normally detects wholesale shard corruption from its
        own probes (``shard_loss_threshold``); this is the explicit path
        for known losses (a device dropped out).  Requires the patroller
        (``RedundancyPolicy.patrol_bytes_per_tick > 0``); the rebuild
        starts on the next ``tick``.  Pass the current ``red`` state when
        available: its ``dirty | shadow`` marks snapshot which blocks had
        writes in flight at declaration (data died with the shard — they
        report as unrecoverable), so that foreground writes landing
        *after* the declaration still classify as fresh.
        """
        if self.patroller is None:
            raise RuntimeError(
                "declare_shard_lost needs the scrub patroller "
                "(set RedundancyPolicy.patrol_bytes_per_tick > 0)")
        if self.remeshing:
            # The patroller (and its cross-shard parity) is rebuilt fresh
            # at remesh adoption — a loss queued now would silently vanish
            # with the old patroller.  Fail loudly instead.
            raise RuntimeError(
                f"{name}: cannot declare a shard lost while a remesh is "
                "queued or migrating; re-declare after TickReport.remesh "
                "reports done")
        self.patroller.declare_shard_lost(name, shard, red)

    def inject(self, leaves: Mapping[str, jax.Array], red: RedundancyState,
               spec) -> Tuple[Dict[str, jax.Array], RedundancyState]:
        """Apply one ``repro.faults.FaultSpec`` functionally (test/CI hook).

        The store is the façade for fault injection too: corruptions are
        placed in block-lane space against this store's exact geometry —
        global block space under a mesh (the owning shard's slice is
        corrupted) — never via test-local array surgery.  Returns new
        ``(leaves, red)``; inputs are untouched.
        """
        from repro.faults.inject import apply_fault
        return apply_fault(self.metas, leaves, red, spec,
                           factors={n: self.shard_factor(n)
                                    for n in self.metas})

    def vulnerable_masks(self, red: RedundancyState) -> Dict[str, jax.Array]:
        """Per-leaf bool[n_blocks] masks of the instantaneous vulnerability
        window (``dirty | shadow``) — the exact set the §5 oracle audits.
        Deliberately *not* settled, like :meth:`dirty_stats`: blocks
        consumed by an in-flight overlapped update stay marked until
        adoption."""
        out: Dict[str, jax.Array] = {}
        for g in self._protected():
            out.update(g.engine.vulnerable_masks(
                {n: red[n] for n in g.names}))
        return out

    # ------------------------------------------------------------- accounting
    def dirty_stats(self, red: RedundancyState) -> Dict[str, Dict[str, Any]]:
        """Per-leaf dirty/vulnerable counts.  Deliberately *not* settled:
        blocks consumed by an in-flight overlapped update stay counted
        (via the live view's shadow) until resolution — the conservative
        answer for flush sizing and MTTDL accounting."""
        out: Dict[str, Dict[str, Any]] = {}
        for g in self._protected():
            out.update(g.engine.dirty_stats({n: red[n] for n in g.names}))
        return out

    def estimate_flush(self, red: RedundancyState) -> "policy_mod.FlushEstimate":
        """Size the preemption flush (battery analogue, paper §4.7)."""
        stats = jax.tree.map(int, self.dirty_stats(red))
        metas = self.metas
        return policy_mod.estimate_flush(
            stats, {n: metas[n].bytes_per_block for n in stats},
            self.policy.stripe_data_blocks)


def as_store(obj: Any, mode: Optional[str] = None,
             period_steps: Optional[int] = None, scrub_period_steps: int = 0,
             caller: str = "caller") -> Optional[ProtectedStore]:
    """Coerce legacy ``(engine, mode)`` arguments into a ProtectedStore.

    The one-release deprecation shim behind ``Trainer(engine=..., mode=...)``
    and friends.  ``None`` (or mode "none" with no engine) maps to no store.
    """
    if obj is None or isinstance(obj, ProtectedStore):
        return obj
    if isinstance(obj, RedundancyEngine):
        warnings.warn(
            f"passing engine=/mode= to {caller} is deprecated; build a "
            "repro.core.ProtectedStore with a RedundancyPolicy instead",
            DeprecationWarning, stacklevel=3)
        return ProtectedStore.from_engine(
            obj, mode or "vilamb", period_steps=period_steps,
            scrub_period_steps=scrub_period_steps)
    raise TypeError(f"expected ProtectedStore/RedundancyEngine/None, got {obj!r}")
