"""Overlap pipeline: what the redundancy path costs the foreground thread.

The paper's headline is *asynchronous* redundancy — background updates
overlapped with foreground writes.  The quantity the overlap pipeline
changes is the **foreground stall**: the time the application thread spends
inside ``store.tick`` per step.  The blocking tick (PR2, ``async_tick=
False``) pays a host-side ``queue_fits`` round trip on every due tick,
which drains the whole dispatch pipeline before the update can even
launch; the overlap-pipelined tick (PR3 default) costs one speculative
dispatch plus a non-blocking flag read.

Measured per step over a write+tick loop at period 4.  Every mode —
``none`` baseline, blocking, pipelined — runs the **same untimed warm
loop** (``2 * period + 1`` write+tick steps, then settle) before its
timed window, so compilation of the tick path (including the batched
multi-group update program and the resolver-thread spin-up for the
pipelined variant) never lands inside the measurement:

  * ``overlap/tick_stall_*``  — mean host time inside ``tick`` (the
    foreground redundancy overhead; ``derived`` repeats the mean next to
    the p99 so the due-tick spike is visible).  **Headline**:
    ``overlap/overhead_reduction`` is the ratio of blocking vs pipelined
    stall over the ``none`` baseline, computed from the *means* — the
    same statistic the ``tick_stall_*`` value column prints — with the
    p99-based ratio quoted alongside in ``derived``.  The acceptance bar
    is >= 2x.
  * ``overlap/endtoend_*``    — full wall clock per step, for context.  On
    this repo's 2-core CPU container the "device" shares cores with the
    host and the two variants execute bitwise-identical update programs,
    so end-to-end wall is device-bound and near-equal here; on an
    accelerator (device compute does not steal host cycles) the stall
    difference converts 1:1 into step time.

Both variants settle and drain every dispatched update inside the timed
window, so the comparison is work-for-work fair.

The ``overlap_sharded/*`` rows repeat the stall comparison on a 2x2x2
host-device mesh (per-shard work queues).  Here the pipelined tick
launches ONE batched multi-group update program per due tick and hands
the single stacked fit vector to the resolver thread, which fetches and
AND-folds it off the critical path — versus the blocking tick's
per-group ``queue_fits`` round trips.  The sharded leg uses its own
(larger) ``sharded_rows``/``sharded_batch`` shapes: with toy shapes the
per-due-tick update work is negligible and both modes degenerate to the
same per-array dispatch overhead, hiding exactly the regression this row
guards.  The multi-device run happens in a subprocess because
``XLA_FLAGS=--xla_force_host_platform_device_count`` must be exported
before jax is imported.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import ROW_ELEMS, Region, cpu_child_rows, key_stream

SHARDED_DEVICES = 8
# The sharded store protects this many separately-sharded leaves (= vilamb
# groups).  One group would hide the regression this row guards: the
# blocking tick pays a dispatch + host round trip per GROUP, the pipelined
# tick one batched program per tick regardless of the group count.
SHARDED_GROUPS = 8


def _measure(mode: str, pipelined: bool, steps: int, n_rows: int,
             batch: int, period: int):
    r = Region(n_rows=n_rows, mode=mode, period=period, pipelined=pipelined)
    warm = 2 * period + 1
    keys = key_stream("uniform", steps + warm + 1, batch, n_rows)
    vals = jnp.ones((batch, ROW_ELEMS), jnp.float32)
    heap, red = r.heap, r.red
    heap, red = r.write(heap, red, keys[0], vals)
    if r.store.has_periodic:
        red = r.store.flush({"heap": heap}, red)
    # Identical untimed warm loop for every mode: two full periods of
    # write+tick (covers compilation of the due-tick update program and,
    # for the pipelined variant, the resolver-thread spin-up), then a
    # settle so each timed window starts from the same quiescent state.
    for i, rows in enumerate(keys[1:warm + 1], 1):
        heap, red = r.write(heap, red, rows, vals)
        red, _ = r.store.tick({"heap": heap}, red, i)
    red = r.store.settle(red, {"heap": heap})
    jax.block_until_ready((heap, jax.tree.leaves(red)))
    ticks = []
    t0 = time.perf_counter()
    for i, rows in enumerate(keys[warm + 1:], warm + 1):
        heap, red = r.write(heap, red, rows, vals)
        s0 = time.perf_counter()
        red, _ = r.store.tick({"heap": heap}, red, i)
        ticks.append(time.perf_counter() - s0)
    red = r.store.settle(red, {"heap": heap})
    jax.block_until_ready((heap, jax.tree.leaves(red)))
    wall_us = (time.perf_counter() - t0) / steps * 1e6
    t = np.asarray(ticks) * 1e6
    return float(t.mean()), float(np.percentile(t, 99)), wall_us


def _measure_sharded(pipelined, steps: int, n_rows: int, batch: int,
                     period: int, mode: str = "vilamb"):
    """One sharded stall measurement (runs inside the 8-device child).

    The store protects ``SHARDED_GROUPS`` separately-sharded leaves —
    the shape a real train/serve state has — so every due tick is a
    *multi-group* tick: the blocking path pays one dispatch + host
    round trip per group, the pipelined path one batched program for
    all of them with the fit fetch on the resolver thread.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    spec = P(("pod", "data", "model"), None)
    g_rows, g_batch = n_rows // SHARDED_GROUPS, batch // SHARDED_GROUPS
    names = [f"heap{k}" for k in range(SHARDED_GROUPS)]
    pol = RedundancyPolicy.single(mode, period_steps=period,
                                  async_tick=pipelined)
    store = ProtectedStore(pol, mesh=mesh).attach(
        {nm: jax.ShapeDtypeStruct((g_rows, ROW_ELEMS), jnp.float32)
         for nm in names},
        specs={nm: spec for nm in names})
    leaves = {nm: jax.device_put(
        jax.random.normal(jax.random.PRNGKey(k), (g_rows, ROW_ELEMS),
                          jnp.float32), NamedSharding(mesh, spec))
        for k, nm in enumerate(names)}
    red = store.init(leaves) if store.protects else {}
    rng = np.random.default_rng(0)
    warm = 2 * period + 1
    all_rows = [jnp.asarray(np.sort(rng.choice(g_rows, g_batch,
                                               replace=False)))
                for _ in range(steps + warm + 1)]

    # The documented write path: on_write is traceable and belongs INSIDE
    # the caller's jitted mutation step (train/serve do exactly this) —
    # one program per step, not 2 eager ops + a dirty-mark per leaf.
    @jax.jit
    def write_step(leaves, red, rows):
        leaves = {nm: v.at[rows].add(1.0) for nm, v in leaves.items()}
        if store.protects:
            ev = jnp.zeros((g_rows,), bool).at[rows].set(True)
            red = store.on_write(red, events={nm: ev for nm in names})
        return leaves, red

    def one_step(leaves, red, i, rows, ticks=None):
        leaves, red = write_step(leaves, red, rows)
        s0 = time.perf_counter()
        red, _ = store.tick(leaves, red, i)
        if ticks is not None:
            ticks.append(time.perf_counter() - s0)
        return leaves, red

    leaves = {nm: v.at[all_rows[0]].add(1.0) for nm, v in leaves.items()}
    if store.has_periodic:
        red = store.flush(leaves, red)
    # Same untimed warm loop as the single-device harness: blocking and
    # pipelined both compile their due-tick programs (for pipelined, the
    # one batched multi-group dispatch) and settle before timing.
    for i, rows in enumerate(all_rows[1:warm + 1], 1):
        leaves, red = one_step(leaves, red, i, rows)
    if store.protects:
        red = store.settle(red, leaves)
    jax.block_until_ready((leaves, jax.tree.leaves(red)))
    ticks = []
    t0 = time.perf_counter()
    for i, rows in enumerate(all_rows[warm + 1:], warm + 1):
        leaves, red = one_step(leaves, red, i, rows, ticks)
    if store.protects:
        red = store.settle(red, leaves)
    jax.block_until_ready((leaves, jax.tree.leaves(red)))
    wall_us = (time.perf_counter() - t0) / steps * 1e6
    t = np.asarray(ticks) * 1e6
    return float(t.mean()), float(np.percentile(t, 99)), wall_us


def sharded_child(steps: int, n_rows: int, batch: int, period: int) -> None:
    """Child entry: print the sharded CSV rows (stdout is the protocol)."""
    n = _measure_sharded(True, steps, n_rows, batch, period, mode="none")
    b = _measure_sharded(False, steps, n_rows, batch, period)
    p = _measure_sharded(True, steps, n_rows, batch, period)
    # The ratio is computed from the MEANS — the same statistic the
    # tick_stall_* value column prints — with the p99-based ratio quoted
    # alongside, so the guarded number and the printed numbers agree.
    noise_us = 5.0
    ratio = max(b[0] - n[0], noise_us) / max(p[0] - n[0], noise_us)
    ratio99 = max(b[1] - n[1], noise_us) / max(p[1] - n[1], noise_us)
    dev = (f"{SHARDED_DEVICES} host devices, {SHARDED_GROUPS} vilamb "
           "groups, per-shard queues")
    g = f"{SHARDED_GROUPS}g"
    for name, us, derived in (
            ("overlap_sharded/tick_stall_none", n[0],
             f"mean {n[0]:.0f} / p99 {n[1]:.0f} us (baseline; {dev})"),
            (f"overlap_sharded/tick_stall_blocking_{g}", b[0],
             f"mean {b[0]:.0f} / p99 {b[1]:.0f} us; one dispatch + "
             "queue_fits round trip PER GROUP each due tick"),
            (f"overlap_sharded/tick_stall_pipelined_{g}", p[0],
             f"mean {p[0]:.0f} / p99 {p[1]:.0f} us; ONE batched "
             "multi-group program, fit fetch+fold on the resolver thread"),
            ("overlap_sharded/overhead_reduction", 0.0,
             f"{ratio:.2f}x sharded stall cut from means "
             f"(p99-based {ratio99:.2f}x; bar: >= 2x)"),
            ("overlap_sharded/endtoend_none", n[2], "wall us/step"),
            ("overlap_sharded/endtoend_blocking", b[2],
             "wall us/step (device-bound on shared-CPU container)"),
            ("overlap_sharded/endtoend_pipelined", p[2],
             "wall us/step (identical device work by construction)")):
        print(f"{name},{us:.2f},{derived}")


def _sharded_rows(steps: int, n_rows: int, batch: int, period: int):
    return cpu_child_rows(
        "benchmarks.overlap", ["--sharded-child", steps, n_rows, batch, period],
        SHARDED_DEVICES, "overlap_sharded/")


def run(steps: int = 240, n_rows: int = 4096, batch: int = 32,
        period: int = 4, repeats: int = 2, sharded_steps: int = 60,
        sharded_rows: int = 16384, sharded_batch: int = 512):
    best = {}
    for name, mode, pipelined in (("none", "none", True),
                                  ("blocking", "vilamb", False),
                                  ("pipelined", "vilamb", True)):
        runs = [_measure(mode, pipelined, steps, n_rows, batch, period)
                for _ in range(repeats)]
        best[name] = min(runs, key=lambda x: x[0])   # least-noise run
    n, b, p = best["none"], best["blocking"], best["pipelined"]
    # Floor both stalls at the timer/scheduler noise level so a lucky run
    # where the pipelined mean dips below the baseline cannot report an
    # unbounded (meaningless) reduction.  The headline ratio uses the
    # MEANS — the statistic the tick_stall_* value column prints — and
    # the derived string quotes the p99-based ratio next to it.
    noise_us = 5.0
    ratio = max(b[0] - n[0], noise_us) / max(p[0] - n[0], noise_us)
    ratio99 = max(b[1] - n[1], noise_us) / max(p[1] - n[1], noise_us)
    return [
        ("overlap/tick_stall_none", n[0],
         f"mean {n[0]:.0f} / p99 {n[1]:.0f} us (baseline)"),
        ("overlap/tick_stall_blocking", b[0],
         f"mean {b[0]:.0f} / p99 {b[1]:.0f} us; queue_fits round trip "
         "each due tick"),
        ("overlap/tick_stall_pipelined", p[0],
         f"mean {p[0]:.0f} / p99 {p[1]:.0f} us; sync-free speculative "
         "dispatch"),
        ("overlap/overhead_reduction", 0.0,
         f"{ratio:.2f}x foreground stall cut from means "
         f"(p99-based {ratio99:.2f}x; bar: >= 2x)"),
        ("overlap/endtoend_none", n[2], "wall us/step"),
        ("overlap/endtoend_blocking", b[2],
         "wall us/step (device-bound on shared-CPU container)"),
        ("overlap/endtoend_pipelined", p[2],
         "wall us/step (identical device work by construction)"),
    ] + _sharded_rows(sharded_steps, sharded_rows, sharded_batch, period)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded-child":
        sharded_child(*map(int, sys.argv[2:6]))
    else:
        from .common import emit
        emit(run())
