"""Scrub patroller + online shard rebuild: what the background duty costs.

The patroller (repro.scrub) trades a per-tick byte budget for detection
latency; the online rebuild trades a bounded per-tick window for a
foreground that never stops.  Rows:

  * ``scrub/patrol_tick_off`` / ``scrub/patrol_tick_on`` — mean wall per
    step of a write+tick loop with the patroller disabled vs enabled
    (same traffic), the patrol's foreground overhead.
  * ``scrub/patrol_coverage`` — ticks per full sweep at the configured
    budget (detection-latency upper bound, in ticks).
  * ``scrub/rebuild_*`` (multi-device child) — wholesale shard loss on a
    2x2x2 mesh-sharded store: ticks + wall to rebuild the shard from
    cross-shard parity while the foreground keeps writing, plus the
    foreground's per-step wall during vs before the rebuild (the measured
    stall the ``rebuild_bytes_per_tick`` budget bounds).

The multi-device leg runs in a subprocess (``--sharded-child``) because
``XLA_FLAGS=--xla_force_host_platform_device_count`` must be exported
before jax is imported — same protocol as benchmarks/overlap.py.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import ROW_ELEMS, Region, cpu_child_rows, key_stream

SHARDED_DEVICES = 8
ROW_BYTES = ROW_ELEMS * 4


def _measure_patrol(patrol_rows_per_tick: int, steps: int, n_rows: int,
                    batch: int, period: int):
    r = Region(n_rows=n_rows, mode="vilamb", period=period,
               patrol_bytes_per_tick=patrol_rows_per_tick * ROW_BYTES)
    keys = key_stream("uniform", steps + 1, batch, n_rows)
    vals = jnp.ones((batch, ROW_ELEMS), jnp.float32)
    heap, red = r.heap, r.red
    heap, red = r.write(heap, red, keys[0], vals)
    red = r.store.flush({"heap": heap}, red)
    jax.block_until_ready(heap)
    t0 = time.perf_counter()
    for i in range(1, steps + 1):
        heap, red = r.write(heap, red, keys[i], vals)
        red, rep = r.store.tick({"heap": heap}, red, i, scrub_period=0)
        if rep.repaired:
            heap = rep.repaired.get("heap", heap)
    red = r.store.settle(red, {"heap": heap})
    jax.block_until_ready((heap, jax.tree.leaves(red)))
    wall_us = (time.perf_counter() - t0) / steps * 1e6
    pat = r.store.patroller
    swept = pat.sweeps["heap"] if pat is not None else 0
    return wall_us, swept


def sharded_child(steps: int, n_rows: int, batch: int, period: int) -> None:
    """Child entry: shard-loss rebuild rows (stdout CSV is the protocol)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.faults.inject import FaultSpec
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    spec = P(("pod", "data", "model"), None)
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=period, lanes_per_block=1024,
        stripe_data_blocks=4, work_queue_frac=0.0,
        patrol_bytes_per_tick=(n_rows // 8) * ROW_BYTES,
        precompile=False)
    heap = jnp.zeros((n_rows, ROW_ELEMS), jnp.float32)
    store = ProtectedStore(pol, mesh=mesh).attach(
        {"heap": heap}, specs={"heap": spec})
    heap = jax.device_put(heap, NamedSharding(mesh, spec))
    red = store.init({"heap": heap})
    k = store.shard_factor("heap")
    rows_local = n_rows // k
    batch = min(batch, rows_local)   # key_stream can't exceed the key space
    keys = key_stream("uniform", 2 * steps + 2, batch, rows_local)
    vals = jnp.ones((batch, ROW_ELEMS), jnp.float32)

    def write(heap, red, rows):
        heap = heap.at[rows].set(vals)
        mask = jnp.zeros((n_rows,), bool).at[rows].set(True)
        return heap, store.on_write(red, events={"heap": mask})

    step = 0
    # Warm + settle, then sweep until cross-shard parity covers the heap.
    for i in range(4):
        heap, red = write(heap, red, keys[i])
        red, _ = store.tick({"heap": heap}, red, step); step += 1
    red = store.flush({"heap": heap}, red, step)
    pat = store.patroller

    def covered() -> bool:
        # Probes racing live writes fail xpar adoption, so sweep counts
        # under-promise; full xvalid is the real rebuild precondition.
        xp = pat.xpar.get("heap")
        return xp is not None and bool(xp.xvalid.all())

    for _ in range(64):
        red, _ = store.tick({"heap": heap}, red, step); step += 1
        if covered():
            break

    # Baseline foreground wall per step (writes into the soon-lost shard).
    lost = 2
    base = jnp.asarray(np.arange(lost * rows_local, (lost + 1) * rows_local))
    def lost_rows(i):
        return base[np.asarray(keys[i]) % rows_local]
    jax.block_until_ready(heap)
    t0 = time.perf_counter()
    for i in range(steps):
        heap, red = write(heap, red, lost_rows(i))
        red, rep = store.tick({"heap": heap}, red, step); step += 1
    jax.block_until_ready(heap)
    before_us = (time.perf_counter() - t0) / steps * 1e6
    red = store.flush({"heap": heap}, red, step)
    for _ in range(64):
        red, _ = store.tick({"heap": heap}, red, step); step += 1
        if covered():
            break

    # Lose a shard wholesale; keep writing into it while it rebuilds.
    lv, red = store.inject({"heap": heap}, red, FaultSpec(
        kind="shard_loss", leaf="heap", block=lost))
    heap = lv["heap"]
    store.declare_shard_lost("heap", lost, red)
    rebuild_ticks = None
    t0 = time.perf_counter()
    i = 0
    while rebuild_ticks is None and i < 4 * steps:
        heap, red = write(heap, red, lost_rows(steps + i))
        red, rep = store.tick({"heap": heap}, red, step); step += 1
        if rep.repaired:
            heap = rep.repaired.get("heap", heap)
        if rep.rebuild is not None and rep.rebuild.done:
            rebuild_ticks = rep.rebuild.ticks
        i += 1
    jax.block_until_ready(heap)
    during_us = (time.perf_counter() - t0) / max(i, 1) * 1e6
    shard_bytes = rows_local * ROW_BYTES
    if rebuild_ticks is None:
        raise RuntimeError("rebuild did not finish in budget")
    wall_s = during_us * 1e-6 * i
    mb_s = shard_bytes / max(wall_s, 1e-9) / 1e6
    stall = during_us / max(before_us, 1e-9)
    for name, us, derived in (
            ("scrub/rebuild_ticks", 0.0,
             f"{rebuild_ticks} ticks to rebuild {shard_bytes >> 10} KiB "
             f"shard ({SHARDED_DEVICES} host devices)"),
            ("scrub/rebuild_throughput", during_us,
             f"{mb_s:.2f} MB/s reconstructed while foreground wrote "
             "into the lost shard"),
            ("scrub/rebuild_stall", 0.0,
             f"{stall:.2f}x foreground step wall during rebuild "
             f"(before {before_us:.0f} us -> during {during_us:.0f} us)")):
        print(f"{name},{us:.2f},{derived}")


def _sharded_rows(steps: int, n_rows: int, batch: int, period: int):
    return cpu_child_rows(
        "benchmarks.scrub_bench", ["--sharded-child", steps, n_rows, batch, period],
        SHARDED_DEVICES, "scrub/")


def run(steps: int = 96, n_rows: int = 2048, batch: int = 32,
        period: int = 4, sweep_ticks: int = 16, sharded_steps: int = 24,
        sharded_rows: int = 256):
    off, _ = _measure_patrol(0, steps, n_rows, batch, period)
    budget_rows = max(1, n_rows // sweep_ticks)
    on, swept = _measure_patrol(budget_rows, steps, n_rows, batch, period)
    overhead = (on - off) / max(off, 1e-9) * 100.0
    rows = [
        ("scrub/patrol_tick_off", off, "wall us/step, patroller disabled"),
        ("scrub/patrol_tick_on", on,
         f"wall us/step at {budget_rows * ROW_BYTES >> 10} KiB/tick budget "
         f"({overhead:+.1f}% vs off)"),
        ("scrub/patrol_coverage", 0.0,
         f"{swept} full sweeps in {steps} ticks "
         f"(target sweep {sweep_ticks} ticks; latency bound = one sweep)"),
    ]
    return rows + _sharded_rows(sharded_steps, sharded_rows, batch, period)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded-child":
        sharded_child(*map(int, sys.argv[2:6]))
    else:
        from .common import emit
        emit(run())
