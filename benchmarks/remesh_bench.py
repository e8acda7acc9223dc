"""Elastic remesh + degraded-mode reads: what elasticity costs.

The remesh (repro.remesh) re-stripes every protected leaf onto a grown or
shrunk mesh over bounded per-tick migration windows — the foreground never
stops.  Degraded reads (``ProtectedStore.read_verified``) trade a
verification/reconstruction pass for never returning stale bytes.  Rows:

  * ``remesh/migrate_ticks`` (multi-device child) — ticks to migrate a
    store across a 4 -> 8 device grow at the configured
    ``remesh_bytes_per_tick`` budget (the pinned bound is
    ``ceil(moved_blocks / window)``).
  * ``remesh/throughput`` — MB/s re-striped while the foreground kept
    writing into migrating blocks.
  * ``remesh/stall`` — foreground step wall during vs before the
    migration: the bounded per-tick stall the budget buys.
  * ``remesh/degraded_read`` — wall per ``read_verified`` call on clean
    blocks (the verify-before-return floor).
  * ``remesh/degraded_read_recon`` — wall per call when the block must be
    parity-reconstructed first (the degraded path proper).

The multi-device leg runs in a subprocess (``--sharded-child``) because
``XLA_FLAGS=--xla_force_host_platform_device_count`` must be exported
before jax is imported — same protocol as benchmarks/scrub_bench.py.
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


def _measure_degraded_read(n_rows: int, iters: int):
    from repro.faults.inject import FaultSpec, apply_fault
    r = Region(n_rows=n_rows, mode="vilamb", period=4)
    heap, red = r.heap, r.red
    red = r.store.flush({"heap": heap}, red)
    blocks = list(range(0, min(8, n_rows)))
    r.store.read_verified({"heap": heap}, red, "heap", blocks)   # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        r.store.read_verified({"heap": heap}, red, "heap", blocks)
    clean_us = (time.perf_counter() - t0) / iters * 1e6
    # Corrupt one block per probed stripe: every call reconstructs.
    lv, red2 = {"heap": heap}, red
    lv, red2 = apply_fault(r.store.metas, lv, red2, FaultSpec(
        "data_bitflip", "heap", block=0, lane=3, bit=5))
    t0 = time.perf_counter()
    for _ in range(iters):
        r.store.read_verified(lv, red2, "heap", [0])
    recon_us = (time.perf_counter() - t0) / iters * 1e6
    return clean_us, recon_us, len(blocks)


def sharded_child(steps: int, n_rows: int, batch: int, period: int) -> None:
    """Child entry: grow-migration rows (stdout CSV is the protocol)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.launch.mesh import make_mesh

    old = make_mesh((1, 2, 2), ("pod", "data", "model"))
    new = make_mesh((2, 2, 2), ("pod", "data", "model"))
    spec = P(("pod", "data", "model"), None)
    budget_blocks = max(8, n_rows // 16)
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=period, lanes_per_block=1024,
        stripe_data_blocks=4, work_queue_frac=0.0, precompile=False,
        remesh_bytes_per_tick=budget_blocks * ROW_BYTES)
    heap = jnp.zeros((n_rows, ROW_ELEMS), jnp.float32)
    store = ProtectedStore(pol, mesh=old).attach(
        {"heap": heap}, specs={"heap": spec})
    heap = jax.device_put(heap, NamedSharding(old, spec))
    red = store.init({"heap": heap})
    batch = min(batch, n_rows // 8)
    keys = key_stream("uniform", 4 * steps + 8, batch, n_rows)
    vals = jnp.ones((batch, ROW_ELEMS), jnp.float32)

    def write(heap, red, rows):
        heap = heap.at[rows].set(vals)
        mask = jnp.zeros((n_rows,), bool).at[rows].set(True)
        return heap, store.on_write(red, events={"heap": mask})

    step = 0
    for i in range(4):   # warm the programs
        heap, red = write(heap, red, keys[i])
        red, _ = store.tick({"heap": heap}, red, step); step += 1
    red = store.flush({"heap": heap}, red, step)

    # Baseline foreground wall per step on the old mesh.
    jax.block_until_ready(heap)
    t0 = time.perf_counter()
    for i in range(steps):
        heap, red = write(heap, red, keys[4 + i])
        red, rep = store.tick({"heap": heap}, red, step); step += 1
    jax.block_until_ready(heap)
    before_us = (time.perf_counter() - t0) / steps * 1e6

    # Grow 4 -> 8 while the foreground keeps writing into migrating rows.
    store.remesh(new)
    status = None
    t0 = time.perf_counter()
    i = 0
    while store.remeshing and i < 8 * steps:
        heap, red = write(heap, red, keys[4 + steps + i])
        red, rep = store.tick({"heap": heap}, red, step); step += 1
        if rep.remesh is not None:
            status = rep.remesh
        if rep.repaired:
            heap = rep.repaired.get("heap", heap)
        i += 1
    jax.block_until_ready(heap)
    during_us = (time.perf_counter() - t0) / max(i, 1) * 1e6
    if status is None or not status.done:
        raise RuntimeError("migration did not finish in budget")
    moved_bytes = n_rows * ROW_BYTES
    wall_s = during_us * 1e-6 * i
    mb_s = moved_bytes / max(wall_s, 1e-9) / 1e6
    stall = during_us / max(before_us, 1e-9)
    for name, us, derived in (
            ("remesh/migrate_ticks", 0.0,
             f"{status.ticks} ticks to re-stripe {moved_bytes >> 10} KiB "
             f"across a 4 -> {SHARDED_DEVICES} device grow "
             f"(window {budget_blocks} blocks/tick)"),
            ("remesh/throughput", during_us,
             f"{mb_s:.2f} MB/s re-striped while the foreground wrote "
             "into migrating blocks"),
            ("remesh/stall", 0.0,
             f"{stall:.2f}x foreground step wall during migration "
             f"(before {before_us:.0f} us -> during {during_us:.0f} us)")):
        print(f"{name},{us:.2f},{derived}")


def _sharded_rows(steps: int, n_rows: int, batch: int, period: int):
    return cpu_child_rows(
        "benchmarks.remesh_bench", ["--sharded-child", steps, n_rows, batch, period],
        SHARDED_DEVICES, "remesh/")


def run(steps: int = 24, n_rows: int = 2048, batch: int = 32,
        period: int = 4, read_iters: int = 20, sharded_steps: int = 16,
        sharded_rows: int = 256):
    clean_us, recon_us, nb = _measure_degraded_read(
        min(n_rows, 512), read_iters)
    rows = [
        ("remesh/degraded_read", clean_us,
         f"verified read of {nb} clean 4 KiB blocks (wall us/call)"),
        ("remesh/degraded_read_recon", recon_us,
         "verified read with parity reconstruction of 1 corrupt block"),
    ]
    return rows + _sharded_rows(sharded_steps, sharded_rows, batch, period)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded-child":
        sharded_child(*map(int, sys.argv[2:6]))
    else:
        from .common import emit
        emit(run())
