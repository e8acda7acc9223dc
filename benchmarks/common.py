"""Benchmark substrate: a DAX-NVM-region analogue with 4 KB pages.

A "heap" of ``n_rows`` rows of 1024 fp32 elements — each row is exactly one
4 KiB block (the paper's page size; lanes_per_block=1024) — protected by a
:class:`repro.core.ProtectedStore`.  The store owns the redundancy
lifecycle: ``on_write`` records each write batch (dirty marks for vilamb,
the sparse row-diff for sync/Pangolin), ``tick`` applies the periodic
Algorithm-1 schedule.  Insert/overwrite/remove/read ops mirror the paper's
PMDK/fio workloads; sync costs O(touched rows) via the diff identities,
Vilamb amortizes over the update period.

Relative throughputs reproduce the paper's claims; absolute numbers are CPU.
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.core import ProtectedStore, RedundancyPolicy

ROW_ELEMS = 1024          # 4 KiB fp32 rows == paper pages
LANES_PER_BLOCK = 1024    # one block per row
STRIPE = 4


@dataclasses.dataclass
class Region:
    n_rows: int = 4096
    mode: str = "none"                    # none | sync | vilamb
    period: int = 16                      # redundancy period (steps)
    # Overlap-pipelined tick (the library default).  The wall-throughput
    # benches construct blocking Regions: on this repo's shared-CPU
    # container, keeping the previous epoch's redundancy arrays alive for
    # the overlap costs defensive copies that a serial device cannot hide,
    # so raw wall numbers stay comparable with the blocking-tick baseline
    # artifact.  benchmarks/overlap.py measures the pipelined path
    # explicitly (foreground stall + end-to-end).
    pipelined: bool = False
    # Scrub patroller byte budget (0 = disabled); benchmarks/scrub_bench.py
    # and the patrolled MTTDL rows size this to hit a target sweep length.
    patrol_bytes_per_tick: int = 0

    def __post_init__(self):
        self.heap = jnp.zeros((self.n_rows, ROW_ELEMS), jnp.float32)
        policy = RedundancyPolicy.single(
            self.mode, period_steps=self.period,
            lanes_per_block=LANES_PER_BLOCK, stripe_data_blocks=STRIPE,
            async_tick=self.pipelined,
            patrol_bytes_per_tick=self.patrol_bytes_per_tick)
        self.store = ProtectedStore(policy).attach({"heap": self.heap})
        self.red = self.store.init({"heap": self.heap})
        self.meta = self.store.metas["heap"]
        # Back-compat surface for sibling benchmark modules.
        self.engine = self.store.engine_for("heap")
        self._build()

    def _build(self):
        store = self.store
        n_rows = self.n_rows

        def write(heap, red, rows, vals):
            old = heap[rows]
            heap = heap.at[rows].set(vals)
            mask = jnp.zeros((n_rows,), bool).at[rows].set(True)
            red = store.on_write(red, events={"heap": mask},
                                 row_diffs={"heap": (rows, old, vals)})
            return heap, red

        self.write = jax.jit(write, donate_argnums=(0, 1))
        self.read = jax.jit(lambda heap, rows: heap[rows])
        if store.protects:
            self.red_step = jax.jit(
                lambda heap, red: store.redundancy_step({"heap": heap}, red),
                donate_argnums=(1,))

    def run_writes(self, key_batches, vals, think_s: float = 0.0) -> float:
        """Timed loop; returns wall seconds. The store's tick applies the
        Vilamb periodicity (no-op for sync/none policies).  ``think_s``
        inserts closed-loop per-batch think time (fio ``thinktime``)."""
        heap, red = self.heap, self.red
        # warmup compile (write step + the periodic pass)
        heap, red = self.write(heap, red, key_batches[0], vals)
        if self.store.has_periodic:
            red = self.store.flush({"heap": heap}, red)
        jax.block_until_ready(heap)
        think = float(think_s)
        t0 = time.perf_counter()
        for i, rows in enumerate(key_batches[1:], 1):
            heap, red = self.write(heap, red, rows, vals)
            red, _ = self.store.tick({"heap": heap}, red, i)
            if think > 0.0:
                # Closed-loop think time (fio ``thinktime`` analogue): the
                # app core works between ops while the device core absorbs
                # whatever the tick dispatched.  Busy wait — time.sleep has
                # multi-ms granularity on this kernel.
                end = time.perf_counter() + think
                while time.perf_counter() < end:
                    pass
        # Fairness: the pipelined tick defers adoption, so settle and drain
        # every dispatched update inside the timed window.
        red = self.store.settle(red, {"heap": heap})
        jax.block_until_ready((heap, jax.tree.leaves(red)))
        dt = time.perf_counter() - t0
        self.heap, self.red = heap, red
        return dt

    def vulnerable_stripes(self) -> int:
        if not self.red:
            return 0
        return int(self.store.dirty_stats(self.red)["heap"]["vulnerable_stripes"])


def key_stream(pattern: str, steps: int, batch: int, n_rows: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    for s in range(steps):
        if pattern == "seq":
            base = (s * batch) % n_rows
            rows = (base + np.arange(batch)) % n_rows
        elif pattern == "zipf":
            z = rng.zipf(1.3, size=batch)
            rows = ((z - 1) % n_rows)
        else:  # uniform
            rows = rng.integers(0, n_rows, size=batch)
        # dedupe within a batch (scatter rules), keep batch size stable
        rows = np.unique(rows)
        if len(rows) < batch:
            fill = np.setdiff1d(np.arange(n_rows), rows)[: batch - len(rows)]
            rows = np.concatenate([rows, fill])
        out.append(jnp.asarray(np.sort(rows[:batch]).astype(np.int32)))
    return out


def cpu_child_rows(module: str, args: Sequence, n_devices: int,
                   prefix: str) -> List[Tuple[str, float, str]]:
    """Run ``python -m module *args`` on ``n_devices`` virtual CPU devices
    and parse the ``name,us,derived`` rows it prints under ``prefix``.

    The child is pinned to the CPU: this process may already hold the
    accelerator, and a chip belongs to one process at a time.  The device
    count must be set before jax is imported, hence the subprocess.  A
    child that fails, or prints no rows, raises.
    """
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
        PYTHONPATH=os.path.join(root, "src") + os.pathsep
        + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", module, *map(str, args)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=root)
    if r.returncode != 0:
        raise RuntimeError(f"{module} child exited {r.returncode}: "
                           f"{r.stderr.strip()[-2000:]}")
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith(prefix):
            name, us, derived = line.split(",", 2)
            rows.append((name, float(us), derived))
    if not rows:
        raise RuntimeError(f"{module} child printed no {prefix}* rows")
    return rows


def emit(rows):
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
