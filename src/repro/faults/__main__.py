"""CI fault-injection battery:  ``python -m repro.faults [--smoke]``.

Five passes, each seeded and fully deterministic:

1. **Crash sweep** — enumerate every lifecycle phase the pipelined tick
   fires (speculative dispatch, coalesce/mid-flight, lazy adoption,
   forced resolve, blocking update, scrub, flush, …) and crash+restart at
   each one; every outcome must be bitwise-recoverable.
2. **Crash + corruption** — at a mid-flight crash point, corrupt one
   block outside the vulnerability window (must be parity-repaired on
   restore) and one inside it (loss must be provably within the window).
3. **Oracle** — scrub over injected single-stripe corruptions must detect
   100% outside the window with zero false positives, across >= 3 seeds.
4. **Patroller** — a bitflip injected into a settled store must be found
   by the background scrub patroller (repro.scrub, no scheduled scrub)
   within one sweep of quiet ticks, parity-repaired bitwise, and leave a
   clean store.
5. **Sharded** — the same oracle + a crash-point subset on a 2x2x2
   mesh-sharded store (8 forced host devices, spawned as a subprocess so
   ``XLA_FLAGS`` lands before the jax import): faults placed through
   global block geometry on non-zero shards must be detected by the
   owning shard's scrub, and mid-pipeline crashes must recover bitwise —
   plus a wholesale shard-loss case whose online rebuild from cross-shard
   parity must restore the lost shard bitwise while the store keeps
   ticking.

Exit status 1 on any violation, so ``scripts/ci.sh`` fails the build.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ProtectedStore, RedundancyPolicy

from .crashpoints import CrashPlan, CrashPointMachine
from .inject import FaultInjector, FaultSpec
from .oracle import check_detection, vulnerability_window

# The pipeline phases a sweep must prove crash-safe (acceptance
# criterion: speculative dispatch, mid-flight, lazy adoption, forced
# resolve — plus the classic flush/scrub/write points).  PR10 adds the
# off-thread dispatcher edges: the batch enqueue before the launch thread
# runs, and the join barrier right before a forced resolve.
REQUIRED_PHASES = ("dispatch", "coalesce", "adopt", "adopt_forced",
                   "dispatcher_enqueue", "dispatcher_join",
                   "on_write", "tick", "flush")


def _make_leaves():
    return {
        "w": jax.random.normal(jax.random.PRNGKey(0), (24, 200), jnp.float32),
        "e": jax.random.normal(jax.random.PRNGKey(1), (16, 64), jnp.bfloat16),
    }


def _make_store():
    # period 2 + a deadline of 3 + a scrub at step 5 exercises speculative
    # dispatch (step 2), coalescing while held in flight (step 4),
    # deadline+scrub-forced resolve (step 5) and lazy adoption (step 6).
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=2, max_vulnerable_steps=3,
        lanes_per_block=128, work_queue_frac=0.5, async_tick=True,
        precompile=False)
    return ProtectedStore(pol).attach(_make_leaves())


def crash_sweep(seed: int, steps: int, tmp: str) -> int:
    machine = CrashPointMachine(
        _make_store, _make_leaves, tmp, seed=seed, steps=steps,
        scrub_every=5, hold_inflight_steps=(3, 4))
    outcomes = machine.sweep(require_phases=REQUIRED_PHASES)
    bad = [o for o in outcomes if not o.ok]
    byc = {}
    for o in outcomes:
        byc[o.classification] = byc.get(o.classification, 0) + 1
    print(f"  crash sweep seed={seed}: {len(outcomes)} crash points, "
          f"outcomes={byc}")
    for o in bad:
        print(f"    FAIL {o.plan.phase}#{o.plan.occurrence} step={o.step}: "
              f"{o.classification} diverged={o.diverged} "
              f"scrub_after={o.scrub_after_flush}")
    return len(bad)


def crash_with_corruption(seed: int, steps: int, tmp: str) -> int:
    """Corrupt the persisted state at a mid-flight crash: outside-window
    blocks must repair, inside-window blocks must be provably in-window."""
    machine = CrashPointMachine(
        _make_store, _make_leaves, f"{tmp}/fx", seed=seed, steps=steps,
        scrub_every=0, hold_inflight_steps=(3, 4))
    fired = machine.enumerate_phases()
    plans = [CrashPlan(p, o) for p, o in fired if p == "dispatch"]
    if not plans:
        print("  crash+corruption: no dispatch phase fired (workload bug)")
        return 1
    plan = plans[-1]
    probe = machine.run_crash(plan)            # learn the window at the crash
    fails = 0
    meta = machine._probe().protected_metas["w"]
    window_w = probe.window.get("w", set())
    clean = [b for b in range(meta.n_blocks)
             if b not in window_w
             and not any((b // meta.stripe_data_blocks)
                         == (v // meta.stripe_data_blocks)
                         for v in window_w)]
    if clean:
        out = machine.run_crash(plan, faults=(
            FaultSpec(kind="data_bitflip", leaf="w", block=clean[0],
                      lane=3, bit=7),))
        ok = out.classification == "recovered_bitwise"
        print(f"  crash+corruption outside window @{plan.phase}: "
              f"{out.classification} {'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    if window_w:
        b = sorted(window_w)[0]
        out = machine.run_crash(plan, faults=(
            FaultSpec(kind="data_bitflip", leaf="w", block=b, lane=3,
                      bit=7),))
        ok = out.ok
        print(f"  crash+corruption inside window @{plan.phase}: "
              f"{out.classification} {'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def oracle_pass(seed: int, steps: int) -> int:
    store = _make_store()
    leaves = _make_leaves()
    inj = FaultInjector(store, seed=seed)
    rng = np.random.default_rng(seed)
    red = store.init(leaves)
    for step in range(1, steps + 1):
        rows = rng.choice(24, size=int(rng.integers(1, 4)), replace=False)
        idx = jnp.asarray(np.sort(rows))
        leaves = dict(leaves, w=leaves["w"].at[idx].add(0.5))
        ev = jnp.zeros((24,), bool).at[idx].set(True)
        red = store.on_write(red, events={"w": ev})
        red, _ = store.tick(leaves, red, step)
    # single-stripe corruptions outside the live window: all must detect
    specs = inj.plan_clean_blocks(red, n=5, kinds=("data_bitflip",
                                                   "stale_redundancy"))
    window = vulnerability_window(store, red)
    leaves2, red2 = inj.inject_many(leaves, red, specs)
    report = check_detection(store, leaves2, red2, specs, window=window)
    ok = report.ok and sum(len(v) for v in report.expected.values()) == len(
        {(s.leaf, b) for s in specs for b in s.touched_blocks})
    print(f"  oracle seed={seed}: {report.summary()} "
          f"{'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


def patrol_pass(seed: int, steps: int) -> int:
    """Patroller detection leg: an injected bitflip on a settled store must
    be found by the background patrol (no scheduled scrub) within one
    sweep-ish of quiet ticks, repaired bitwise, and leave the store clean."""
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=2, lanes_per_block=128, async_tick=True,
        patrol_bytes_per_tick=8 * 128 * 4, precompile=False)
    leaves = _make_leaves()
    store = ProtectedStore(pol).attach(leaves)
    rng = np.random.default_rng(seed)
    red = store.init(leaves)
    for step in range(1, steps + 1):
        rows = rng.choice(24, size=int(rng.integers(1, 4)), replace=False)
        idx = jnp.asarray(np.sort(rows))
        leaves = dict(leaves, w=leaves["w"].at[idx].add(0.5))
        ev = jnp.zeros((24,), bool).at[idx].set(True)
        red = store.on_write(red, events={"w": ev})
        red, _ = store.tick(leaves, red, step)
    red = store.flush(leaves, red, steps + 1)      # settle: V -> 0
    expected = {n: np.array(np.asarray(v)) for n, v in leaves.items()}
    blk = 5 + seed
    leaves, red = store.inject(leaves, red, FaultSpec(
        kind="data_bitflip", leaf="w", block=blk, lane=3, bit=7))
    step = steps + 2
    store.patroller.expect_injection("w", blk, step)
    # Latency bound: round-robin over both leaves, probe processed one
    # tick after dispatch -> ~2 ticks per window, plus repair pacing.
    # Probes only dispatch on quiet ticks and a probe result may take an
    # extra tick to land, so the exact latency jitters with dispatch/
    # resolver timing — budget two full sweeps plus slack, not one.
    nb = sum(store.protected_metas[n].n_blocks for n in ("w", "e"))
    budget = 4 * (nb // 8 + 2) + 16
    detected = repaired = False
    for _ in range(budget):
        red, rep = store.tick(leaves, red, step, scrub_period=0)
        step += 1
        if rep.repaired:
            leaves = dict(leaves, **rep.repaired)
            repaired = True
        if store.patroller.latencies:
            detected = True
        if detected and repaired:
            break
    clean = store.scrub_check(leaves, red) == 0
    bitwise = all(np.array_equal(np.asarray(leaves[n]).view(np.uint8),
                                 expected[n].view(np.uint8))
                  for n in expected)
    pat = store.patroller
    lat = pat.latency_stats(step_seconds=1.0)
    ok = detected and repaired and clean and bitwise
    diag = ("" if ok else
            f" [budget={budget} starved={pat.starved_ticks} "
            f"sweeps={dict(pat.sweeps)} scanned={store.counters['patrol.blocks_scanned']} "
            f"probe_out={pat._probe is not None}]")
    print(f"  patrol seed={seed}: detected={detected} (latency "
          f"{lat['mean_s']:.0f} ticks) repaired={repaired} clean={clean} "
          f"bitwise={bitwise} {'OK' if ok else 'FAIL'}{diag}")
    return 0 if ok else 1


def sharded_child(seed: int, steps: int) -> int:
    """Runs inside the 8-device subprocess: sharded oracle + crash subset."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    specs = {"w": P(("pod", "data", "model"), None)}

    def make_leaves():
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 2048), jnp.float32)
        return {"w": jax.device_put(w, NamedSharding(mesh, specs["w"]))}

    def make_store():
        # precompile=False: crash replays restore *unsharded* host arrays,
        # which the sharding-pinned AOT executables would reject.
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=2, max_vulnerable_steps=3,
            lanes_per_block=128, work_queue_frac=0.5, async_tick=True,
            precompile=False)
        return ProtectedStore(pol, mesh=mesh).attach(make_leaves(),
                                                     specs=specs)

    fails = 0
    # -- oracle over global block geometry (multiple shards must be hit) --
    store = make_store()
    leaves = make_leaves()
    inj = FaultInjector(store, seed=seed)
    rng = np.random.default_rng(seed)
    red = store.init(leaves)
    for step in range(1, steps + 1):
        rows = rng.choice(64, size=int(rng.integers(1, 4)), replace=False)
        idx = jnp.asarray(np.sort(rows))
        leaves = dict(leaves, w=leaves["w"].at[idx].add(0.5))
        ev = jnp.zeros((64,), bool).at[idx].set(True)
        red = store.on_write(red, events={"w": ev})
        red, _ = store.tick(leaves, red, step)
    spec_list = inj.plan_clean_blocks(red, n=6, kinds=("data_bitflip",
                                                      "stale_redundancy"))
    nb = store.protected_metas["w"].n_blocks
    shards_hit = {s.block // nb for s in spec_list}
    window = vulnerability_window(store, red)
    leaves2, red2 = inj.inject_many(leaves, red, spec_list)
    report = check_detection(store, leaves2, red2, spec_list, window=window)
    ok = report.ok and len(shards_hit) > 1
    print(f"  sharded oracle seed={seed}: {report.summary()} "
          f"shards_hit={sorted(shards_hit)} {'OK' if ok else 'FAIL'}")
    fails += 0 if ok else 1
    # -- crash-point subset on the sharded overlap pipeline --
    with tempfile.TemporaryDirectory() as tmp:
        machine = CrashPointMachine(
            make_store, make_leaves, tmp, seed=seed, steps=steps,
            scrub_every=5, hold_inflight_steps=(3, 4))
        fired = machine.enumerate_phases()
        plans = []
        for ph in ("dispatch", "coalesce", "adopt", "adopt_forced",
                   "dispatcher_enqueue", "dispatcher_join", "flush"):
            occ = [o for p, o in fired if p == ph]
            if occ:
                plans.append(CrashPlan(ph, occ[-1]))
        for plan in plans:
            out = machine.run_crash(plan)
            print(f"  sharded crash @{plan.phase}#{plan.occurrence}: "
                  f"{out.classification} {'OK' if out.ok else 'FAIL'}")
            fails += 0 if out.ok else 1
    # -- wholesale shard loss: online rebuild from cross-shard parity --
    fails += sharded_rebuild_case(seed, steps, mesh, specs)
    return fails


def sharded_rebuild_case(seed, steps, mesh, specs) -> int:
    """One shard wiped wholesale must rebuild bitwise from the patroller's
    cross-shard parity while the store keeps ticking (no restore)."""
    from jax.sharding import NamedSharding

    pol = RedundancyPolicy.single(
        "vilamb", period_steps=2, lanes_per_block=128, async_tick=True,
        patrol_bytes_per_tick=32 * 128 * 4, precompile=False)
    w = jax.random.normal(jax.random.PRNGKey(seed), (64, 2048), jnp.float32)
    leaves = {"w": jax.device_put(w, NamedSharding(mesh, specs["w"]))}
    store = ProtectedStore(pol, mesh=mesh).attach(leaves,
                                                  specs={"w": specs["w"]})
    red = store.init(leaves)
    rng = np.random.default_rng(seed)
    step = 0
    for _ in range(3):
        rows = rng.choice(64, size=4, replace=False)
        idx = jnp.asarray(np.sort(rows))
        leaves = dict(leaves, w=leaves["w"].at[idx].add(0.5))
        ev = jnp.zeros((64,), bool).at[idx].set(True)
        red = store.on_write(red, events={"w": ev})
        red, _ = store.tick(leaves, red, step)
        step += 1
    red = store.flush(leaves, red, step)
    pat = store.patroller
    for _ in range(48):          # quiet sweeps until xpar covers the leaf
        red, _ = store.tick(leaves, red, step, scrub_period=0)
        step += 1
        xp = pat.xpar.get("w")
        # Probes racing the warm writes fail adoption (their slabs saw
        # live rows), so sweep counts under-promise: wait for coverage.
        if xp is not None and bool(xp.xvalid.all()):
            break
    else:
        print(f"  sharded shard-loss rebuild seed={seed}: xpar never "
              "covered the leaf FAIL")
        return 1
    expected = np.array(np.asarray(leaves["w"]))
    lost = 3
    leaves, red = store.inject(leaves, red, FaultSpec(
        kind="shard_loss", leaf="w", block=lost))
    store.declare_shard_lost("w", lost, red)
    status = None
    for _ in range(32):
        red, rep = store.tick(leaves, red, step, scrub_period=0)
        step += 1
        if rep.repaired:
            leaves = dict(leaves, **rep.repaired)
        if rep.rebuild is not None and rep.rebuild.done:
            status = rep.rebuild
            break
    red = store.flush(leaves, red, step)
    clean = store.scrub_check(leaves, red) == 0
    bitwise = np.array_equal(np.asarray(leaves["w"]), expected)
    ok = (status is not None and status.lost == 0 and clean and bitwise)
    print(f"  sharded shard-loss rebuild seed={seed}: "
          f"status={status} clean={clean} bitwise={bitwise} "
          f"{'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


def _cpu_child(args, label: str) -> int:
    """Re-exec this module under 8 forced host CPU devices; 1 on failure.

    ``XLA_FLAGS`` must be set before jax is imported, so the module is
    re-executed rather than re-configuring the initialized backend.  The
    child is pinned to the CPU: this process may hold the accelerator, and
    a chip belongs to one process at a time.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    try:
        r = subprocess.run([sys.executable, "-m", "repro.faults", *args],
                           env=env, capture_output=True, text=True,
                           timeout=1800)
    except Exception as e:   # timeout/OSError: count it, keep the summary
        print(f"  {label} subprocess FAILED ({e!r})")
        return 1
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stdout.write(r.stderr[-4000:])
        print(f"  {label} subprocess FAILED (exit {r.returncode})")
        return 1
    return 0


def sharded_pass(seed: int, steps: int) -> int:
    """Spawn the sharded battery under 8 forced host devices."""
    return _cpu_child(["--sharded-child", "--seeds", str(seed),
                       "--steps", str(steps)], "sharded battery")


def chaos_child(seed: int, smoke: bool) -> int:
    """Runs inside the 8-device subprocess: the full multi-storm soak
    (bitflips + straggler storm + crash + shard loss + mid-rebuild remesh
    under live traffic; see repro.faults.chaos)."""
    from .chaos import run_chaos_soak
    r = run_chaos_soak(seed, sharded=True, smoke=smoke, verbose=print)
    print(f"  chaos soak: {r.summary()}")
    return 0 if r.ok() else 1


def chaos_pass(seed: int, smoke: bool) -> int:
    """Spawn the chaos soak under 8 forced host devices (the shard-loss
    and remesh storm phases need a mesh)."""
    return _cpu_child(["--chaos-child", "--seeds", str(seed)]
                      + (["--smoke"] if smoke else []), "chaos soak")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="CI budget: 1 crash-sweep seed, 3 oracle seeds")
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--no-sharded", action="store_true",
                   help="skip the multi-device (subprocess) battery")
    p.add_argument("--chaos", action="store_true",
                   help="run ONLY the chaos soak (seeded multi-storm run "
                        "under live traffic, 8 host devices)")
    p.add_argument("--sharded-child", action="store_true",
                   help=argparse.SUPPRESS)   # internal: runs in-process
    p.add_argument("--chaos-child", action="store_true",
                   help=argparse.SUPPRESS)   # internal: runs in-process
    args = p.parse_args(argv)

    if args.sharded_child:
        return sharded_child(args.seeds, args.steps)
    if args.chaos_child:
        return chaos_child(args.seeds, args.smoke)
    if args.chaos:
        t0 = time.time()
        print("== chaos soak (multi-storm, live traffic, 8 host devices) ==")
        fails = chaos_pass(args.seeds if args.seeds != 3 else 0, args.smoke)
        dt = time.time() - t0
        print(f"== chaos soak {'OK' if not fails else 'FAILED'} "
              f"in {dt:.1f}s ==")
        return 1 if fails else 0

    t0 = time.time()
    fails = 0
    sweep_seeds = 1 if args.smoke else args.seeds
    with tempfile.TemporaryDirectory() as tmp:
        print("== crash-point sweep ==")
        for seed in range(sweep_seeds):
            fails += crash_sweep(seed, args.steps, f"{tmp}/s{seed}")
        print("== crash + corruption ==")
        fails += crash_with_corruption(0, args.steps, tmp)
    print("== vulnerability-window oracle ==")
    for seed in range(max(args.seeds, 3)):
        fails += oracle_pass(seed, args.steps)
    print("== scrub patroller detection ==")
    for seed in range(1 if args.smoke else max(args.seeds, 2)):
        fails += patrol_pass(seed, args.steps)
    if not args.no_sharded:
        print("== sharded battery (2x2x2 mesh, 8 host devices) ==")
        fails += sharded_pass(0, args.steps)
    dt = time.time() - t0
    print(f"== fault battery {'OK' if not fails else f'FAILED ({fails})'} "
          f"in {dt:.1f}s ==")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
