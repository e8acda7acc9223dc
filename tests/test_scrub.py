"""Scrub patroller + online shard rebuild (repro.scrub).

Machine-local: byte-budget pacing, full-sweep coverage bound, mid-traffic
bitflip detection with bitwise parity repair, structured unrecoverable
reporting, and the measured >= 10x detection-latency win over a scheduled
scrub (deterministic: step_seconds=1, settled store — the MTTDL ratio
reduces to the latency ratio).

Multi-device (subprocess, 8 forced host devices): the steady-state patrol
programs (verify window, write sample) lower with zero collectives on a
2x2x2 mesh, and a wholesale shard loss rebuilds bitwise from cross-shard
parity while the foreground keeps writing into the lost shard.  The
rebuild's reconstruction/paste programs are *deliberately* cross-shard
(data must move between shards — same category as the tiny fold programs),
so they are exempt from the collective-free rule.
"""
import math

import numpy as np
import jax
import jax.numpy as jnp

from subproc import run_snippet

from repro.core import (ProtectedStore, RedundancyPolicy, UnrecoverableBlock,
                        plan_stripe_repairs)
from repro.faults.inject import FaultSpec

LANES = 128
BPB = LANES * 4                    # bytes per block at 128 uint32 lanes


def make_store(n_rows=32, cols=512, patrol_blocks=8, **kw):
    leaves = {"w": jax.random.normal(jax.random.PRNGKey(0),
                                     (n_rows, cols), jnp.float32)}
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=2, lanes_per_block=LANES,
        patrol_bytes_per_tick=patrol_blocks * BPB, precompile=False, **kw)
    store = ProtectedStore(pol).attach(leaves)
    return store, leaves, store.init(leaves)


def wait_probe(store):
    """Determinism under machine load: the next probe only dispatches
    once the previous one's flags have landed, so a loaded host would
    otherwise see fewer probes per N ticks (flaky pacing/sweep counts).
    Same idiom as tests/subproc.py's pending-update wait."""
    if store.patroller is not None and store.patroller._probe is not None:
        _, _, _, mism_d, clean_d, _, _ = store.patroller._probe
        jax.block_until_ready((mism_d, clean_d))


def quiet_ticks(store, leaves, red, step, n):
    for _ in range(n):
        red, rep = store.tick(leaves, red, step, scrub_period=0)
        if rep.repaired:
            leaves = dict(leaves, **rep.repaired)
        step += 1
        wait_probe(store)
    return leaves, red, step


# ---------------------------------------------------------------- machine-local


def test_patroller_gated_on_budget():
    store, _, _ = make_store(patrol_blocks=0)
    assert store.patroller is None
    store, _, _ = make_store(patrol_blocks=8)
    assert store.patroller is not None
    assert store.patroller.window["w"] == 8


def test_patrol_byte_budget_pacing():
    """Each probe covers exactly the byte budget's worth of blocks; the
    per-tick scan never exceeds it and the window caps at the leaf size."""
    store, leaves, red = make_store(patrol_blocks=8)     # nb=128, window=8
    pat = store.patroller
    nb = store.metas["w"].n_blocks
    assert nb == 128 and pat.window["w"] == 8
    T = 24
    leaves, red, _ = quiet_ticks(store, leaves, red, 0, T)
    # One probe max per tick (dispatch gated on the previous one landing),
    # every probe exactly one window: budget is a per-tick ceiling.
    scanned = store.counters["patrol.blocks_scanned"]
    assert scanned % 8 == 0
    assert 8 * (T // 2) <= scanned <= 8 * T
    # Budget larger than the leaf clamps to one-probe-covers-everything.
    big, _, _ = make_store(patrol_blocks=10_000)
    assert big.patroller.window["w"] == nb


def test_patrol_full_coverage_within_bound():
    """A full sweep completes within ~2 ticks per window (dispatch + land),
    so detection latency is bounded by the configured sweep length."""
    store, leaves, red = make_store(patrol_blocks=8)
    pat = store.patroller
    nb = store.metas["w"].n_blocks
    bound = 2 * math.ceil(nb / 8) + 4
    step = 0
    for _ in range(bound):
        red, _ = store.tick(leaves, red, step, scrub_period=0)
        step += 1
        wait_probe(store)
        if pat.sweeps["w"] >= 1:
            break
    assert pat.sweeps["w"] >= 1, (pat.sweeps, pat.cursor, bound)
    assert pat.coverage()["w"] == 1.0


def test_patrol_detects_and_repairs_mid_traffic():
    """A bitflip on a settled block is detected by the patrol *while
    foreground writes keep landing*, parity-repaired bitwise, and the
    store scrubs clean afterwards."""
    store, leaves, red = make_store(n_rows=32, patrol_blocks=8)
    pat = store.patroller
    rows = jnp.arange(4)                     # traffic: rows 0..3 only
    step = 0
    for _ in range(6):                       # settle the rest of the heap
        leaves = dict(leaves, w=leaves["w"].at[rows].add(0.5))
        ev = jnp.zeros((32,), bool).at[rows].set(True)
        red = store.on_write(red, events={"w": ev})
        red, _ = store.tick(leaves, red, step, scrub_period=0)
        step += 1
    red = store.flush(leaves, red, step)
    # Corrupt a block far from the traffic (4 blocks per 512-elem row).
    blk = 16 * (512 * 4 // BPB)
    leaves, red = store.inject(leaves, red, FaultSpec(
        kind="data_bitflip", leaf="w", block=blk, lane=3, bit=7))
    pat.expect_injection("w", blk, step)
    detected = repaired = False
    for _ in range(3 * (2 * (128 // 8) + 4)):
        leaves = dict(leaves, w=leaves["w"].at[rows].add(0.5))
        ev = jnp.zeros((32,), bool).at[rows].set(True)
        red = store.on_write(red, events={"w": ev})
        red, rep = store.tick(leaves, red, step, scrub_period=0)
        step += 1
        if rep.repaired:
            leaves = dict(leaves, **rep.repaired)
            repaired = True
        if pat.latencies:
            detected = True
        if detected and repaired:
            break
    assert detected, "patrol never detected the injected bitflip"
    assert repaired, "patrol never repaired the detected block"
    assert pat.latencies[0] <= 2 * (2 * (128 // 8) + 4)
    red = store.flush(leaves, red, step)
    assert store.scrub_check(leaves, red) == 0
    # Bitwise: the repaired block equals the original data (row 16 was
    # never written after init, so parity reconstruction must restore it).
    orig = np.asarray(jax.random.normal(jax.random.PRNGKey(0),
                                        (32, 512), jnp.float32))
    np.testing.assert_array_equal(np.asarray(leaves["w"])[16], orig[16])


def test_patrol_starvation_floor():
    """Wall-to-wall foreground traffic (an update dispatched every tick)
    must not starve the patrol forever: past
    ``patrol_max_starved_ticks`` consecutive probe-less ticks one probe
    dispatches anyway, and the streak rides on
    ``TickReport.patrol_starved_ticks``.  Floor 0 disables forcing (the
    pure quiet-tick gate), which is the starvation baseline."""
    for floor, expect_probes in ((0, False), (4, True)):
        leaves = {"w": jax.random.normal(jax.random.PRNGKey(0),
                                         (32, 512), jnp.float32)}
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=1, lanes_per_block=LANES,
            patrol_bytes_per_tick=8 * BPB, precompile=False,
            async_tick=False, patrol_max_starved_ticks=floor)
        store = ProtectedStore(pol).attach(leaves)
        red = store.init(leaves)
        pat = store.patroller
        last = 0
        for step in range(1, 31):      # step 0 is never update-due
            leaves = dict(leaves, w=leaves["w"].at[:4].add(0.5))
            ev = jnp.zeros((32,), bool).at[:4].set(True)
            red = store.on_write(red, events={"w": ev})
            red, rep = store.tick(leaves, red, step, scrub_period=0)
            assert rep.updated, "tick unexpectedly quiet"
            last = rep.patrol_starved_ticks
        scanned = store.counters["patrol.blocks_scanned"]
        if expect_probes:
            assert scanned >= 8, scanned
            assert last <= floor, last
        else:
            assert scanned == 0
            assert last >= 20, last


def test_unrecoverable_reported_structurally():
    """Two corruptions in one stripe defeat single-parity: the patroller
    reports them as a typed UnrecoverableBlock instead of looping."""
    store, leaves, red = make_store(patrol_blocks=8)
    pat = store.patroller
    red = store.flush(leaves, red, 0)
    for blk in (0, 1):                       # same stripe (stripe size 4+1)
        leaves, red = store.inject(leaves, red, FaultSpec(
            kind="data_bitflip", leaf="w", block=blk, lane=1, bit=2))
    step, found = 1, []
    for _ in range(40):
        red, rep = store.tick(leaves, red, step, scrub_period=0)
        if rep.repaired:
            leaves = dict(leaves, **rep.repaired)
        found.extend(rep.unrecoverable)
        step += 1
        if found:
            break
    assert found, "multi-corrupt stripe never reported"
    rec = found[0]
    assert isinstance(rec, UnrecoverableBlock)
    assert rec.leaf == "w" and rec.reason == "multi_corrupt"
    assert rec.stripe == 0 and set(rec.blocks) == {0, 1}
    assert pat.unrecoverable                 # also kept on the patroller


def test_plan_stripe_repairs_classifies():
    store, _, red = make_store()
    metas = {"w": store.metas["w"]}
    singles, unrec = plan_stripe_repairs(metas, {"w": [2, 8, 9]})
    assert singles == [("w", 2)]
    assert len(unrec) == 1 and unrec[0].reason == "multi_corrupt"
    assert set(unrec[0].blocks) == {8, 9}
    # bool-mask form is equivalent
    mask = np.zeros((store.metas["w"].n_blocks,), bool)
    mask[[2, 8, 9]] = True
    singles2, unrec2 = plan_stripe_repairs(metas, {"w": mask})
    assert singles2 == singles and unrec2[0].blocks == unrec[0].blocks


def test_patrol_latency_beats_scheduled_scrub_10x():
    """Acceptance: measured detection latency (hence measured MTTDL) with
    the patroller is >= 10x better than scheduled-scrub-only detection.
    Deterministic: unit step seconds, settled store, fixed schedules."""
    from benchmarks.mttdl_bench import run_patrolled
    rows = {name: derived for name, _, derived in
            run_patrolled(n_rows=256, sweep_ticks=8, scrub_period=240,
                          n_faults=1)}
    assert "mttdl/patrol/improvement" in rows, rows
    ratio = float(rows["mttdl/patrol/improvement"].split("x")[0])
    assert ratio >= 10.0, rows


# ----------------------------------------------------------------- multi-device


def test_sharded_patrol_programs_collective_free():
    run_snippet("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ProtectedStore, RedundancyPolicy
        from repro.launch.hlo_analysis import assert_no_collectives
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        spec = P(("pod", "data", "model"), None)
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=2, lanes_per_block=128, async_tick=True,
            patrol_bytes_per_tick=32 * 128 * 4, precompile=False)
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 2048), jnp.float32)
        lv = {"w": jax.device_put(w, NamedSharding(mesh, spec))}
        store = ProtectedStore(pol, mesh=mesh).attach(lv, specs={"w": spec})
        red = store.init(lv)
        pat = store.patroller
        eng = pat.engine_of("w")
        wdw = pat.window["w"]
        for want_slab in (False, True):
            lowered = jax.jit(eng.verify_window_fn("w", wdw, want_slab)).lower(
                lv["w"], red["w"], jnp.int32(0))
            assert_no_collectives(lowered, f"patrol_probe(slab={want_slab})")
        # per-tick write sample: elementwise over the sharded bitvectors
        lowered = jax.jit(lambda r: r.dirty | r.shadow).lower(red["w"])
        assert_no_collectives(lowered, "patrol_sample")
        print("PATROL_LOCAL_OK")
    """, "PATROL_LOCAL_OK")


def test_sharded_shard_loss_rebuild_bitwise():
    """Wholesale shard loss on a 2x2x2 mesh: the online rebuild restores
    the lost shard bitwise from cross-shard parity while foreground writes
    keep landing in the lost shard, within the paced tick budget."""
    run_snippet("""
        import math
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ProtectedStore, RedundancyPolicy
        from repro.faults.inject import FaultSpec
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        spec = P(("pod", "data", "model"), None)
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=2, lanes_per_block=128, async_tick=True,
            patrol_bytes_per_tick=32 * 128 * 4, precompile=False)
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 2048), jnp.float32)
        lv = {"w": jax.device_put(w, NamedSharding(mesh, spec))}
        store = ProtectedStore(pol, mesh=mesh).attach(lv, specs={"w": spec})
        red = store.init(lv)
        pat = store.patroller
        step = 0
        # Quiet sweeps until cross-shard parity covers the leaf.
        for _ in range(48):
            red, _ = store.tick(lv, red, step, scrub_period=0); step += 1
            xp = pat.xpar["w"]
            if xp.xpar is not None and bool(xp.xvalid.all()):
                break
        assert bool(pat.xpar["w"].xvalid.all()), "xpar never covered leaf"
        expected = np.array(np.asarray(lv["w"]))

        lost, rows_local = 3, 64 // 8
        lv, red = store.inject(lv, red, FaultSpec(
            kind="shard_loss", leaf="w", block=lost))
        pat._attempts[("w", 5)] = 99       # must reset with the rebuild
        store.declare_shard_lost("w", lost, red)
        # Foreground keeps writing — into the lost shard only (writes to
        # survivors after the xpar freeze are legitimate losses).
        w_rows = np.arange(lost * rows_local, lost * rows_local + 2)
        status = None
        writes = 0
        for i in range(24):
            idx = jnp.asarray(w_rows)
            lv = dict(lv, w=lv["w"].at[idx].set(float(i + 1)))
            expected[w_rows] = float(i + 1)
            writes += 1
            ev = jnp.zeros((64,), bool).at[idx].set(True)
            red = store.on_write(red, events={"w": ev})
            red, rep = store.tick(lv, red, step, scrub_period=0); step += 1
            if rep.repaired:
                lv = dict(lv, **rep.repaired)
            if rep.rebuild is not None and rep.rebuild.done:
                status = rep.rebuild
                break
        assert status is not None, "rebuild never finished"
        nb = store.metas["w"].n_blocks
        # Pacing: the rebuild takes ceil(nb / window) ticks, not one giant
        # stall (rebuild budget defaults to 4x the patrol budget).
        wb = min(nb, 4 * 32)
        assert status.ticks == math.ceil(nb / wb), (status, nb, wb)
        assert status.lost == 0, status
        # Stale per-block repair-attempt counts for the leaf died with the
        # rebuild (post-rebuild re-detections get a fresh budget).
        assert all(k[0] != "w" for k in pat._attempts), pat._attempts
        assert status.rebuilt + status.fresh == nb, status
        red = store.flush(lv, red, step)
        assert store.scrub_check(lv, red) == 0
        got = np.asarray(lv["w"])
        np.testing.assert_array_equal(got, expected)
        print("REBUILD_OK", status.rebuilt, status.fresh, writes)
    """, "REBUILD_OK")


def test_sharded_preloss_dirty_blocks_reported_lost():
    """Blocks with writes in flight *at loss time* (dirty at declaration)
    died with the shard: the rebuild must report them as ``shard_loss``
    unrecoverables, never misclassify them as fresh foreground rewrites —
    while the rest of the shard still rebuilds bitwise."""
    run_snippet("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ProtectedStore, RedundancyPolicy
        from repro.faults.inject import FaultSpec
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        spec = P(("pod", "data", "model"), None)
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=2, lanes_per_block=128, async_tick=True,
            patrol_bytes_per_tick=32 * 128 * 4, precompile=False)
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 2048), jnp.float32)
        lv = {"w": jax.device_put(w, NamedSharding(mesh, spec))}
        store = ProtectedStore(pol, mesh=mesh).attach(lv, specs={"w": spec})
        red = store.init(lv)
        pat = store.patroller
        step = 0
        for _ in range(48):
            red, _ = store.tick(lv, red, step, scrub_period=0); step += 1
            xp = pat.xpar["w"]
            if xp.xpar is not None and bool(xp.xvalid.all()):
                break
        assert bool(pat.xpar["w"].xvalid.all()), "xpar never covered leaf"
        expected = np.array(np.asarray(lv["w"]))

        lost, rows_local = 3, 64 // 8
        nb = store.metas["w"].n_blocks          # 128 local blocks
        bpr = nb // rows_local                  # 16 blocks per local row
        # An in-flight write at loss time: marks land, then the shard dies
        # before its redundancy covers the write — the data is gone.
        w_rows = np.arange(lost * rows_local, lost * rows_local + 2)
        idx = jnp.asarray(w_rows)
        lv = dict(lv, w=lv["w"].at[idx].set(7.0))
        ev = jnp.zeros((64,), bool).at[idx].set(True)
        red = store.on_write(red, events={"w": ev})
        lv, red = store.inject(lv, red, FaultSpec(
            kind="shard_loss", leaf="w", block=lost))
        store.declare_shard_lost("w", lost, red)   # marks -> preloss
        status, unrec = None, []
        for _ in range(24):
            red, rep = store.tick(lv, red, step, scrub_period=0); step += 1
            if rep.repaired:
                lv = dict(lv, **rep.repaired)
            unrec.extend(rep.unrecoverable)
            if rep.rebuild is not None and rep.rebuild.done:
                status = rep.rebuild
                break
        assert status is not None, "rebuild never finished"
        n_preloss = 2 * bpr
        assert status.lost == n_preloss, status
        assert status.fresh == 0, status
        assert status.rebuilt == nb - n_preloss, status
        want = {lost * nb + b for b in range(n_preloss)}
        got_blocks = {b for u in unrec if u.reason == "shard_loss"
                      for b in u.blocks}
        assert got_blocks == want, (sorted(got_blocks), sorted(want))
        # The untouched remainder of the shard still rebuilt bitwise, and
        # redundancy re-converged over the named loss (no eternal alarm).
        red = store.flush(lv, red, step)
        assert store.scrub_check(lv, red) == 0
        got = np.asarray(lv["w"])
        rest = np.arange(lost * rows_local + 2, (lost + 1) * rows_local)
        np.testing.assert_array_equal(got[rest], expected[rest])
        print("PRELOSS_OK", status.lost, status.rebuilt)
    """, "PRELOSS_OK")


def test_sharded_late_probe_cannot_revalidate_written_rows():
    """A probe that stays in flight for more than one tick must not
    re-validate cross-shard parity rows a foreground write invalidated
    after its dispatch (its clean mask predates the write): the sample
    invalidations processed while it flew mask its adoption."""
    run_snippet("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ProtectedStore, RedundancyPolicy
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        spec = P(("pod", "data", "model"), None)
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=2, lanes_per_block=128, async_tick=True,
            patrol_bytes_per_tick=32 * 128 * 4, precompile=False)
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 2048), jnp.float32)
        lv = {"w": jax.device_put(w, NamedSharding(mesh, spec))}
        store = ProtectedStore(pol, mesh=mesh).attach(lv, specs={"w": spec})
        red = store.init(lv)
        pat = store.patroller
        # Tick 0: prime + dispatch the first probe (window [0, 32)).
        red, _ = store.tick(lv, red, 0, scrub_period=0)
        assert pat._probe is not None and pat._probe[1] == 0

        class Slow:                    # pin the probe in flight
            def __init__(self, a, gate): self.a, self.gate = a, gate
            def is_ready(self): return self.gate[0] <= 0
            def __array__(self, *a, **k): return np.asarray(self.a)
        gate = [1]
        nm, st, wdw, mi, cl, xw, sp = pat._probe
        pat._probe = (nm, st, wdw, Slow(mi, gate), Slow(cl, gate), xw, sp)

        # A write lands while the probe is in flight: global row 0 ->
        # shard 0, local blocks [0, 16).
        lv = dict(lv, w=lv["w"].at[0:1].add(1.0))
        red = store.on_write(red, events={"w": jnp.zeros((64,), bool)
                                          .at[0].set(True)})
        # Tick 1: probe still pinned; the write sample covering the new
        # marks is dispatched.  Tick 2: that sample is processed (rows
        # [0, 16) invalidated), then the probe lands and adopts.
        red, _ = store.tick(lv, red, 1, scrub_period=0)
        gate[0] = 0
        red, _ = store.tick(lv, red, 2, scrub_period=0)
        xv = pat.xpar["w"].xvalid
        assert pat._probe is None, "probe never landed"
        assert not xv[0:16].any(), "late probe re-validated written rows"
        assert xv[16:32].all(), "adoption lost for untouched rows"
        print("LATE_PROBE_OK")
    """, "LATE_PROBE_OK")
