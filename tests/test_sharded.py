"""Multi-device behaviour (subprocess: needs XLA_FLAGS before jax import).

Covers: machine-local redundancy (zero collectives — including the queued
and overlap-pipelined Algorithm-1 programs), the sharded work-queue /
async-tick matrix (bitwise identity vs the blocking full recompute on a
2x2x2 host mesh), the sync-free sharded hot path, dry-run machinery on a
small production-shaped mesh, and gradient compression.  Subprocess
plumbing and the shared sharded-store fixture live in tests/subproc.py.
"""
import pytest

from subproc import MESH_PRELUDE, run_snippet


def test_redundancy_is_machine_local():
    run_snippet("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import RedundancyConfig, RedundancyEngine
        from repro.launch.hlo_analysis import assert_no_collectives
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        leaves = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 512), jnp.float32)}
        specs = {"w": P(("data","model"), None)}
        eng = RedundancyEngine({k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k,v in leaves.items()},
                               RedundancyConfig(lanes_per_block=128), mesh=mesh, specs=specs)
        leaves = {k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k,v in leaves.items()}
        red = eng.init(leaves)
        assert_no_collectives(jax.jit(eng.redundancy_step).lower(leaves, red), "full")
        mm = eng.scrub(leaves, red)
        assert all(int(v.sum())==0 for v in mm.values())
        print("LOCAL_OK")
    """, "LOCAL_OK")


def test_sharded_queued_and_async_programs_are_collective_free():
    """Acceptance: the per-shard work-queue and overlap Algorithm-1
    programs — including the batched multi-group program — lower with
    zero collectives on a 2x2x2 mesh; the stacked fit vector keeps one
    bool per device per group and is AND-folded on the host, never in a
    device program."""
    run_snippet("""
        from repro.core import workqueue
        from repro.launch.hlo_analysis import assert_no_collectives
        store = mesh_store(async_tick=True, precompile=False)
        g = next(iter(store.groups.values()))
        eng = g.engine
        assert eng.has_queue and eng.queue_capacity("w") == 16 \
            and eng.queue_capacity("e") == 0, \
            (eng.has_queue, eng.queue_capacity("w"), eng.queue_capacity("e"))
        lv = put(make_leaves())
        red = store.init(lv)
        from repro.core.store import _queued_variant
        for variant in ("queued", "full", "async_full"):
            lowered = store._build_update(g.label, variant).lower(lv, red)
            assert_no_collectives(lowered, variant)
        rungs = tuple(map(_queued_variant, eng.queue_ladder))
        assert rungs, eng.queue_ladder
        for variant in rungs + ("async_full",):
            lowered = store._build_update_many(
                (g.label,), (variant,)).lower((lv,), (red,))
            assert_no_collectives(lowered, "many_" + variant)
        outs, stacked, counts = store._update_many_fn(
            (g.label,), (rungs[-1],))((lv,), (red,))
        # one row per group, one flag column per device; each device's
        # [stripes, need] pair beside its flag
        assert stacked.shape == (1, 8), stacked.shape
        assert counts.shape == (1, 8, 2), counts.shape
        assert workqueue.fold_fits_host(np.asarray(stacked)[0])
        print("PROGRAMS_OK")
    """, "PROGRAMS_OK", prelude=MESH_PRELUDE)


@pytest.mark.parametrize("async_tick", ["0", "1"])
def test_sharded_queued_matrix_bitwise_vs_blocking_full(async_tick):
    """Queued-path x REPRO_ASYNC_TICK matrix: on a 2x2x2 host mesh the
    work-queue dispatch (blocking exact fit or speculative overlap per the
    env lever) must be bitwise-identical to the blocking full recompute,
    actually dispatch the queued program, and end scrub-clean."""
    run_snippet("""
        # env lever decides the tick mode (policy does not pin async_tick)
        store = mesh_store()
        used = []
        orig = store._update_fn
        store._update_fn = lambda label, variant: (used.append(variant),
                                                   orig(label, variant))[1]
        orig_many = store._update_many_fn
        store._update_many_fn = lambda labels, variants: (
            used.extend(variants), orig_many(labels, variants))[1]
        lv, red = drive(store, steps=8, seed=5)
        red = store.settle(red, lv)
        assert any("queued" in v for v in used), used
        import os
        if os.environ["REPRO_ASYNC_TICK"] == "1":
            assert any(v.startswith("async") for v in used), used
        else:
            assert not any(v.startswith("async") for v in used), used
        ref = mesh_store(frac=0.0, async_tick=False)    # blocking full recompute
        lv_ref, red_ref = drive(ref, steps=8, seed=5)
        assert_red_equal(red, red_ref)
        assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
        assert all(bool(v) for v in store.verify_meta(red).values())
        print("MATRIX_OK")
    """, "MATRIX_OK", env={"REPRO_ASYNC_TICK": async_tick},
        prelude=MESH_PRELUDE)


def test_sharded_async_hot_path_never_pays_queue_fits_round_trip():
    """Acceptance: a due tick on the sharded overlap path must never call
    the host-side queue_fits round trip — the fit signal is the per-shard
    flag array folded on device and fetched one tick ahead."""
    run_snippet("""
        store = mesh_store(async_tick=True, period=1)
        def boom(*a, **k):
            raise AssertionError("queue_fits called on the sharded async hot path")
        for g in store._protected():
            g.engine.queue_check = boom      # queue_fits goes through it
        lv, red = drive(store, steps=6, seed=2)
        g = next(iter(store.groups.values()))
        assert g.pending is None or g.pending.fits.shape == (1, 8), \
            "pending fit signal must be the batched per-shard row"
        for g in store._protected():
            del g.engine.queue_check         # settle may use the exact check
        red = store.settle(red, lv)
        assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
        print("HOTPATH_OK")
    """, "HOTPATH_OK", prelude=MESH_PRELUDE)


def test_sharded_overflow_on_one_shard_is_bitwise_safe():
    """A speculative queued dispatch that overflows a single shard's local
    queue must keep that shard's snapshot marked and settle to the exact
    blocking-path bits via the full fallback."""
    run_snippet("""
        outs = []
        for async_on in (True, False):
            store = mesh_store(async_tick=async_on, period=1)
            lv = put(make_leaves())
            red = store.init(lv)
            g = next(iter(store.groups.values()))
            if async_on:
                g.predicted_fits = True       # force the misprediction
            # overflow ONLY shard 0 of "w" (it owns rows 0..7)
            ev = jnp.zeros((64,), bool).at[jnp.arange(8)].set(True)
            lv = dict(lv, w=lv["w"].at[jnp.arange(8)].add(1.0))
            red = store.on_write(red, events={"w": ev})
            red, rep = store.tick(lv, red, 1)
            if async_on:
                p = g.pending
                assert p is not None and p.queued
                store.sync_inflight()
                red, rep = store.tick(lv, red, 2)
                assert rep.overflowed and g.predicted_fits is False
            red = store.settle(red, lv)
            outs.append(red)
            assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
        assert_red_equal(outs[0], outs[1])
        print("OVERFLOW_OK")
    """, "OVERFLOW_OK", prelude=MESH_PRELUDE)


def test_sharded_multigroup_tick_batches_one_launch_one_fetch():
    """Tentpole acceptance: with TWO due vilamb groups on 8 devices, a due
    tick dispatches exactly ONE batched update program covering both
    groups and fetches ONE stacked fits vector shared by both pendings;
    the per-group update programs never launch on the async tick path."""
    run_snippet("""
        from repro.core import LeafPolicy, ProtectedStore, RedundancyPolicy
        pol = RedundancyPolicy(
            default=LeafPolicy(mode="vilamb", period_steps=2,
                               work_queue_frac=0.5),
            rules=(("e", LeafPolicy(mode="vilamb", period_steps=2,
                                    work_queue_frac=0.0)),),
            lanes_per_block=128, async_tick=True, precompile=False)
        store = ProtectedStore(pol, mesh=MESH).attach(make_leaves(),
                                                      specs=SPECS)
        groups = list(store._protected())
        assert len(groups) == 2, [g.label for g in groups]
        many_calls, single_calls = [], []
        orig_many = store._update_many_fn
        store._update_many_fn = lambda labels, variants: (
            many_calls.append((labels, variants)),
            orig_many(labels, variants))[1]
        orig = store._update_fn
        store._update_fn = lambda label, variant: (
            single_calls.append((label, variant)),
            orig(label, variant))[1]
        lv = put(make_leaves())
        red = store.init(lv)
        for step in (1, 2, 3, 4):
            evw = jnp.zeros((64,), bool).at[step].set(True)
            eve = jnp.zeros((16,), bool).at[step].set(True)
            red = store.on_write(red, events={"w": evw, "e": eve})
            store.sync_inflight()
            n_before = len(many_calls)
            red, _ = store.tick(lv, red, step)
            if step % 2 == 0:                  # both groups due
                assert len(many_calls) == n_before + 1, many_calls
                labels, variants = many_calls[-1]
                assert sorted(labels) == sorted(g.label for g in groups)
                pendings = [g.pending for g in groups]
                assert all(p is not None for p in pendings)
                # ONE stacked fits vector + ONE resolver event per batch
                assert pendings[0].fits is pendings[1].fits
                assert pendings[0].launched is pendings[1].launched
                assert pendings[0].fits.shape == (2, 8), pendings[0].fits.shape
            else:
                assert len(many_calls) == n_before
        assert not single_calls, single_calls
        red = store.settle(red, lv)
        assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
        print("MULTIGROUP_OK")
    """, "MULTIGROUP_OK", prelude=MESH_PRELUDE)


def test_sharded_dispatcher_thread_lifecycle():
    """Satellite acceptance: the resolver thread exists only between the
    first overlapped dispatch and the next flush/remesh handover — flush
    joins it cleanly, and a remesh adoption never leaks it."""
    run_snippet("""
        import threading
        from repro.launch.mesh import make_mesh

        def dispatch_threads():
            return [t for t in threading.enumerate()
                    if t.name == "repro-dispatch" and t.is_alive()]

        store = mesh_store(async_tick=True, period=1, precompile=False,
                           remesh_bytes_per_tick=64 * 128 * 4)
        assert store._dispatcher is None and not dispatch_threads()
        lv, red = drive(store, steps=3, seed=7)
        assert store._dispatcher is not None \
            and store._dispatcher.thread.is_alive(), \
            "overlapped dispatch must have spun up the resolver thread"
        red = store.flush(lv, red, step=3)
        assert store._dispatcher is None and not dispatch_threads(), \
            "flush must join the resolver thread"
        # Next overlapped dispatch re-creates it lazily...
        step = 3
        for step in (4, 5):
            ev = jnp.zeros((64,), bool).at[step].set(True)
            red = store.on_write(red, events={"w": ev})
            red, _ = store.tick(lv, red, step)
        assert store._dispatcher is not None
        # ...and a remesh handover shuts it down before migrating, without
        # leaking a thread across the geometry swap.
        store.remesh(make_mesh((2, 2, 1), ("pod", "data", "model")),
                     {"w": SPECS["w"], "e": SPECS["e"]})
        while store.remeshing:
            step += 1
            ev = jnp.zeros((64,), bool).at[step % 64].set(True)
            red = store.on_write(red, events={"w": ev})
            red, rep = store.tick(lv, red, step)
            if rep.repaired:
                lv = dict(lv, **rep.repaired)
            assert step < 80, "remesh never finished"
        assert len(dispatch_threads()) <= 1, \
            "remesh must not leak resolver threads"
        red = store.flush(lv, red, step=step)
        assert store._dispatcher is None and not dispatch_threads()
        assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
        print("LIFECYCLE_OK")
    """, "LIFECYCLE_OK", prelude=MESH_PRELUDE)


def test_tiny_mesh_dryrun_all_kinds():
    run_snippet("""
        import jax
        from repro.configs import get_smoke
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import (build_train_setup, build_decode_setup,
                                        build_prefill_setup)
        from repro.models.config import ShapeConfig
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        cfg = get_smoke("jamba-1.5-large-398b")
        with mesh:
            s = build_train_setup(cfg, ShapeConfig("t", 64, 8, "train"), mesh)
            jax.jit(s.step_fn, in_shardings=(s.state_sharding, s.batch_sharding),
                    out_shardings=(s.state_sharding, None), donate_argnums=(0,)
                    ).lower(s.state_struct, s.batch_struct).compile()
            d = build_decode_setup(cfg, ShapeConfig("d", 64, 8, "decode"), mesh)
            jax.jit(d.step_fn, in_shardings=d.args_sharding, donate_argnums=(1,2)
                    ).lower(*d.args_struct).compile()
            p = build_prefill_setup(cfg, ShapeConfig("p", 64, 4, "prefill"), mesh)
            jax.jit(p.step_fn, in_shardings=p.args_sharding,
                    out_shardings=p.out_sharding).lower(*p.args_struct).compile()
        print("DRYRUN_OK")
    """, "DRYRUN_OK")


def test_sharded_training_matches_single_device():
    run_snippet("""
        import jax, numpy as np
        from repro.configs import get_smoke
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import build_train_setup
        from repro.models.config import ShapeConfig
        from repro.data import SyntheticPipeline
        import dataclasses
        cfg = dataclasses.replace(get_smoke("olmo-1b"), param_dtype="float32")
        shape = ShapeConfig("t", 32, 8, "train")
        # single device reference
        s1 = build_train_setup(cfg, shape, None, mode="none")
        params = s1.model.init(jax.random.PRNGKey(0))
        from repro.optim import AdamW, warmup_cosine
        opt = AdamW(lr=warmup_cosine(3e-4, 100, 10000), moment_dtype=cfg.moment_dtype)
        from repro.train.state import TrainState
        st = TrainState.create(params, opt.init(params))
        data = SyntheticPipeline(cfg, shape, seed=0)
        st1, m1 = jax.jit(s1.step_fn)(st, data.get(0))
        # sharded
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        with mesh:
            s8 = build_train_setup(cfg, shape, mesh, mode="none", accum_steps=1)
            fn = jax.jit(s8.step_fn, in_shardings=(s8.state_sharding, s8.batch_sharding),
                         out_shardings=(s8.state_sharding, None))
            data8 = SyntheticPipeline(cfg, shape, seed=0, mesh=mesh)
            st8, m8 = fn(st, data8.get(0))
        l1, l8 = float(m1["loss"]), float(m8["loss"])
        assert abs(l1 - l8) < 5e-4, (l1, l8)
        a = np.asarray(jax.tree.leaves(st1.params)[0])
        b = np.asarray(jax.tree.leaves(st8.params)[0])
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-5)
        print("MATCH_OK", l1, l8)
    """, "MATCH_OK")


def test_sharded_rung_follows_the_largest_shard():
    """Per-shard queues pick their rung from the largest shard's count,
    not the sum: 150 dirty stripes on each of 8 shards (1200 in all, past
    the 1024 top) run at 256 a shard, paying 8 x 256 queue rows a pass,
    and settle to the full recompute's bits."""
    run_snippet("""
        from repro.core import RedundancyEngine
        mesh = MESH
        spec = {"h": P(("pod", "data", "model"), None)}
        shard = NamedSharding(mesh, spec["h"])
        rows = 8 * 8192                   # 2048 stripes a shard: top 1024
        rng = np.random.default_rng(0)
        lv = {"h": jax.device_put(jnp.asarray(rng.integers(
            0, 2**32, (rows, 128), dtype=np.uint32)), shard)}
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=1, lanes_per_block=128,
            work_queue_frac=0.5, async_tick=True, precompile=False)
        store = ProtectedStore(pol, mesh=mesh).attach(lv, specs=spec)
        g = next(iter(store.groups.values()))
        assert g.engine.queue_ladder == (256, 1024), g.engine.queue_ladder
        red = store.init(lv)
        for step in range(1, 5):
            sids = np.concatenate([s * 2048 + rng.choice(2048, 150, replace=False)
                                   for s in range(8)])
            idx = jnp.asarray(np.sort(sids * 4))
            lv = {"h": lv["h"].at[idx].add(jnp.uint32(step))}
            red = store.on_write(red, events={"h": jnp.zeros((rows,), bool)
                                              .at[idx].set(True)})
            store.sync_inflight()
            before = dict(store.counters)
            red, rep = store.tick(lv, red, step)
            assert not rep.overflowed
            if step >= 2:
                assert g.rung == 256 and g.pending.queued, g.rung
            if step >= 3:
                grew = store.counters["update.queue_rows"] - before["update.queue_rows"]
                assert grew == 8 * 256, grew
                assert store.counters["update.stripes"] - before["update.stripes"] == 1200
        red = store.settle(red, lv)
        assert_red_equal(red, g.engine.init(lv))
        print("RUNG_OK")
    """, "RUNG_OK", prelude=MESH_PRELUDE)
