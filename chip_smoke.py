"""Smoke run of the protected store on a TPU: its main paths, once, at real size.

A smoke run, not a benchmark: every phase checks its results and raises on
the first failed check; the timings and memory it prints are the facts of
one run.

    python chip_smoke.py               # one chip: region, serve, train
    python chip_smoke.py --chips 4     # four chips: the sharded region only

Phases (all under the library defaults: vilamb, pipelined tick, resolver
thread, AOT precompile):

* ``region`` — the paper's DAX region: a 2 GiB heap of 4 KiB rows under a
  vilamb policy with the scrub patroller and a freshness deadline, driven
  by ~200 Zipf/uniform write batches.  Checks the heap against a host
  mirror bitwise, a clean scrub, patrol detection + parity repair of an
  injected bit flip, ``read_verified``, and one update through the Pallas
  kernel (compiled, not interpreted) bitwise equal to the XLA path.
* ``serve`` — olmo-1b as registered (random bf16 weights) generating with
  a protected KV cache; tokens equal an unprotected run, scrubs are clean.
* ``train`` — olmo-1b widths, depth cut to ``TRAIN_LAYERS``; AdamW with
  fp32 moments under a mixed sync/vilamb policy; the launcher's corruption
  demo repairs; a checkpoint restores verified into a fresh store bitwise.
* ``sharded`` (``--chips 4``) — the region split over a 2x2 mesh in four
  vilamb groups: batched multi-group updates, collective-free update
  programs, and one shard lost and rebuilt under live writes.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or on any failed check, the script exits non-zero and prints
no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ROW = 1024                      # fp32 elements per heap row: one 4 KiB block
STRIPE = 4                      # data blocks per parity stripe (paper: 4+1)
REGION_ROWS = 512 * 1024        # 2 GiB heap (memory_analysis: fits 16 GB)
BATCHES = (32, 64, 128, 256, 512)
SERVE_POLICY = "*/k=vilamb:4,*/v=vilamb:8"
SERVE_SCRUB_EVERY = 8
TRAIN_POLICY = "params/*=sync,m/*=vilamb:4,v/*=vilamb:4"
# olmo-1b at its published widths with 16 layers needs ~17 GiB for params,
# fp32 moments and redundancy; 4 layers leave room for a second copy of the
# state, which the verified restore holds beside the first.
TRAIN_LAYERS = 4
SHARDED_HEAPS = 4               # >= 4 vilamb groups: batched multi-group updates
GiB = float(1 << 30)


def check(ok, what: str) -> None:
    """Raise on a failed smoke check (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def report(phase: str, **facts) -> None:
    import jax
    facts = dict(device=repr(jax.devices()[0].device_kind), **facts)
    print(f"[smoke {phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def peak_gib() -> str:
    """Peak device memory so far (the largest over devices), in GiB."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return f"{max(peaks) / GiB:.3f}" if peaks else "n/a"


def red_bytes(red) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(red))


def data_bytes(store) -> int:
    return sum(m.data_bytes for m in store.protected_metas.values())


# ------------------------------------------------------------------ traffic
def batch_rows(rng, lo: int, hi: int, batch: int, zipf: bool) -> np.ndarray:
    """``batch`` distinct rows in ``[lo, hi)``: Zipf-hot rows first (when
    ``zipf``), topped up uniformly.  Distinct, so a scatter has one writer
    per row and the host mirror's order of writes does not matter."""
    n = hi - lo
    rows = np.unique((rng.zipf(1.2, size=batch) - 1) % n) if zipf else \
        np.empty(0, np.int64)
    while len(rows) < batch:
        extra = rng.integers(0, n, size=2 * batch)
        rows = np.concatenate([rows, np.setdiff1d(extra, rows)])
    return (rng.permutation(rows[:batch]) + lo).astype(np.int32)


def fill(shape, seed: int, sharding=None):
    """Random-normal fp32 rows made on the device (set-up, not traffic)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                 out_shardings=sharding)
    return fn(jax.random.PRNGKey(seed))


def same_bits(dev, host) -> bool:
    """Bitwise equality of a device array and a host array."""
    a = np.asarray(dev)
    return (a.dtype == host.dtype and a.shape == host.shape and np.array_equal(
        a.reshape(-1).view(np.uint8), host.reshape(-1).view(np.uint8)))


def region_policy(patrol_bytes: int, **kw):
    from repro.core import RedundancyPolicy
    return RedundancyPolicy.single(
        "vilamb", period_steps=8, max_vulnerable_steps=16,
        lanes_per_block=ROW, stripe_data_blocks=STRIPE,
        patrol_bytes_per_tick=patrol_bytes, **kw)


def region_phase(rows: int = REGION_ROWS, steps: int = 200, seed: int = 0,
                 patrol_bytes: int = 32 << 20,
                 interpret: bool = False) -> dict:
    """One heap under vilamb + patrol: traffic, bit-flip repair, verified
    reads, and a kernel update against the XLA path."""
    import jax
    import jax.numpy as jnp
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.faults import FaultSpec
    from repro.scrub.patrol import PROBE_FORCE_TICKS

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    heap = fill((rows, ROW), seed)
    mirror = np.array(heap)
    store = ProtectedStore(region_policy(patrol_bytes)).attach({"heap": heap})
    red = store.init({"heap": heap})
    jax.block_until_ready(red)
    setup_s = time.perf_counter() - t0

    def write(heap, red, idx, vals):
        heap = heap.at[idx].set(vals)
        mask = jnp.zeros((rows,), bool).at[idx].set(True)
        return heap, store.on_write(red, events={"heap": mask})

    write = jax.jit(write, donate_argnums=(0, 1))
    sizes = list(BATCHES) + list(rng.choice(BATCHES, size=steps - len(BATCHES)))
    traffic = []
    for i, b in enumerate(sizes):
        idx = batch_rows(rng, 0, rows, int(b), zipf=i % 2 == 0)
        traffic.append((idx, rng.standard_normal((int(b), ROW), np.float32)))

    first_s = 0.0
    for step, (idx, vals) in enumerate(traffic):
        if step == len(BATCHES):
            jax.block_until_ready(heap)
            t_steady = time.perf_counter()
        t = time.perf_counter()
        heap, red = write(heap, red, jnp.asarray(idx), jnp.asarray(vals))
        red, _ = store.tick({"heap": heap}, red, step)
        if step < len(BATCHES):   # first call of each batch shape compiles
            jax.block_until_ready(heap)
            first_s += time.perf_counter() - t
    red = store.settle(red, {"heap": heap}, step=steps - 1)
    jax.block_until_ready((heap, red))
    steady_s = time.perf_counter() - t_steady
    for idx, vals in traffic:     # replayed in order, outside the timing
        mirror[idx] = vals
    step = steps
    check(same_bits(heap, mirror), "heap equals the host mirror after traffic")
    check(store.scrub_check({"heap": heap}, red) == 0,
          "scrub is clean after traffic")

    # One bit flip outside the vulnerability window: the patroller must
    # find it and repair it from parity.
    red = store.flush({"heap": heap}, red, step)
    block = int(rng.integers(rows))
    leaves, red = store.inject({"heap": heap}, red, FaultSpec(
        kind="data_bitflip", leaf="heap", block=block,
        lane=int(rng.integers(ROW)), bit=int(rng.integers(32))))
    heap = leaves["heap"]
    pat = store.patroller
    pat.expect_injection("heap", block, step)
    # Two sweeps, each probe resolving within PROBE_FORCE_TICKS ticks.
    sweep = -(-rows // pat.window["heap"])
    repaired = False
    for _ in range(2 * sweep * (PROBE_FORCE_TICKS + 1) + 16):
        red, rep = store.tick({"heap": heap}, red, step)
        step += 1
        if "heap" in rep.repaired:
            heap = rep.repaired["heap"]
            repaired = True
            break
    # The one registered injection's latency lands once the patrol finds it.
    detected = list(pat.latencies)
    check(detected and repaired, f"patrol detected+repaired block {block}")
    check(same_bits(heap, mirror), "heap equals the mirror after repair")
    check(store.scrub_check({"heap": heap}, red) == 0,
          "scrub is clean after repair")

    picks = sorted({block, 0, rows - 1, *traffic[-1][0][:4].tolist()})
    got = store.read_verified({"heap": heap}, red, "heap", picks)
    check(all(np.array_equal(got[b], mirror[b].view(np.uint32))
              for b in picks), f"read_verified returns the mirror at {picks}")

    # One update through the Pallas kernel against the XLA path, over
    # fresh writes whose redundancy is stale.
    kstore = ProtectedStore(RedundancyPolicy.single(
        "vilamb", lanes_per_block=ROW, stripe_data_blocks=STRIPE,
        use_kernels=True, kernel_interpret=interpret,
        precompile=False)).attach({"heap": heap})
    idx, vals = traffic[-1]
    vals = -vals
    mirror[idx] = vals
    heap, red = write(heap, red, jnp.asarray(idx), jnp.asarray(vals))
    t = time.perf_counter()
    k_red = jax.jit(kstore.redundancy_step)({"heap": heap}, red)
    jax.block_until_ready(k_red)
    kernel_first_s = time.perf_counter() - t
    x_red = jax.jit(store.redundancy_step)({"heap": heap}, red)
    for f in ("checksums", "parity", "meta_ck"):
        check(bool(jnp.array_equal(getattr(k_red["heap"], f),
                                   getattr(x_red["heap"], f))),
              f"kernel update {f} equal to the XLA path's")
    check(store.scrub_check({"heap": heap}, k_red) == 0,
          "scrub is clean against the kernel's redundancy")
    check(same_bits(heap, mirror), "heap equals the mirror at the end")

    facts = dict(
        rows=rows, protected_gib=f"{data_bytes(store) / GiB:.3f}",
        redundancy_gib=f"{red_bytes(red) / GiB:.3f}",
        setup_s=f"{setup_s:.2f}", first_calls_s=f"{first_s:.2f}",
        steady_ms_per_step=f"{steady_s / (steps - len(BATCHES)) * 1e3:.3f}",
        kernel_update_first_s=f"{kernel_first_s:.2f}",
        patrol_probes_ready=store.counters["patrol.probes_ready"],
        patrol_probes_forced=store.counters["patrol.probes_forced"],
        detect_latency_steps=detected[0],
        peak_gib=peak_gib())
    report("region", **facts)
    return facts


def serve_phase(cfg, batch: int = 8, prompt_len: int = 512, gen: int = 64,
                seed: int = 0) -> dict:
    """Greedy generation with a protected KV cache vs an unprotected run."""
    import jax
    import jax.numpy as jnp
    from repro.common import flatten_dict
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.models import build_model
    from repro.serve import Server

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    max_len = prompt_len + gen + 1
    prompts = {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, prompt_len), 0,
        cfg.vocab_size, jnp.int32)}

    t = time.perf_counter()
    ref, _ = Server(model=model, max_len=max_len).generate(
        params, prompts, gen, scrub_every=0)
    ref = np.asarray(ref)
    ref_s = time.perf_counter() - t

    caches = jax.eval_shape(lambda: model.init_caches(batch, max_len, 0))
    store = ProtectedStore(RedundancyPolicy.from_spec(
        SERVE_POLICY, period_steps=16)).attach(flatten_dict(caches))
    srv = Server(model=model, store=store, max_len=max_len)
    times, stats = [], None
    for _ in range(2):            # the first call compiles
        t = time.perf_counter()
        tokens, stats = srv.generate(params, prompts, gen,
                                     scrub_every=SERVE_SCRUB_EVERY)
        tokens = np.asarray(tokens)
        times.append(time.perf_counter() - t)
        check(np.array_equal(tokens, ref),
              "protected tokens equal the unprotected run's")
        check(stats["mismatches"] == 0, "KV-cache scrubs are clean")
    facts = dict(
        arch=cfg.name, layers=cfg.n_layers, batch=batch,
        prompt_len=prompt_len, gen=gen,
        protected_gib=f"{data_bytes(store) / GiB:.3f}",
        redundancy_gib=f"{red_bytes(stats['red']) / GiB:.3f}",
        unprotected_first_call_s=f"{ref_s:.2f}",
        first_call_s=f"{times[0]:.2f}",
        steady_ms_per_token=f"{times[1] / gen * 1e3:.3f}",
        peak_gib=peak_gib())
    report("serve", **facts)
    return facts


def train_phase(cfg, steps: int = 8, seq: int = 512, batch: int = 8,
                seed: int = 0) -> dict:
    """Trainer + ProtectedStore + CheckpointManager, wired as the training
    launcher wires them: loss, SDC repair, verified restore."""
    import jax
    from repro.ckpt import CheckpointManager
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.data import SyntheticPipeline
    from repro.launch.train import inject_corruption
    from repro.models import build_model
    from repro.models.config import ShapeConfig
    from repro.optim import AdamW, warmup_cosine
    from repro.train import Trainer, protected_structs

    model = build_model(cfg)
    data = SyntheticPipeline(cfg, ShapeConfig("smoke", seq, batch, "train"),
                             seed=seed)
    opt = AdamW(lr=warmup_cosine(1e-3, 10, steps),
                moment_dtype=cfg.moment_dtype)
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(model.init, key)
    structs = protected_structs(params, jax.eval_shape(opt.init, params))
    policy = RedundancyPolicy.from_spec(
        TRAIN_POLICY, period_steps=8, scrub_period_steps=4,
        max_vulnerable_steps=6)
    t = time.perf_counter()
    store = ProtectedStore(policy).attach(structs)
    trainer = Trainer(model=model, opt=opt, store=store, scrub_period_steps=4)
    state = trainer.init_state(key)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t

    losses = []
    t = time.perf_counter()
    state = trainer.run(state, data, 1,
                        on_step=lambda st, m: losses.append(float(m["loss"])))
    first_s = time.perf_counter() - t
    t = time.perf_counter()
    state = trainer.run(state, data, steps - 1,
                        on_step=lambda st, m: losses.append(float(m["loss"])))
    jax.block_until_ready(state)
    steady_s = time.perf_counter() - t
    check(np.all(np.isfinite(losses)), f"losses finite: {losses}")
    check(trainer.corruption_alarms == 0, "no scrub alarms while training")

    state, counts = inject_corruption(trainer, store, state)
    check(counts == {"detected": 1, "repaired": 1, "unrecoverable": 0,
                     "residual": 0}, f"corruption demo: {counts}")

    struct = jax.eval_shape(lambda: trainer.init_state(key))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        step = int(state.step)
        t = time.perf_counter()
        mgr.save(step, state, blocking=True)
        save_s = time.perf_counter() - t
        saved = [np.asarray(x) for x in jax.tree.leaves(state)]
        prot_bytes, red_b = data_bytes(store), red_bytes(state.red)
        del state                 # the restore holds its own copy
        fresh = ProtectedStore(policy).attach(structs)
        t = time.perf_counter()
        restored = mgr.restore_verified(struct, fresh)
        restore_s = time.perf_counter() - t
        check(restored is not None
              and mgr.last_restore_report.tried == [(step, "ok")],
              f"verified restore: {mgr.last_restore_report}")
        got = jax.tree.leaves(restored)
        check(len(got) == len(saved) and all(
            same_bits(a, b) for a, b in zip(got, saved)),
            "restored state equals the saved state bitwise")
    facts = dict(
        arch=cfg.name, layers=cfg.n_layers,
        seq=seq, batch=batch, steps=steps,
        protected_gib=f"{prot_bytes / GiB:.3f}",
        redundancy_gib=f"{red_b / GiB:.3f}", setup_s=f"{setup_s:.2f}",
        first_step_s=f"{first_s:.2f}",
        steady_ms_per_step=f"{steady_s / (steps - 1) * 1e3:.3f}",
        loss_first=f"{losses[0]:.4f}", loss_last=f"{losses[-1]:.4f}",
        ckpt_save_s=f"{save_s:.2f}", ckpt_restore_verified_s=f"{restore_s:.2f}",
        peak_gib=peak_gib())
    report("train", **facts)
    return facts


def sharded_phase(rows: int = 1 << 18, steps: int = 60, seed: int = 0,
                  patrol_bytes: int = 16 << 20) -> dict:
    """The region on a 2x2 mesh in ``SHARDED_HEAPS`` vilamb groups (one
    heap each, dim 0 split over both axes): traffic, collective-free
    batched updates, and one shard lost and rebuilt under live writes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import LeafPolicy, ProtectedStore
    from repro.core.store import _queued_variant
    from repro.faults import FaultSpec
    from repro.launch.hlo_analysis import assert_no_collectives
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    shards = mesh.devices.size
    spec = P(("data", "model"), None)
    sh = NamedSharding(mesh, spec)
    names = [f"h{i}" for i in range(SHARDED_HEAPS)]
    # Distinct periods make distinct groups; several fall due together, so
    # the tick dispatches them as one batched multi-group program.
    policy = dataclasses.replace(region_policy(patrol_bytes), rules=tuple(
        (n, LeafPolicy(mode="vilamb", period_steps=2 * (i + 1),
                       max_vulnerable_steps=16))
        for i, n in enumerate(names)))
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    heaps = {n: fill((rows, ROW), seed + i, sh) for i, n in enumerate(names)}
    mirror = {n: np.array(h) for n, h in heaps.items()}
    store = ProtectedStore(policy, mesh=mesh).attach(
        heaps, specs={n: spec for n in names})
    red = store.init(heaps)
    jax.block_until_ready(red)
    setup_s = time.perf_counter() - t0
    check(len(store.groups) == SHARDED_HEAPS, f"{SHARDED_HEAPS} vilamb groups")

    def write(heaps, red, idx, vals):
        heaps, events = dict(heaps), {}
        for i, n in enumerate(names):
            heaps[n] = heaps[n].at[idx[i]].set(vals[i])
            events[n] = jnp.zeros((rows,), bool).at[idx[i]].set(True)
        return heaps, store.on_write(red, events=events)

    write = jax.jit(write, donate_argnums=(0, 1), out_shardings=(
        {n: sh for n in names}, store.red_shardings()))

    def traffic_step(red, heaps, step, batch, lost=None):
        idx = np.stack([
            batch_rows(rng, *((lost * rows // shards, (lost + 1) * rows // shards)
                              if lost is not None and n == names[0]
                              else (0, rows)), batch, zipf=step % 2 == 0)
            for n in names])
        vals = rng.standard_normal((SHARDED_HEAPS, batch, ROW), np.float32)
        for i, n in enumerate(names):
            mirror[n][idx[i]] = vals[i]
        heaps, red = write(heaps, red, jnp.asarray(idx), jnp.asarray(vals))
        red, rep = store.tick(heaps, red, step)
        if rep.repaired:
            heaps = dict(heaps, **rep.repaired)
        return red, heaps, rep

    t = time.perf_counter()
    for step in range(steps):
        red, heaps, _ = traffic_step(red, heaps, step, (64, 256)[step % 2])
    red = store.settle(red, heaps, step=steps - 1)
    jax.block_until_ready((heaps, red))
    traffic_s = time.perf_counter() - t
    step = steps
    check(all(same_bits(heaps[n], mirror[n]) for n in names),
          "sharded heaps equal the mirror after traffic")
    check(store.scrub_check(heaps, red) == 0, "sharded scrub is clean")
    labels = tuple(g.label for g in store._protected())
    check(any(isinstance(k[0], tuple) and len(k[0]) > 1
              for k in store._jit_update),
          "a batched multi-group update was dispatched")
    groups = [store.groups[l] for l in labels]
    ladders = [g.engine.queue_ladder for g in groups]
    for what, variants in (
            ("full", ("async_full",) * len(groups)),
            ("top rung", tuple(_queued_variant(k[-1]) if k else "async_full"
                               for k in ladders)),
            ("bottom rung", tuple(_queued_variant(k[0]) if k else "async_full"
                                  for k in ladders))):
        assert_no_collectives(store._build_update_many(
            labels, variants).lower(
            tuple({n: heaps[n] for n in g.names} for g in groups),
            tuple({n: red[n] for n in g.names} for g in groups)),
            f"batched {what} update")

    # Quiet ticks until cross-shard parity covers every heap, then lose one
    # shard of h0 and rebuild it while writes land in that shard.
    red = store.flush(heaps, red, step)
    pat = store.patroller
    for _ in range(400):
        red, _ = store.tick(heaps, red, step, scrub_period=0)
        step += 1
        if all(bool(pat.xpar[n].xvalid.all()) for n in names):
            break
    check(all(bool(pat.xpar[n].xvalid.all()) for n in names),
          "cross-shard parity covers every heap")
    lost = 1
    leaves, red = store.inject(heaps, red, FaultSpec(
        kind="shard_loss", leaf=names[0], block=lost))
    heaps = dict(leaves)
    store.declare_shard_lost(names[0], lost, red)
    status, rebuild_ticks = None, 0
    t = time.perf_counter()
    for _ in range(400):
        red, heaps, rep = traffic_step(red, heaps, step, 64, lost=lost)
        step += 1
        rebuild_ticks += 1
        if rep.rebuild is not None and rep.rebuild.done:
            status = rep.rebuild
            break
    red = store.flush(heaps, red, step)
    heaps = dict(heaps, **store.take_repaired())
    jax.block_until_ready((heaps, red))
    rebuild_s = time.perf_counter() - t
    check(status is not None and status.lost == 0,
          f"shard rebuild finished with nothing lost: {status}")
    check(store.scrub_check(heaps, red) == 0,
          "sharded scrub is clean after the rebuild")
    check(all(same_bits(heaps[n], mirror[n]) for n in names),
          "sharded heaps equal the mirror after the rebuild")
    facts = dict(
        mesh="2x2", heaps=SHARDED_HEAPS, rows_per_heap=rows,
        protected_gib=f"{data_bytes(store) * shards / GiB:.3f}",
        redundancy_gib=f"{red_bytes(red) / GiB:.3f}",
        setup_s=f"{setup_s:.2f}",
        traffic_ms_per_step=f"{traffic_s / steps * 1e3:.3f}",
        rebuild_ticks=rebuild_ticks, rebuild_s=f"{rebuild_s:.2f}",
        patrol_probes_ready=store.counters["patrol.probes_ready"],
        patrol_probes_forced=store.counters["patrol.probes_forced"],
        peak_gib=peak_gib())
    report("sharded", **facts)
    return facts


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); this is a chip run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")
    from repro.common.compile_cache import use_compile_cache
    from repro.configs import get_arch

    cache = use_compile_cache()
    report("start", devices=len(devices), jax=jax.__version__,
           compile_cache=cache)
    if args.chips == 4:
        sharded_phase(seed=args.seed)
    else:
        region_phase(seed=args.seed)
        serve_phase(get_arch("olmo-1b"), seed=args.seed)
        report("train", cut=f"depth only, {get_arch('olmo-1b').n_layers} -> "
                            f"{TRAIN_LAYERS} layers")
        train_phase(dataclasses.replace(get_arch("olmo-1b"),
                                        n_layers=TRAIN_LAYERS),
                    seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
