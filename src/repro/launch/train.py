"""Training launcher.

Single-host CPU execution for development; the same script drives the
production mesh when run under multi-host JAX (jax.distributed initializes
from the cluster env). Wires together: config -> model -> sharding rules ->
ProtectedStore (per-leaf policies) -> Trainer loop -> checkpoints ->
preemption handler.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 50 --redundancy vilamb --period 8
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 20 --redundancy sync --inject-corruption 10

Per-leaf policies (params sync-protected, Adam moments amortized):
  ... --policy "params/*=sync,m/*=vilamb:16,v/*=vilamb:16" \
      --max-vulnerable-steps 64
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np


def inject_corruption(trainer, store, state):
    """SDC demonstration: flip bits in one protected block outside the
    vulnerability window, then scrub -> parity repair -> re-scrub.

    Returns the flushed state (its own leaves stay intact; the corruption
    lands in a copy) and the counts ``detected``, ``repaired``,
    ``unrecoverable`` and ``residual``.
    """
    from repro.core import blocks as B
    from repro.train import protected_leaves

    state = trainer.flush(state)  # make everything clean/covered
    leaves = protected_leaves(state.params, state.opt)
    name = sorted(store.protected_metas)[0]
    meta = store.metas[name]
    lanes = B.to_lanes(leaves[name], meta)
    lanes = lanes.at[0, 0].add(np.uint32(0xDEAD))
    leaves[name] = B.from_lanes(lanes, meta)
    mm = store.scrub(leaves, state.red)
    n_bad = int(sum(int(v.sum()) for v in jax.tree.leaves(mm)))
    repaired, fixed, lostn = store.repair(leaves, state.red, mm)
    mm2 = store.scrub(repaired, state.red)
    n_after = int(sum(int(v.sum()) for v in jax.tree.leaves(mm2)))
    return state, {"detected": n_bad, "repaired": fixed,
                   "unrecoverable": lostn, "residual": n_after}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--redundancy", default="vilamb", choices=["none", "sync", "vilamb"])
    ap.add_argument("--period", type=int, default=8)
    ap.add_argument("--scrub-period", type=int, default=32)
    ap.add_argument("--policy", default="",
                    help='per-leaf rules "pattern=mode[:period],..." '
                         "(fnmatch over params/... m/... v/... paths)")
    ap.add_argument("--max-vulnerable-steps", type=int, default=0,
                    help="freshness deadline: force an update after this "
                         "many steps regardless of period/back-off")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-corruption", type=int, default=0,
                    help="flip bits in a random block at this step (demo)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import get_arch, get_smoke
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.data import SyntheticPipeline
    from repro.models import build_model
    from repro.models.config import ShapeConfig
    from repro.optim import AdamW, warmup_cosine
    from repro.train import Trainer, protected_structs
    from repro.ckpt import CheckpointManager, PreemptionHandler

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    model = build_model(cfg)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    data = SyntheticPipeline(cfg, shape, seed=0)
    opt = AdamW(lr=warmup_cosine(args.lr, 10, args.steps),
                moment_dtype=cfg.moment_dtype)

    store = None
    if args.redundancy != "none" or args.policy:
        params0 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt0 = jax.eval_shape(opt.init, params0)
        policy = RedundancyPolicy.from_spec(
            args.policy, default_mode=args.redundancy,
            period_steps=args.period, scrub_period_steps=args.scrub_period,
            max_vulnerable_steps=args.max_vulnerable_steps)
        store = ProtectedStore(policy).attach(protected_structs(params0, opt0))

    trainer = Trainer(model=model, opt=opt, store=store,
                      scrub_period_steps=args.scrub_period)
    handler = PreemptionHandler().install()
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    state = None
    if ckpt is not None and args.resume:
        struct = jax.eval_shape(lambda: trainer.init_state(jax.random.PRNGKey(0)))
        # Verified restore: scrub against the persisted redundancy and
        # parity-repair single-block corruption before resuming.
        state = ckpt.restore_verified(struct, store)
        if state is not None:
            print(f"[train] resumed from step {int(state.step)}")
    if state is None:
        state = trainer.init_state(jax.random.PRNGKey(0))

    t_start = time.perf_counter()
    done = 0
    while done < args.steps:
        def on_step(st, metrics):
            nonlocal done
            done += 1
            s = int(st.step)
            if s % args.log_every == 0:
                print(f"[train] step {s} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")
            if ckpt is not None and args.ckpt_every and s % args.ckpt_every == 0:
                ckpt.save(s, st, blocking=False)

        chunk = min(args.steps - done, 10)
        state = trainer.run(state, data, chunk, on_step=on_step)

        # Demonstration: SDC injection -> scrub detect -> parity repair.
        if args.inject_corruption and done >= args.inject_corruption and store:
            args.inject_corruption = 0
            state, c = inject_corruption(trainer, store, state)
            print(f"[vilamb] injected corruption: detected={c['detected']} "
                  f"repaired={c['repaired']} unrecoverable={c['unrecoverable']} "
                  f"residual={c['residual']}")

        if handler.requested:
            state = handler.drain(trainer, state, ckpt)
            print(f"[train] preempted: flushed in {handler.flush_seconds:.3f}s, "
                  f"checkpointed at step {int(state.step)}")
            sys.exit(handler.exit_code)

    dt = time.perf_counter() - t_start
    print(f"[train] done: {args.steps} steps in {dt:.1f}s "
          f"({args.steps * shape.seq_len * shape.global_batch / dt:.0f} tok/s) "
          f"alarms={trainer.corruption_alarms}")
    if ckpt is not None:
        state = trainer.flush(state)
        ckpt.save(int(state.step), state, blocking=True)


if __name__ == "__main__":
    main()
