"""Serving launcher: batched prefill + decode with Vilamb-protected KV cache.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
      --batch 4 --prompt-len 32 --gen 64 --redundancy vilamb --period 16

Per-leaf policies (e.g. protect K pages harder than V pages):
  ... --policy "*/k=vilamb:8,*/v=vilamb:64" --max-vulnerable-steps 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--redundancy", default="vilamb", choices=["none", "sync", "vilamb"])
    ap.add_argument("--period", type=int, default=16)
    ap.add_argument("--scrub-every", type=int, default=16)
    ap.add_argument("--policy", default="",
                    help='per-leaf rules "pattern=mode[:period],..." '
                         "(fnmatch over flat cache paths)")
    ap.add_argument("--max-vulnerable-steps", type=int, default=0,
                    help="freshness deadline: force an update after this "
                         "many decode steps regardless of period")
    args = ap.parse_args(argv)

    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.common import flatten_dict
    from repro.configs import get_arch, get_smoke
    from repro.core import ProtectedStore, RedundancyPolicy
    from repro.models import build_model
    from repro.serve import Server

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.gen + 1

    key = jax.random.PRNGKey(7)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)}
    if cfg.frontend == "vision":
        batch["frontend"] = jax.random.normal(
            key, (args.batch, cfg.frontend_len, cfg.d_model), jnp.float32)
    if cfg.enc_dec:
        batch["enc_input"] = jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model), jnp.float32)

    store = None
    if args.redundancy != "none" or args.policy:
        caches0 = jax.eval_shape(
            lambda: model.init_caches(args.batch, max_len,
                                      args.prompt_len if cfg.enc_dec else 0))
        policy = RedundancyPolicy.from_spec(
            args.policy, default_mode=args.redundancy,
            period_steps=args.period,
            max_vulnerable_steps=args.max_vulnerable_steps)
        store = ProtectedStore(policy).attach(flatten_dict(caches0))

    srv = Server(model=model, store=store, max_len=max_len)
    t0 = time.perf_counter()
    tokens, stats = srv.generate(params, batch, args.gen,
                                 scrub_every=args.scrub_every)
    dt = time.perf_counter() - t0
    print(f"[serve] generated {tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s) "
          f"scrub mismatches={stats['mismatches']}")
    print("[serve] first sequence:", tokens[0, :16].tolist())


if __name__ == "__main__":
    main()
