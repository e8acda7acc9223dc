import os
# A CPU tool: 512 virtual host devices stand in for the production mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Perf hillclimbing driver (EXPERIMENTS.md §Perf).

Runs tagged dry-run variants of one cell with config/knob overrides and
reports the three roofline terms vs the baseline, so each
hypothesis -> change -> measure -> validate iteration is one command:

  PYTHONPATH=src python -m repro.launch.hillclimb --arch glm4-9b \
      --shape train_4k --variant accum=1 --variant remat=none --tag noaccum
"""
import argparse
import dataclasses
import json
import pathlib

from repro.configs import get_arch
from repro.launch.dryrun import RESULTS, run_cell

PERF_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "perf"

KNOB_TYPES = {
    "accum": int, "capacity_factor": float, "remat": str, "seq_parallel": lambda s: s == "true",
    "attn_tile": int, "moe_every": int, "expand": int, "param_dtype": str,
    "moment_dtype": str, "top_k": int, "norm_vjp": str,
    "attn_kv_gather_first": lambda s: s == "true",
    "bf16_grad_boundaries": lambda s: s == "true",
    "opt_grad_barrier": lambda s: s == "true",
}


def parse_variant(kvs):
    cfg_kw, accum = {}, None
    for kv in kvs:
        k, _, v = kv.partition("=")
        cast = KNOB_TYPES.get(k, str)
        if k == "accum":
            accum = int(v)
        else:
            cfg_kw[k] = cast(v)
    return cfg_kw, accum


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--mode", default="vilamb")
    ap.add_argument("--variant", action="append", default=[],
                    help="knob=value (repeatable); e.g. accum=1 remat=none")
    ap.add_argument("--tag", required=True)
    args = ap.parse_args()

    cfg_kw, accum = parse_variant(args.variant)
    cfg = get_arch(args.arch)
    if cfg_kw:
        cfg = dataclasses.replace(cfg, **cfg_kw)

    PERF_DIR.mkdir(parents=True, exist_ok=True)
    rec = run_cell(args.arch, args.shape, args.mesh == "multi", mode=args.mode,
                   out_dir=PERF_DIR, tag=args.tag, cfg_override=cfg, accum=accum)

    base_file = RESULTS / f"{args.arch}__{args.shape}__{args.mesh}.json"
    base = json.loads(base_file.read_text()) if base_file.exists() else None
    rl = rec["roofline"]
    print(f"\n=== {args.arch} {args.shape} {args.mesh} [{args.tag}] "
          f"variant={args.variant} ===")
    print(f"compute {rl['compute_s']:.3f}s  memory {rl['memory_s']:.3f}s  "
          f"collective {rl['collective_s']:.3f}s  bottleneck={rl['bottleneck']}  "
          f"frac={rl['roofline_fraction']:.4f}  fits={rec.get('fits_16g')}")
    if base and base["status"] == "ok":
        b = base["roofline"]
        for term in ("compute_s", "memory_s", "collective_s"):
            delta = (rl[term] - b[term]) / max(b[term], 1e-12) * 100
            print(f"  {term:13s} {b[term]:8.3f} -> {rl[term]:8.3f}  ({delta:+.1f}%)")
        print(f"  frac          {b['roofline_fraction']:.4f} -> "
              f"{rl['roofline_fraction']:.4f}")


if __name__ == "__main__":
    main()
