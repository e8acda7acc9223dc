"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (absolute wall numbers are CPU;
cross-mode ratios reproduce the paper's claims). Roofline terms come from
the dry-run artifacts (see repro.launch.dryrun).

Machine-readable output (perf trajectory tracking, see docs/perf.md):

    python -m benchmarks.run --json BENCH_PR2.json            # full sweep
    python -m benchmarks.run --json BENCH_PR2.json --smoke \
        --only insert_throughput,dirty_cost                   # CI artifact

The JSON artifact is ``{"env": {...}, "rows": [{name, us_per_call,
derived}, ...]}`` — one row per CSV line, plus enough environment metadata
to compare artifacts across PRs.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

sys.path.insert(0, "src")

# Per-module kwargs for --smoke (tiny shapes, CI-budget runtimes).
SMOKE_KW = {
    "insert_throughput": dict(steps=6, n_rows=1024),
    "ycsb": dict(steps=6, n_rows=1024, batch=128),
    "op_latency": dict(n_rows=1024),
    "overwrite_scaling": dict(steps=6, n_rows=1024),
    "fio_patterns": dict(steps=6, n_rows=1024, batch=32),
    # fig9a capped at 4096 rows; the fig9c sweep keeps its representative
    # region size (sweep_rows default) even in smoke mode — see dirty_cost.
    "dirty_cost": dict(n_rows=4096, iters=10),
    # The sharded leg keeps its full-size shapes even in smoke mode: the
    # multi-group batching win only shows once per-due-tick update work is
    # non-trivial (see overlap.py), and the leg is ~15 s wall.
    "overlap": dict(steps=120, n_rows=2048, batch=32, repeats=2,
                    sharded_steps=40),
    "battery": dict(n_rows=1024),
    "mttdl_bench": dict(n_rows=1024, steps=12),
    "kernel_bench": dict(nb=128, L=512),
    "scrub_bench": dict(steps=24, n_rows=512, sweep_ticks=8,
                        sharded_steps=8, sharded_rows=128),
    "remesh_bench": dict(steps=12, n_rows=512, read_iters=8,
                         sharded_steps=8, sharded_rows=128),
    "health_bench": dict(steps=60, n_rows=512, batch=32),
}


def _env_metadata(args) -> dict:
    import jax
    dev = jax.devices()[0]
    return {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device": str(dev.device_kind),
        "device_count": jax.device_count(),
        "smoke": bool(args.smoke),
        "only": args.only or None,
    }


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--json", dest="json_path", default=None,
                   help="also write rows + env metadata to this JSON file")
    p.add_argument("--only", default="",
                   help="comma-separated module names (e.g. dirty_cost,ycsb)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes / few iterations (CI budget)")
    p.add_argument("--repeat", type=int, default=1,
                   help="run each module N times, keep the per-row minimum "
                        "us_per_call (scheduler-noise suppression on the "
                        "shared CPU container; a real regression raises "
                        "the minimum too)")
    args = p.parse_args(argv)

    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    from . import (battery, dirty_cost, fio_patterns, health_bench,
                   insert_throughput, kernel_bench, mttdl_bench, op_latency,
                   overlap, overwrite_scaling, remesh_bench, scrub_bench,
                   ycsb)
    from .common import emit

    modules = [
        ("fig1/fig5 insert throughput", insert_throughput),
        ("fig4 ycsb", ycsb),
        ("fig6 op latency", op_latency),
        ("fig7 overwrite scaling", overwrite_scaling),
        ("fig8 fio patterns", fio_patterns),
        ("fig9 dirty-bit cost", dirty_cost),
        ("overlap pipeline", overlap),
        ("sec4.7 battery", battery),
        ("sec4.8 mttdl", mttdl_bench),
        ("scrub patrol + rebuild", scrub_bench),
        ("elastic remesh + degraded reads", remesh_bench),
        ("health governor + breaker recovery", health_bench),
        ("kernel fusion", kernel_bench),
    ]
    selected = {s.strip() for s in args.only.split(",") if s.strip()}
    known = {mod.__name__.rsplit(".", 1)[-1] for _, mod in modules}
    unknown = selected - known
    if unknown:
        p.error(f"unknown --only module(s) {sorted(unknown)}; "
                f"choose from {sorted(known)}")
    all_rows = []
    print("name,us_per_call,derived")
    for title, mod in modules:
        short = mod.__name__.rsplit(".", 1)[-1]
        if selected and short not in selected:
            continue
        kw = SMOKE_KW.get(short, {}) if args.smoke else {}
        t0 = time.time()
        # Best-of-N merge by row name: wall rows (us > 0) keep their
        # fastest repeat, derived-only rows keep the first.  A module that
        # fails fails the run.
        merged: dict = {}
        order: list = []
        for _ in range(max(args.repeat, 1)):
            for name, us, derived in mod.run(**kw):
                if name not in merged:
                    merged[name] = (us, derived)
                    order.append(name)
                elif us > 0 and us < merged[name][0]:
                    merged[name] = (us, derived)
        rows = [(n, *merged[n]) for n in order]
        emit(rows)
        all_rows.extend(rows)
        print(f"# [{title}] {time.time() - t0:.1f}s", file=sys.stderr)

    if args.json_path:
        doc = {
            "env": _env_metadata(args),
            "rows": [{"name": n, "us_per_call": round(float(us), 2),
                      "derived": str(d)} for n, us, d in all_rows],
        }
        with open(args.json_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json_path} ({len(doc['rows'])} rows)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
