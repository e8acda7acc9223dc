"""Read the numbers a cell compares, over many seeds, for the program and
for its control, in one process (set-up is paid once per seed, compiles
once per process).  This is how each limit in a configuration's file was
set; the benchmark's own runs never run the control.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 2

One JSON line per run: ``{"seed", "control", "checks", "metrics"}``.
Needs the chips the cell asks for, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool,
             make=None) -> dict:
    spans = harness.Spans()
    runner = harness.runner_for(cell.config)
    r = (make or runner.make)(cell, seed, spans)
    r.control = control
    t0 = time.perf_counter()
    r.setup()
    setup_s = time.perf_counter() - t0
    spans.recording = True
    e2e = r.window(seconds)
    spans.recording = False
    t1 = time.perf_counter()
    checks = r.check()
    r.close()
    del r
    gc.collect()
    return {"seed": seed, "control": control,
            "checks": {c.name: c.value for c in checks},
            "metrics": dict(e2e, setup_s=setup_s,
                            check_s=time.perf_counter() - t1)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    bench_run.require_chips(cell.chips)
    bench_run.use_cache()
    seeds = [(int(s), False) for s in args.seeds.split(",") if s] + \
            [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in seeds:
        print(json.dumps(readings(cell, seed, args.seconds, control)),
              flush=True)


if __name__ == "__main__":
    main()
