"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``) names its
configuration, traffic mix and chips.  The run builds the system from the
seed, warms every program the window uses (``setup_s``), measures for
``--seconds``, then checks what the window produced against the plain
references.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device`` and, traced, ``breakdown``; ``diagnostics`` (the run's own
counters, such as programs compiled or loaded in set-up and in the window);
``checks`` (each compared number with its limit) comes last, and the same
checks end stderr.

Without a TPU, with fewer chips than the cell asks for, or on a chip that
``bench/peaks.py`` does not know, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.peaks import UnknownDevice, peaks_for  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def require_chips(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    try:
        return peaks_for(devs[0].device_kind)
    except UnknownDevice as e:
        fail(str(e))


def use_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout; the
    library's own cache helper takes the directory given to it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from repro.common.compile_cache import use_compile_cache
    jax.config.update("jax_compilation_cache_dir", use_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = harness.reader_for(m["name"]).read(ctx, m["name"])
        if v is not None:
            out[m["name"]] = float(v)
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, peaks: dict,
             make_run=None) -> dict:
    """Set up, measure and check one cell in this process; returns the
    result object (the caller has made sure the chips are there)."""
    t_start = time.perf_counter()
    compiles = harness.Compiles()
    spans = harness.Spans(annotate=trace)
    run = (make_run or harness.runner_for(cell.config).make)(cell, seed, spans)
    run.setup()
    setup_s = time.perf_counter() - t_start
    in_setup, missed_in_setup = compiles.count, compiles.misses

    import jax
    trace_dir = TRACE_DIR / f"{cell.name}.{os.getpid()}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    spans.recording = True
    before = compiles.count
    with spans("window"):
        e2e = run.window(seconds)
    spans.recording = False
    run.counters.update(compiles_in_setup=in_setup,
                        cache_misses_in_setup=missed_in_setup,
                        compiles_in_window=compiles.count - before)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
    device = harness.device_info(cell.chips)
    if trace:
        from bench.trace_reduce import find_xplane, reduce_file
        path = find_xplane(str(trace_dir))
        reduced = reduce_file(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = harness.Context(cell, peaks, cell.chips, run.counters["window_s"],
                          spans, dict(run.counters), reduced,
                          harness.load_layers())
    ctx.counters.update(run.work())

    checks = run.check()
    run.counters["memory_peak_after_check_bytes"] = harness.device_info(
        cell.chips)["memory_peak_bytes"] or 0
    values = dict(e2e, setup_s=setup_s)
    if device["memory_peak_bytes"]:
        values["peak_hbm_gib"] = device["memory_peak_bytes"] / harness.GiB
    if trace:
        metrics = harness.metric_line(cell.per_layer,
                                      read_per_layer(cell, ctx))
        device.update(harness.busy_window(reduced, cell.chips))
    else:
        metrics = harness.metric_line(cell.end_to_end, values)
    result = {"correct": all(c.ok for c in checks),
              "attempted": run.attempted(), "failed": run.failed(),
              "metrics": metrics, "device": device}
    if trace and reduced is not None and reduced.devices:
        result["breakdown"] = harness.breakdown(reduced, cell.chips)
    result["diagnostics"] = {k: v for k, v in run.counters.items()
                             if isinstance(v, (int, float))}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    run.close()
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    peaks = require_chips(cell.chips)
    use_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks)
    print("diagnostics " + json.dumps(result["diagnostics"]), file=sys.stderr)
    for name, c in result["checks"].items():
        bad = "" if c["value"] <= c["limit"] else "  FAILED"
        print(f"check {name} = {c['value']} (limit {c['limit']}){bad}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
