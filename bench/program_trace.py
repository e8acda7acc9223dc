"""Program spans: the library's own host spans in a profile, and each device
program run put down to the span that launched it.

With ``repro.core.trace.enable(True)`` the store names its host work as
``vilamb.<span>`` profiler annotations (``vilamb.tick``,
``vilamb.tick.dispatch``, ``vilamb.patrol.probe``, ``vilamb.wait.<site>``,
...).  :func:`reduce_program_file` reduces a profile to a
:class:`ProgramTrace`: the :class:`bench.trace_reduce.Trace` of the same
file, plus every ``vilamb.*`` and ``bench.*`` host event with its thread,
and every device program run with its launch.

* A run's ``run_id`` matches the host's ``DoEnqueueProgram`` event that
  enqueued it.  The runtime's flow events lead from that enqueue back to
  the Python call that caused it (``PJRT_LoadedExecutable_Execute
  linkage``, on the calling thread); the innermost ``vilamb.*`` or
  ``bench.*`` span open there is the span that launched the run, whatever
  the program's module is called.  An enqueue can come long after its
  call: on a TPU v5e most of the store's update passes were enqueued
  while the tick had moved on to the patroller.
* Device times are shifted onto the host clock as ``trace_reduce`` does.

``python3 bench/program_trace.py --workload <name> --seed <n> --seconds <s>``
runs one cell as ``bench/run.py`` does and prints its result line, with
``diagnostics`` holding the window's change of every store counter under
``store.``.  ``--trace 1`` also profiles the window with the spans on and
adds ``per_layer`` (the cell's metrics and those of
``bench/program_metrics.json``) and ``breakdown`` with
``idle_gaps_program``.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import dataclasses
import json
import os
import pathlib
import shutil
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Trace, merge, clip  # noqa: E402

KEPT = ("vilamb.", "bench.")
TICK_CHILDREN = ("vilamb.tick.schedule", "vilamb.tick.dispatch",
                 "vilamb.tick.scrub", "vilamb.patrol", "vilamb.remesh")
METRICS = ROOT / "bench" / "program_metrics.json"

Span = Tuple[str, float, float, int]          # (name, start, end, thread)
Flow = Tuple[int, Optional[int]]              # (flow id, flow type)


def _matches(name: str, prefix: str) -> bool:
    return name == prefix or name.startswith(prefix + ".")


def innermost(spans: Sequence[Tuple[str, float, float]],
              times: Sequence[float]) -> List[Optional[Tuple[str, float]]]:
    """For each time, ``(name, start)`` of the innermost of one thread's
    nested ``spans`` open at it, or None."""
    order = sorted(range(len(times)), key=times.__getitem__)
    ev = sorted(spans, key=lambda s: s[1])
    out: List[Optional[Tuple[str, float]]] = [None] * len(times)
    stack: list = []
    i = 0
    for q in order:
        t = times[q]
        while i < len(ev) and ev[i][1] <= t:
            stack.append(ev[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        if stack:
            out[q] = (stack[-1][0], stack[-1][1])
    return out


@dataclasses.dataclass
class ProgramTrace(Trace):
    spans: List[Span] = dataclasses.field(default_factory=list)
    # device -> [(module, start, end, launching span or "none")], shifted
    runs: Dict[str, List[Tuple[str, float, float, str]]] = \
        dataclasses.field(default_factory=dict)

    def named(self, prefix: str) -> List[Span]:
        lo, hi = self.window
        return [s for s in self.spans
                if _matches(s[0], prefix) and lo <= s[1] < hi]

    def count(self, name: str) -> int:
        return sum(1 for s in self.named(name) if s[0] == name)

    def span_s(self, name: str) -> float:
        return sum(e - s for n, s, e, _ in self.named(name)
                   if n == name) * 1e-9

    def nested_s(self, prefix: str, parent: str) -> float:
        """Seconds of the spans matching ``prefix`` that lie inside a
        ``parent`` span on the same thread."""
        by_thread = collections.defaultdict(list)
        for n, s, e, th in self.named(parent):
            if n == parent:
                by_thread[th].append((s, e))
        for iv in by_thread.values():
            iv.sort()
        total = 0.0
        for n, s, e, th in self.named(prefix):
            iv = by_thread.get(th)
            if not iv:
                continue
            j = bisect.bisect_right(iv, (s, float("inf"))) - 1
            if j >= 0 and iv[j][1] >= e:
                total += e - s
        return total * 1e-9

    def launched_by(self, device: str, prefix: str) -> float:
        """Device seconds of the program runs launched inside the innermost
        span matching ``prefix`` (``vilamb.patrol`` matches
        ``vilamb.patrol.probe``), over the window."""
        busy = self._run_busy(device)
        return sum(b for (m, s, e, by), b in zip(self.runs.get(device, ()),
                                                 busy)
                   if _matches(by, prefix)) * 1e-9

    def _run_busy(self, device: str) -> List[float]:
        """Per run, ns of its operations' union in the window (the run's
        interval where the device records no operations)."""
        runs = self.runs.get(device, [])
        ops = sorted(self.devices[device].ops, key=lambda o: o[2]) \
            if device in self.devices else []
        if not ops:
            return [sum(e - s for s, e in clip([(s, e)], *self.window))
                    for _, s, e, _ in runs]
        order = sorted(range(len(runs)), key=lambda r: runs[r][1])
        per: List[list] = [[] for _ in runs]
        k = 0
        for _, _, s, e in ops:
            while k < len(order) and runs[order[k]][2] < s:
                k += 1
            if k < len(order) and runs[order[k]][1] <= s:
                per[order[k]].append((s, e))
        return [sum(e - s for s, e in merge(clip(iv, *self.window)))
                for iv in per]

    def idle_gaps_program(self, device: str) -> List[Tuple[str, float]]:
        """The idle time of ``device`` in the window, split at span
        boundaries and put down to the innermost span open on the calling
        thread at each instant: ``(label, seconds)`` pieces, labelled with
        the library's span (``vilamb.tick.dispatch``), else the harness's
        (``read``, as ``idle_gaps`` names it), else ``none``.  Whole-gap
        labels would put a gap that covers a tick down to ``vilamb.tick``
        alone, whatever part of the tick it fell in."""
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy(device):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        segs = _segments([(s, e, n) for n, s, e, th in self.spans
                          if th == self._caller() and n != "bench.window"])
        out, k = [], 0
        for gs, ge in gaps:
            while k < len(segs) and segs[k][1] <= gs:
                k += 1
            covered = 0.0
            for s, e, n in segs[k:]:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    covered += ov
                    out.append((n[6:] if n.startswith("bench.") else n,
                                ov * 1e-9))
            if ge - gs > covered:
                out.append(("none", (ge - gs - covered) * 1e-9))
        return out

    def _caller(self) -> Optional[int]:
        """The thread that drives the loop: the one holding the window
        span (else the first tick)."""
        for name in ("bench.window", "vilamb.tick"):
            for n, _, _, th in self.spans:
                if n == name:
                    return th
        return None


def _segments(spans) -> List[Tuple[float, float, str]]:
    """One thread's nested spans as a flat partition of the time they
    cover: ``(start, end, innermost span)``, sorted."""
    out: List[Tuple[float, float, str]] = []
    stack: list = []
    t = float("-inf")

    def close(until):
        nonlocal t
        while stack and stack[-1][1] <= until:
            _, end, name = stack.pop()
            if t < end:
                out.append((t, end, name))
                t = end

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1],
                                                x[2].startswith("vilamb."))):
        close(s)
        if stack and t < s:
            out.append((t, s, stack[-1][2]))
        t = max(t, s)
        stack.append((s, e, n))
    close(float("inf"))
    return out


def reduce_program_file(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    return reduce_program_profile(ProfileData.from_file(path))


def reduce_program_profile(pd) -> ProgramTrace:
    base = trace_reduce.reduce_profile(pd)
    runs: Dict[int, List[Tuple[int, str, float, float]]] = {}
    names: Dict[int, str] = {}
    threads: Dict[int, List[Tuple[str, float, float]]] = {}
    consumers: Dict[int, List[Tuple[Flow, float, float]]] = {}
    producers: Dict[Flow, Tuple[int, float]] = {}
    launches: Dict[Tuple[int, int], Tuple[int, float]] = {}
    th = 0
    for plane in pd.planes:
        m = trace_reduce._DEVICE.match(plane.name)
        if m:
            ordinal = int(m.group(2))
            names[ordinal] = f"{m.group(1).lower()}:{ordinal}"
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for ev in line.events:
                    rid = trace_reduce._stats(ev).get("run_id")
                    if rid is not None:
                        runs.setdefault(ordinal, []).append(
                            (int(rid), trace_reduce.module_name(ev.name),
                             ev.start_ns, ev.end_ns))
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            th += 1
            for ev in line.events:
                if ev.name.startswith(KEPT):
                    threads.setdefault(th, []).append(
                        (ev.name, ev.start_ns, ev.end_ns))
                    continue
                st = trace_reduce._stats(ev)
                if "_c" in st:
                    consumers.setdefault(th, []).append(
                        ((int(st["_c"]), st.get("_ct")), ev.start_ns,
                         ev.end_ns))
                # A flow out of an enqueue ends in that run's completion
                # callbacks: it leads to no later call.
                if "_p" in st and ev.name != "DoEnqueueProgram":
                    producers[(int(st["_p"]), st.get("_pt"))] = \
                        (th, ev.start_ns)
                if ev.name == "DoEnqueueProgram" and "run_id" in st:
                    launches[(int(st.get("device_ordinal", 0)),
                              int(st["run_id"]))] = (th, ev.start_ns)
    spans = [(n, s, e, t) for t, evs in threads.items() for n, s, e in evs]
    out: Dict[str, List[Tuple[str, float, float, str]]] = {}
    for ordinal, rs in runs.items():
        lags = [launches[(ordinal, r)][1] - s for r, _, s, _ in rs
                if (ordinal, r) in launches]
        by = max(lags) if lags and max(lags) > 0 else 0.0
        sites = [launches.get((ordinal, r)) for r, *_ in rs]
        labels = _launching_spans(sites, threads, consumers, producers)
        out[names[ordinal]] = [(mod, s + by, e + by, label)
                               for (_, mod, s, e), label in zip(rs, labels)]
    return ProgramTrace(base.window, base.devices, base.host, spans, out)


def _launching_spans(sites, threads, consumers, producers) -> List[str]:
    """For each launch site ``(thread, time)`` (None where the launch is
    not in the profile), the innermost kept span open at the call that
    caused it.  The runtime may enqueue a program on another thread or
    later than the call (a continuation); its flow events link each
    enqueue back: an event that consumes flow ``_c`` of type ``_ct`` was
    caused by the event producing ``_p == _c`` of type ``_pt == _ct`` (an
    id is unique only within its type: on a TPU v5e the caller's linkage
    flows reuse the runtime's ids).  The walk follows the innermost
    consuming event around the site to its producer until none is left:
    the caller's ``PJRT_LoadedExecutable_Execute linkage``, on the calling
    thread, inside the span that made the call."""
    sites = list(sites)
    active = [i for i, s in enumerate(sites) if s is not None]
    for _ in range(16):
        by_line = collections.defaultdict(list)
        for i in active:
            by_line[sites[i][0]].append(i)
        active = []
        for th, idx in by_line.items():
            found = innermost(consumers.get(th, ()),
                              [sites[i][1] for i in idx])
            for i, f in zip(idx, found):
                if f is not None and f[0] in producers:
                    sites[i] = producers[f[0]]
                    active.append(i)
        if not active:
            break
    out = ["none"] * len(sites)
    by_line = collections.defaultdict(list)
    for i, site in enumerate(sites):
        if site is not None:
            by_line[site[0]].append(i)
    for th, idx in by_line.items():
        found = innermost(threads.get(th, ()), [sites[i][1] for i in idx])
        for i, f in zip(idx, found):
            if f is not None:
                out[i] = f[0]
    return out


def has_program_spans(t) -> bool:
    """Does ``t`` hold the library's spans?  (Duck-typed: this module may
    also run as ``__main__``.)"""
    return hasattr(t, "launched_by") and any(
        s[0].startswith("vilamb.") for s in t.spans)


def per_tick_ms(t, seconds: float) -> Optional[float]:
    n = t.count("vilamb.tick")
    return seconds / n * 1e3 if n else None


def busiest(ctx, prefix: str) -> float:
    """Device seconds launched by ``prefix`` on the busiest device."""
    devs = sorted(ctx.trace.devices)[:ctx.n_devices]
    return max((ctx.trace.launched_by(d, prefix) for d in devs), default=0.0)


# ------------------------------------------------------------------- runs
class Probed:
    """A cell's run whose window also records the store's counters and,
    with ``profile``, a profile of the window with the spans on."""

    def __init__(self, run, spans, profile: bool, trace_dir: pathlib.Path):
        self.run, self.spans = run, spans
        self.profile, self.trace_dir = profile, trace_dir
        self.counters_at_end: dict = {}

    def __getattr__(self, name):
        return getattr(self.run, name)

    def window(self, seconds: float):
        import jax
        from repro.core import trace
        c = self.run.store.counters
        for k in c:             # the window's longest wait, not set-up's
            if k.endswith(".max_ms"):
                c[k] = 0.0
        before = dict(c)
        trace.enable(self.profile)
        ann = contextlib.nullcontext()
        if self.profile:
            self.spans.annotate = True
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
            ann = jax.profiler.TraceAnnotation("bench.window")
        try:
            with ann:
                e2e = self.run.window(seconds)
        finally:
            if self.profile:
                jax.profiler.stop_trace()
                self.spans.annotate = False
            trace.enable(False)
        self.run.counters.update(
            {f"store.{k}": v if k.endswith(".max_ms") else
             v - before.get(k, 0) for k, v in c.items()})
        self.counters_at_end = dict(self.run.counters, **self.run.work())
        return e2e


def read_program(cell, probed: Probed, peaks: dict) -> dict:
    """Per-layer metrics and breakdown of a profiled window."""
    from bench import harness
    from bench import run as bench_run
    path = trace_reduce.find_xplane(str(probed.trace_dir))
    if path is None:
        return {}
    t = reduce_program_file(path)
    shutil.rmtree(probed.trace_dir, ignore_errors=True)
    counters = probed.counters_at_end
    ctx = harness.Context(cell, peaks, cell.chips, counters["window_s"],
                          probed.spans, dict(counters), t,
                          harness.load_layers())
    extra = [m for m in json.loads(METRICS.read_text())
             if cell.name in m.get("workloads", ())]
    per_layer = bench_run.read_per_layer(cell, ctx)
    for m in extra:
        v = harness.reader_for(m["name"]).read(ctx, m["name"])
        if v is not None:
            per_layer[m["name"]] = float(v)
    # The tick's own time, outside its child spans (what they leave out).
    own = per_tick_ms(t, t.span_s("vilamb.tick")
                      - sum(t.span_s(c) for c in TICK_CHILDREN))
    out = {"per_layer": per_layer, "tick_self_ms": own}
    if t.devices:
        bd = harness.breakdown(t, cell.chips)
        devs = sorted(t.devices)[:cell.chips]
        gaps: Dict[str, float] = {}
        for d in devs:
            for label, s in t.idle_gaps_program(d):
                gaps[label] = gaps.get(label, 0.0) + s / len(devs)
        bd["idle_gaps_program"] = sorted(
            ([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = bd
        out["busy"] = harness.busy_window(t, cell.chips)
        # Device seconds by program and launching span, busiest device.
        dev = max(devs, key=t.busy_s)
        table: Dict[str, Dict[str, float]] = {}
        for (mod, _, _, by), ns in zip(t.runs.get(dev, ()), t._run_busy(dev)):
            row = table.setdefault(mod, {})
            row[by] = row.get(by, 0.0) + ns * 1e-9
        out["launches"] = table
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    from bench import run as bench_run
    cell = harness.load_cell(args.workload)
    peaks = bench_run.require_chips(cell.chips)
    bench_run.use_cache()
    made: List[Probed] = []
    trace_dir = bench_run.TRACE_DIR / f"program.{cell.name}.{os.getpid()}"

    def make(cell_, seed, spans):
        run = harness.runner_for(cell_.config).make(cell_, seed, spans)
        made.append(Probed(run, spans, bool(args.trace), trace_dir))
        return made[-1]

    result = bench_run.run_cell(cell, args.seed, args.seconds, False, peaks,
                                make_run=make)
    if args.trace:
        result.update(read_program(cell, made[0], peaks))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
