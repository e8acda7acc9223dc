"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer readers
use: device-busy intervals, device time per program (XLA module) and per
operation, and the harness's host spans, all on the trace's one clock.

* A TPU device is a plane named ``/device:TPU:<n>``.  Its ``XLA Ops`` line
  holds one event per executed operation; its ``XLA Modules`` line one
  event per program run (``jit_many(<id>)``), and an operation belongs to
  the program run that holds it.  Busy time is the union of the
  operations' intervals (of the programs' where a plane has no
  operations).
* On the CPU backend there is no device plane: operations are host events
  that carry an ``hlo_module`` stat, and count as device ``cpu``.
* Host spans are events named ``bench.<span>`` (``Spans`` with
  ``annotate``); ``bench.window`` bounds the measured window, and every
  other number is clipped to it.
* The device clock in a TPU trace runs behind the host's by a millisecond
  or two.  Each program run carries a ``run_id``, as does the host's
  ``DoEnqueueProgram`` event that launched it; no run starts before its
  launch, so the device's events are shifted by the largest
  ``launch - start`` over its runs.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:([A-Z]+):(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def module_name(name: str) -> str:
    """``jit_many(17)`` -> ``jit_many``: program runs share a name."""
    return _SUFFIX.sub("", name)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Device:
    ops: List[Tuple[str, str, float, float]]   # (module, op, start, end) ns
    modules: List[Tuple[str, float, float]]    # (module, start, end) ns


@dataclasses.dataclass
class Trace:
    window: Interval                           # ns
    devices: Dict[str, Device]
    host: List[Tuple[str, float, float]]       # (span, start, end) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, device: str) -> List[Interval]:
        d = self.devices[device]
        src = ([(s, e) for _, _, s, e in d.ops] if d.ops
               else [(s, e) for _, s, e in d.modules])
        return merge(clip(src, *self.window))

    def busy_s(self, device: str) -> float:
        return sum(e - s for s, e in self.busy(device)) * 1e-9

    def module_s(self, device: str) -> Dict[str, float]:
        """Device seconds per program inside the window: the union of each
        program's operations (its run intervals where no ops exist)."""
        d = self.devices[device]
        per: Dict[str, List[Interval]] = collections.defaultdict(list)
        if d.ops:
            for mod, _, s, e in d.ops:
                per[mod].append((s, e))
        else:
            for mod, s, e in d.modules:
                per[mod].append((s, e))
        return {m: sum(e - s for s, e in merge(clip(iv, *self.window))) * 1e-9
                for m, iv in per.items()}

    def idle_gaps(self, device: str) -> List[Tuple[str, float]]:
        """Each idle gap of ``device`` in the window, labelled with the
        host span that overlaps it most (``none`` where no span does)."""
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy(device):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        spans = sorted((s, e, n) for n, s, e in self.host if n != "window")
        # ends[i]: the latest end of spans[:i + 1]; spans up to the first i
        # with ends[i] > gap start end before this gap and every later one.
        ends, top = [], float("-inf")
        for _, e, _ in spans:
            top = max(top, e)
            ends.append(top)
        out, first = [], 0
        for gs, ge in gaps:
            while first < len(spans) and ends[first] <= gs:
                first += 1
            best, label = 0.0, "none"
            for i in range(first, len(spans)):
                s, e, n = spans[i]
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, n
            out.append((label, (ge - gs) * 1e-9))
        return out


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def reduce_file(path: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd) -> Trace:
    devices: Dict[str, Device] = {}
    starts: Dict[str, Tuple[int, Dict[int, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    host_planes = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if plane.name.startswith("/host:"):
            host_planes.append(plane)
        if not m:
            continue
        dev = Device([], [])
        runs: Dict[int, float] = {}
        for line in plane.lines:
            if line.name == "XLA Ops":
                dev.ops = [("?", ev.name, ev.start_ns, ev.end_ns)
                           for ev in line.events]
            elif line.name == "XLA Modules":
                for ev in line.events:
                    dev.modules.append((module_name(ev.name), ev.start_ns,
                                        ev.end_ns))
                    rid = _stats(ev).get("run_id")
                    if rid is not None:
                        runs[int(rid)] = ev.start_ns
        if dev.ops:
            dev.ops = _ops_into_modules(dev.ops, dev.modules)
        devices[f"{m.group(1).lower()}:{m.group(2)}"] = dev
        starts[f"{m.group(1).lower()}:{m.group(2)}"] = (int(m.group(2)), runs)
    launches: Dict[Tuple[int, int], float] = {}
    cpu_ops: List[Tuple[str, str, float, float]] = []
    for plane in host_planes:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith("bench."):
                    host.append((name[6:], ev.start_ns, ev.end_ns))
                elif name == "DoEnqueueProgram":
                    st = _stats(ev)
                    if "run_id" in st:
                        launches[(int(st.get("device_ordinal", 0)),
                                  int(st["run_id"]))] = ev.start_ns
                elif not devices and ev.duration_ns > 0:
                    st = _stats(ev)
                    if "hlo_module" in st:
                        cpu_ops.append((module_name(str(st["hlo_module"])),
                                        name, ev.start_ns, ev.end_ns))
    for name, (ordinal, runs) in starts.items():
        lags = [launches[(ordinal, r)] - t for r, t in runs.items()
                if (ordinal, r) in launches]
        if lags and max(lags) > 0:
            devices[name] = _shifted(devices[name], max(lags))
    if not devices and cpu_ops:
        devices["cpu:0"] = Device(cpu_ops, [])
    win = [(s, e) for n, s, e in host if n == "window"]
    if win:
        window = (win[0][0], win[0][1])
    else:
        starts = [iv[-2] for d in devices.values() for iv in d.ops + d.modules]
        ends = [iv[-1] for d in devices.values() for iv in d.ops + d.modules]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return Trace(window, devices, host)


def _shifted(dev: Device, by: float) -> Device:
    return Device([(m, o, s + by, e + by) for m, o, s, e in dev.ops],
                  [(m, s + by, e + by) for m, s, e in dev.modules])


def _ops_into_modules(ops, modules):
    """Name each operation's program by the program run that holds it."""
    runs = sorted((s, e, m) for m, s, e in modules)
    out, i = [], 0
    for _, op, s, e in sorted(ops, key=lambda o: o[2]):
        while i < len(runs) and runs[i][1] < s:
            i += 1
        mod = runs[i][2] if i < len(runs) and runs[i][0] <= s else "?"
        out.append((mod, op, s, e))
    return out


def find_xplane(directory: str) -> Optional[str]:
    import glob
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None
