"""What every cell shares: its files by name, host spans, the device, the
per-layer readers and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: ``BENCHMARK.json`` gives the configuration's file,
``bench/traffic/<traffic>.json`` the mix, ``bench/runners/<runner>.py`` the
code that drives a configuration's kind, and ``bench/metrics/<base>.py`` the
reader of every per-layer metric whose name starts with ``<base>`` (up to
the first ``.``, which names the family of cells it is read in).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import pathlib
import time
from typing import Any, Dict, List, Optional, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
GiB = float(1 << 30)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    return make_cell(name, cfg["file"], w["traffic"], int(w["chips"]), bench)


def make_cell(name: str, config_file: str, traffic: str, chips: int,
              bench: Optional[dict] = None) -> Cell:
    """A cell from its files; its metrics are the entries of ``bench``
    (``BENCHMARK.json``) that apply to ``name``."""
    bench = bench or load_benchmark()
    config = json.loads((ROOT / config_file).read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, chips, config, mix, e2e, layer)


def runner_for(config: dict):
    return importlib.import_module(f"bench.runners.{config['runner']}")


def reader_for(metric_name: str):
    return importlib.import_module(
        f"bench.metrics.{metric_name.split('.', 1)[0]}")


class Spans:
    """Host spans around the calls into each layer, on ``perf_counter``.

    With ``annotate`` each span is also a ``jax.profiler.TraceAnnotation``
    named ``bench.<name>``, so the device trace can attribute its idle gaps
    to what the host was doing.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.recording = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.recording:
            yield
            return
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.spans.setdefault(name, []).append((t0, time.perf_counter()))

    def durations(self, name: str) -> List[float]:
        return [b - a for a, b in self.spans.get(name, [])]


class Compiles:
    """Counts the programs this process compiles or loads from JAX's
    persistent cache (``count``) and those it had to compile because the
    cache missed (``misses``), so a run can show that none fell inside its
    window."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1

    @property
    def misses(self) -> int:
        return self.count - self.hits


@dataclasses.dataclass
class Context:
    """What a per-layer reader sees after the window."""
    cell: Cell
    peaks: dict
    n_devices: int
    window_s: float
    spans: Spans
    counters: Dict[str, Any]
    trace: Optional[Any] = None        # bench.trace_reduce.Trace
    layers: Dict[str, List[str]] = dataclasses.field(default_factory=dict)


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": int(max(peaks)) if peaks else None}


def metric_line(metrics: List[dict], values: Dict[str, Optional[float]]
                ) -> Dict[str, dict]:
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics if values.get(m["name"]) is not None}


def load_layers() -> Dict[str, List[str]]:
    """``bench/layers.json``: which device programs (XLA module names, as
    regular expressions) belong to which layer."""
    return json.loads((BENCH / "layers.json").read_text())


def layer_seconds(ctx: Context, layers) -> Dict[str, float]:
    """Device seconds per device of the programs of ``layers``."""
    import re
    pats = [re.compile(p) for layer in layers
            for p in ctx.layers.get(layer, ())]
    out = {}
    for dev in ctx.trace.devices:
        out[dev] = sum(s for mod, s in ctx.trace.module_s(dev).items()
                       if any(p.search(mod) for p in pats))
    return out


def busy_window(trace, chips: int) -> dict:
    if trace is None or not trace.devices:
        return {}
    devs = sorted(trace.devices)[:chips]
    return {"busy_s": sum(trace.busy_s(d) for d in devs) / len(devs),
            "window_s": trace.window_s}


def breakdown(trace, chips: int, top: int = 10) -> dict:
    """The programs that took most device time and the idle time by what
    the host was doing, each averaged over the cell's devices."""
    devs = sorted(trace.devices)[:chips]
    mods: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for d in devs:
        for m, s in trace.module_s(d).items():
            mods[m] = mods.get(m, 0.0) + s / len(devs)
        for label, s in trace.idle_gaps(d):
            gaps[label] = gaps.get(label, 0.0) + s / len(devs)
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"device_ops": rank(mods), "idle_gaps": rank(gaps)}
