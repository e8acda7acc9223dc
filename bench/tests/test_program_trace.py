"""Program spans in a profile: launch attribution, nesting, idle gaps by
program span, the readers of the proposed metrics, and a profiled window
of a small kv cell on the CPU."""
import pathlib

import pytest

from bench import harness
from bench import program_trace as PT
from bench import trace_reduce as T
from bench.metrics import (alg1_roofline, background_device_ms,
                           patrol_host_ms, tick_dispatch_ms, tick_wait_ms)

DATA = pathlib.Path(__file__).parent / "data"
NEW = (tick_wait_ms, tick_dispatch_ms, patrol_host_ms, background_device_ms,
       alg1_roofline)


def _trace():
    dev = T.Device(ops=[("jit_many", "a", 10, 20), ("jit_local", "b", 30, 40),
                        ("jit_many", "c", 60, 70), ("jit_x", "d", 100, 105)],
                   modules=[])
    spans = [("vilamb.tick", 0, 50, 1), ("vilamb.tick.schedule", 1, 5, 1),
             ("vilamb.wait.resolve", 2, 4, 1),
             ("vilamb.tick.dispatch", 5, 8, 1), ("vilamb.patrol", 8, 50, 1),
             ("vilamb.patrol.probe", 9, 20, 1),
             ("vilamb.wait.probe", 25, 45, 1),
             ("vilamb.resolver.fetch", 5, 60, 2),
             ("vilamb.tick", 55, 100, 1), ("vilamb.tick.dispatch", 56, 58, 1),
             ("vilamb.wait.resolve", 70, 75, 2)]
    runs = {"tpu:0": [("jit_many", 10, 20, "vilamb.tick.dispatch"),
                      ("jit_local", 30, 40, "vilamb.patrol.probe"),
                      ("jit_many", 60, 70, "vilamb.tick.dispatch"),
                      ("jit_x", 100, 105, "bench.read")]}
    host = [("window", 0, 120), ("tick", 0, 50), ("tick", 55, 100),
            ("read", 104, 120)]
    spans += [("bench." + n, s, e, 1) for n, s, e in host]
    return PT.ProgramTrace((0, 120), {"tpu:0": dev}, host, spans, runs)


def test_innermost_open_span():
    spans = [("a", 0, 100), ("b", 10, 20), ("c", 12, 15), ("d", 30, 40)]
    got = PT.innermost(spans, [13, 5, 25, 35, 120, 20])
    assert got == [("c", 12), ("a", 0), ("a", 0), ("d", 30), None,
                   ("b", 10)]


def test_launched_by_nesting_and_spans():
    t = _trace()
    assert t.launched_by("tpu:0", "vilamb.tick.dispatch") == \
        pytest.approx(20e-9)
    assert t.launched_by("tpu:0", "vilamb.patrol") == pytest.approx(10e-9)
    # a prefix matches whole name segments only
    assert t.launched_by("tpu:0", "vilamb.patr") == 0
    assert t.launched_by("tpu:0", "bench.read") == pytest.approx(5e-9)
    assert t.count("vilamb.tick") == 2
    assert t.span_s("vilamb.tick.dispatch") == pytest.approx(5e-9)
    # waits inside a tick on the tick's thread; the resolver's is not
    assert t.nested_s("vilamb.wait", "vilamb.tick") == pytest.approx(22e-9)
    assert t.nested_s("vilamb.wait", "vilamb.patrol") == pytest.approx(20e-9)


def test_idle_gaps_by_program_span():
    t = _trace()
    assert [g[0] for g in t.idle_gaps("tpu:0")] == \
        ["tick", "tick", "tick", "tick", "read"]
    # Idle time split at span boundaries, put down to the innermost span
    # on the calling thread (the library's over the harness's on the same
    # extent; the resolver thread's fetch is not the caller's work).
    got = {}
    for label, s in t.idle_gaps_program("tpu:0"):
        got[label] = got.get(label, 0.0) + s
    want = {"vilamb.tick": 34, "vilamb.tick.schedule": 2,
            "vilamb.wait.resolve": 2, "vilamb.tick.dispatch": 5,
            "vilamb.patrol": 11, "vilamb.patrol.probe": 1,
            "vilamb.wait.probe": 10, "none": 5, "read": 15}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(
        sum(s for _, s in t.idle_gaps("tpu:0")))


def test_segments_flatten_nested_spans():
    segs = PT._segments([(0, 10, "bench.tick"), (0, 10, "vilamb.tick"),
                         (2, 4, "vilamb.tick.dispatch"), (12, 15, "bench.read")])
    assert segs == [(0, 2, "vilamb.tick"), (2, 4, "vilamb.tick.dispatch"),
                    (4, 10, "vilamb.tick"), (12, 15, "bench.read")]


def _ctx(trace, counters):
    cell = harness.Cell("kv-ycsb-a", 1, {}, {}, [], [])
    return harness.Context(cell, {"hbm_bytes_per_s": 1e9}, 1, 1.0,
                           harness.Spans(), counters, trace)


def test_readers_of_the_proposed_metrics():
    ctx = _ctx(_trace(), {"steps": 2, "store.update.alg1_bytes": 10})
    assert tick_wait_ms.read(ctx, "tick_wait_ms.kv") == pytest.approx(11e-6)
    assert tick_dispatch_ms.read(ctx, "") == pytest.approx(2.5e-6)
    assert patrol_host_ms.read(ctx, "") == pytest.approx(11e-6)
    assert background_device_ms.read(ctx, "") == pytest.approx(5e-6)
    # 10 bytes at 1 GB/s take 10 ns; the dispatched runs took 20 ns
    assert alg1_roofline.read(ctx, "") == pytest.approx(50.0)
    # A trace without the library's spans reads nothing.
    plain = T.Trace((0, 120), {}, [("window", 0, 120)])
    assert all(m.read(_ctx(plain, {"steps": 2}), "") is None for m in NEW)


def test_v5e_runs_put_down_to_the_span_that_launched_them():
    """Run 5 is launched inside the first ``bench.write`` span (its
    ``DoEnqueueProgram`` sits on another line of the same thread, inside
    the caller's ``PjitFunction``); run 6 after the tick span, inside
    ``bench.window`` only."""
    t = PT.reduce_program_file(str(DATA / "v5e_small.xplane.pb"))
    base = T.reduce_file(str(DATA / "v5e_small.xplane.pb"))
    assert t.window == base.window and t.host == base.host
    runs = t.runs["tpu:0"]
    assert len(runs) == 6
    assert [r[3] for r in runs] == ["bench.write", "bench.window"] * 3
    write = [(s, e) for n, s, e, _ in t.spans if n == "bench.write"]
    assert runs[0][1] >= write[0][0]          # shifted onto the host clock
    assert t.launched_by("tpu:0", "bench.write") == pytest.approx(
        0.000638321)
    assert t.launched_by("tpu:0", "bench.write") + t.launched_by(
        "tpu:0", "bench.window") == pytest.approx(base.busy_s("tpu:0"))
    assert sum(s for _, s in t.idle_gaps_program("tpu:0")) == \
        pytest.approx(sum(s for _, s in base.idle_gaps("tpu:0")))


class _Ev:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, *events):
        self.name, self.events = name, list(events)


class _Plane:
    def __init__(self, name, *lines):
        self.name, self.lines = name, list(lines)


def test_deferred_enqueue_follows_flows_back_to_its_call():
    """The update pass is called inside ``vilamb.tick.dispatch`` but
    enqueued later on another thread, while the caller is in the patrol:
    the flow events put it down to the dispatch."""
    link = "PJRT_LoadedExecutable_Execute linkage"
    pd = type("PD", (), {"planes": [
        _Plane("/device:TPU:0",
               _Line("XLA Modules", _Ev("jit_many(1)", 300, 340, run_id=7),
                     _Ev("jit_local(2)", 350, 360, run_id=8)),
               _Line("XLA Ops", _Ev("fusion", 300, 340),
                     _Ev("fusion.1", 350, 360))),
        _Plane("/host:CPU",
               _Line("python3", _Ev("bench.window", 0, 1000),
                     _Ev("vilamb.tick", 10, 200),
                     _Ev("vilamb.tick.dispatch", 20, 60),
                     _Ev(link, 30, 31, _pt=14, _p=1),
                     _Ev("vilamb.patrol", 60, 190),
                     _Ev("vilamb.patrol.probe", 70, 150),
                     _Ev(link, 80, 81, _pt=14, _p=2)),
               _Line("main/1",
                     _Ev("PJRT_LoadedExecutable_Execute", 31, 45, _ct=14,
                         _c=1),
                     _Ev("tpu::System::Execute", 35, 40, _pt=7, _p=3),
                     _Ev("PJRT_LoadedExecutable_Execute", 81, 100, _ct=14,
                         _c=2),
                     _Ev("DoEnqueueProgram", 85, 90, run_id=8,
                         device_ordinal=0)),
               _Line("worker",
                     _Ev("IssueSequencedEvent", 100, 130, _ct=7, _c=3),
                     _Ev("DoEnqueueProgram", 110, 115, run_id=7,
                         device_ordinal=0)))]})()
    t = PT.reduce_program_profile(pd)
    assert [r[3] for r in t.runs["tpu:0"]] == ["vilamb.tick.dispatch",
                                               "vilamb.patrol.probe"]
    assert t.launched_by("tpu:0", "vilamb.tick.dispatch") == \
        pytest.approx(40e-9)
    assert t.launched_by("tpu:0", "vilamb.patrol") == pytest.approx(10e-9)


def test_profiled_window_of_a_small_kv_cell(tmp_path):
    """The probed window records the store's counters and, profiled with
    the spans on, reads the host-span metrics (the CPU has no program
    launches, so the device ones read nothing)."""
    from bench.runners.kv_region import KvRegion
    cell = harness.load_cell("kv-ycsb-a")
    cell.traffic["batches_ahead"] = 0
    spans = harness.Spans()
    run = KvRegion(cell, 2 ** 33 + 7, spans, records=2048)
    run.setup(warm_batches=2)
    probed = PT.Probed(run, spans, True, tmp_path / "trace")
    spans.recording = True
    e2e = probed.window(1.0)
    spans.recording = False
    assert e2e["kv_ops_per_s"] > 0
    c = probed.counters_at_end
    assert c["store.update.stripes"] > 0
    assert c["store.update.alg1_bytes"] == c["store.update.stripes"] * (
        4 * 4096 + 4096 + 4 * 4)
    assert "store.patrol.blocks_scanned" in c
    out = PT.read_program(cell, probed, {"hbm_bytes_per_s": 819e9})
    got = out["per_layer"]
    for name in ("tick_wait_ms.kv", "tick_dispatch_ms.kv",
                 "patrol_host_ms.kv"):
        assert got[name] >= 0, (name, got)
    assert got["tick_dispatch_ms.kv"] > 0
    assert "tick_host_ms.kv" in got           # the cell's own metrics too
    labels = [g[0] for g in out["breakdown"]["idle_gaps_program"]]
    assert any(label.startswith("vilamb.") for label in labels), labels
    run.close()


def _launch_profile(*host_lines):
    """Two runs on one device: run 7 (``jit_many``) and run 8
    (``jit_local``), with the given host lines."""
    return type("PD", (), {"planes": [
        _Plane("/device:TPU:0",
               _Line("XLA Modules", _Ev("jit_many(1)", 300, 340, run_id=7),
                     _Ev("jit_local(2)", 350, 360, run_id=8)),
               _Line("XLA Ops", _Ev("fusion", 300, 340),
                     _Ev("fusion.1", 350, 360))),
        _Plane("/host:CPU", *host_lines)]})()


def test_flow_ids_are_matched_within_their_type():
    """The caller's linkage flows (type 14) and the runtime's (type 7)
    draw ids from separate counters, so one id names two flows: the
    deferred update pass follows its type-7 flow back to the dispatch,
    not to the probe's linkage that carries the same id."""
    link = "PJRT_LoadedExecutable_Execute linkage"
    pd = _launch_profile(
        _Line("main/1",
              _Ev("PJRT_LoadedExecutable_Execute", 31, 45, _ct=14, _c=1),
              _Ev("tpu::System::Execute", 35, 40, _pt=7, _p=2),
              _Ev("PJRT_LoadedExecutable_Execute", 81, 100, _ct=14, _c=2),
              _Ev("DoEnqueueProgram", 85, 90, run_id=8, device_ordinal=0,
                  _pt=12, _p=9)),
        _Line("tfrt-non-blocking-queue/2",
              _Ev("tpu::System::Execute=>IssueSequencedEvent", 100, 130,
                  _ct=7, _c=2),
              _Ev("DoEnqueueProgram", 110, 115, run_id=7, device_ordinal=0,
                  _pt=12, _p=8)),
        _Line("python3", _Ev("bench.window", 0, 1000),
              _Ev("vilamb.tick", 10, 200),
              _Ev("vilamb.tick.dispatch", 20, 60),
              _Ev(link, 30, 31, _pt=14, _p=1),
              _Ev("vilamb.patrol", 60, 190),
              _Ev("vilamb.patrol.probe", 70, 150),
              _Ev(link, 80, 81, _pt=14, _p=2)))
    t = PT.reduce_program_profile(pd)
    assert [r[3] for r in t.runs["tpu:0"]] == ["vilamb.tick.dispatch",
                                               "vilamb.patrol.probe"]
    assert t.launched_by("tpu:0", "vilamb.patrol") == pytest.approx(10e-9)


def test_completion_callbacks_lead_to_no_later_call():
    """Run 8 is enqueued inside run 7's completion callbacks, which
    consume the flow out of run 7's enqueue: that flow does not name run
    8's caller, so run 8 is not put down to run 7's span."""
    link = "PJRT_LoadedExecutable_Execute linkage"
    pd = _launch_profile(
        _Line("main/1",
              _Ev("PJRT_LoadedExecutable_Execute", 31, 45, _ct=14, _c=1),
              _Ev("DoEnqueueProgram", 35, 40, run_id=7, device_ordinal=0,
                  _pt=12, _p=5)),
        _Line("futex-default-SDomainT/3",
              _Ev("CompleteCallbacks", 341, 349, run_id=7, _ct=12, _c=5),
              _Ev("DoEnqueueProgram", 343, 346, run_id=8, device_ordinal=0,
                  _pt=12, _p=6)),
        _Line("python3", _Ev("bench.window", 0, 1000),
              _Ev("vilamb.tick.dispatch", 20, 60),
              _Ev(link, 30, 31, _pt=14, _p=1)))
    t = PT.reduce_program_profile(pd)
    assert [r[3] for r in t.runs["tpu:0"]] == ["vilamb.tick.dispatch",
                                               "none"]
    assert t.launched_by("tpu:0", "vilamb.tick.dispatch") == \
        pytest.approx(40e-9)
