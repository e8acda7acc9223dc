"""Host spans and counters inside the store (``repro.core.trace``).

Covers: the named spans of a due tick, a quiet tick and a forced resolve,
with their nesting, as a CPU profile records them; no profiler annotation
at all while tracing is off; the always-on wait counters; and the
Algorithm-1 pass counters (dirty stripes counted on the device, fetched
with the fit signal) against a host count over queued, full and
overflowed passes, on one device and on a 4-device host mesh.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import ProtectedStore, RedundancyPolicy
from repro.core import trace
from subproc import run_snippet

ROWS = 64
LANES = 128


def _store(**kw):
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=2, lanes_per_block=LANES,
        stripe_data_blocks=4, work_queue_frac=0.25, async_tick=True,
        precompile=False, patrol_bytes_per_tick=8 * LANES * 4, **kw)
    lv = {"w": jax.random.normal(jax.random.PRNGKey(0), (ROWS, LANES),
                                 jnp.float32)}
    store = ProtectedStore(pol).attach(lv)
    return store, lv, store.init(lv)


def _write(store, red, rows):
    ev = jnp.zeros((ROWS,), bool).at[jnp.asarray(list(rows))].set(True)
    return store.on_write(red, events={"w": ev})


def _drive(store, lv, red):
    """A due tick (dispatch), a due tick with a scrub that forces the
    outstanding pass to resolve, a quiet tick (patrol probe), a quiet tick
    that processes the probe, then settle and flush."""
    red = _write(store, red, (1, 9))
    red, rep = store.tick(lv, red, 2)
    assert rep.updated
    red = _write(store, red, (17,))
    red, rep = store.tick(lv, red, 4, scrub_period=4)
    assert rep.scrubbed and rep.updated
    red, rep = store.tick(lv, red, 5)
    assert rep.patrolled
    store.sync_inflight()
    red, _ = store.tick(lv, red, 7)
    red = store.settle(red, lv)
    red = _write(store, red, (33,))
    red = store.flush(lv, red, step=8)
    store._stop_dispatcher()
    return red


def _profile_events(path):
    """``(name, start, end, line)`` of every ``vilamb.*`` host event."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(next(path.glob("**/*.xplane.pb"))))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("vilamb."):
                    out.append((ev.name[7:], ev.start_ns, ev.end_ns,
                                (plane.name, i)))
    return out


def _inside(child, events, parent):
    return any(n == parent and line == child[3] and s <= child[1]
               and child[2] <= e for n, s, e, line in events)


def test_spans_name_the_tick_and_nest(tmp_path):
    store, lv, red = _store()
    _drive(store, lv, red)              # compiles every program first
    store, lv, red = _store()
    trace.enable(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive(store, lv, red)
    finally:
        jax.profiler.stop_trace()
        trace.enable(False)
    ev = _profile_events(tmp_path)
    names = [e[0] for e in ev]
    assert names.count("tick") == 4
    assert names.count("tick.schedule") == 4
    for name in ("tick.dispatch", "tick.scrub", "patrol", "patrol.probe",
                 "wait.resolve", "wait.scrub", "resolver.fetch", "settle",
                 "flush", "wait.queue_fits"):
        assert name in names, (name, sorted(set(names)))
    parents = {"tick.schedule": "tick", "tick.dispatch": "tick",
               "tick.scrub": "tick", "patrol": "tick",
               "patrol.probe": "patrol", "wait.resolve": "tick.schedule",
               "wait.scrub": "tick.scrub"}
    for e in ev:
        if e[0] in parents:
            assert _inside(e, ev, parents[e[0]]), (e[0], parents[e[0]])
    # The flush's blocking dispatch waits on its fit check.
    flush_waits = [e for e in ev if e[0] == "wait.queue_fits"]
    assert all(_inside(e, ev, "flush") for e in flush_waits)
    # The resolver's fetch runs on its own thread, off the tick's line.
    tick_lines = {e[3] for e in ev if e[0] == "tick"}
    assert all(e[3] not in tick_lines for e in ev
               if e[0] == "resolver.fetch")


def test_tracing_off_constructs_no_annotation(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("TraceAnnotation constructed with tracing off")

    assert not trace._on                # off is the default
    monkeypatch.setattr(trace, "TraceAnnotation", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    store, lv, red = _store()
    _drive(store, lv, red)
    assert store.counters["wait.resolve.n"] >= 1


def test_forced_resolve_and_scrub_count_their_waits():
    store, lv, red = _store()
    red = _write(store, red, (1,))
    red, _ = store.tick(lv, red, 2)
    assert store._protected()[0].pending is not None
    assert "wait.resolve.n" not in store.counters
    red, _ = store.tick(lv, red, 4, scrub_period=4)
    c = store.counters
    assert c["wait.resolve.n"] == 1 and c["wait.scrub.n"] == 1
    assert c["wait.resolve.s"] >= 0 and c["wait.scrub.max_ms"] >= 0
    assert c["patrol.blocks_scanned"] == 0    # busy ticks: no probe yet
    red, rep = store.tick(lv, red, 5)
    assert rep.patrolled and c["patrol.blocks_scanned"] == 8
    store.settle(red, lv)
    store._stop_dispatcher()


PASSES = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import ProtectedStore, RedundancyPolicy

n = jax.device_count()
ROWS, STRIPE = 64 * n, 4
mesh = (jax.make_mesh((n,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
        if n > 1 else None)
specs = {"w": P("d", None)} if mesh is not None else None
pol = RedundancyPolicy.single(
    "vilamb", period_steps=2, lanes_per_block=128, stripe_data_blocks=STRIPE,
    work_queue_frac=0.25, async_tick=True, precompile=False)
lv = {"w": jnp.zeros((ROWS, 128), jnp.float32)}
if mesh is not None:
    lv = {"w": jax.device_put(lv["w"], NamedSharding(mesh, specs["w"]))}
store = ProtectedStore(pol, mesh=mesh).attach(lv, specs=specs)
red = store.init(lv)
g = next(iter(store.groups.values()))
assert g.engine.queue_capacity("w") == 4      # per shard, of 16 stripes

def write(red, rows):
    ev = jnp.zeros((ROWS,), bool).at[jnp.asarray(sorted(rows))].set(True)
    return store.on_write(red, events={"w": ev})

def stripes(rows):                 # rows of one shard never share a stripe
    return len({r // STRIPE for r in rows})

# Steps: full first pass (pessimistic start), queued passes, a queued pass
# over 7 stripes of one shard (overflows a 4-stripe queue), its full
# fallback, an empty due pass, then a blocking flush.
plan = [{0}, {5}, {9, 70 % ROWS}, {13}, {0, 4, 8, 12, 16, 20}, {24}, set(),
        set(), {40}]
marks, left, snap, pend, want, ovf = set(), set(), None, None, 0, 0
for step, rows in enumerate(plan, 1):
    if rows:
        red = write(red, rows)
        marks |= rows
    store.sync_inflight()
    red, rep = store.tick(lv, red, step)
    if pend is not None and g.pending is not pend:     # resolved this tick
        if g.label in rep.overflowed:
            left, ovf = snap, ovf + 1
        else:
            want += stripes(snap)
        pend = None
    if g.pending is not None and g.pending.step == step:   # dispatched
        pend, snap, marks, left = g.pending, marks | left, set(), set()
red = store.settle(red, lv)
if pend is not None:
    want += stripes(snap)
red = write(red, {44, 48})
red = store.flush(lv, red, step=len(plan) + 1)
want += stripes(marks | {44, 48} | left)
c = store.counters
assert ovf == 1 and c["update.overflowed"] == 1, (ovf, c)
assert c["update.passes_queued"] == 3 and c["update.passes_full"] == 3, c
assert c["update.stripes"] == want, (c["update.stripes"], want)
assert c["update.alg1_bytes"] == want * (STRIPE * 512 + 512 + STRIPE * 4)
assert g.engine.alg1_stripe_bytes == STRIPE * 512 + 512 + STRIPE * 4
print("PASSES_OK", n, want)
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_pass_counters_match_a_host_count_of_dirty_stripes(devices):
    run_snippet(PASSES, f"PASSES_OK {devices}", devices=devices)
