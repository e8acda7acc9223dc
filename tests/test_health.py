"""Freshness-SLO health governor: breaker state machine, escalation
ladder (retry -> forced resolve -> backpressure -> sync escalation),
shared retry backoff, deadline-clock continuity across remesh, and the
chaos-soak battery's invariants.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from subproc import run_snippet, MESH_PRELUDE

from repro.core import (ProtectedStore, RedundancyPolicy,
                        UnrecoverableReadError)
from repro.core import store as store_mod
from repro.core.store import TickReport
from repro.faults.inject import FaultSpec, apply_fault
from repro.health import (BackpressureError, CRITICAL, DEGRADED,
                          FreshnessViolationError, HEALTHY, HealthGovernor,
                          HealthPolicy, backoff_delay, backoff_schedule)

LANES = 64


def _store(health=None, *, period=2, n_rows=16, async_tick=True, **pol_kw):
    pol = RedundancyPolicy.single("vilamb", period_steps=period,
                                  lanes_per_block=LANES,
                                  async_tick=async_tick, health=health,
                                  **pol_kw)
    lv = {"w": jax.random.normal(jax.random.PRNGKey(0), (n_rows, 512),
                                 jnp.float32)}
    store = ProtectedStore(pol).attach(lv)
    red = store.init(lv)
    red = store.flush(lv, red, step=0)
    return store, lv, red


def _write(store, lv, red, rows=(0, 1)):
    idx = jnp.asarray(rows)
    lv = dict(lv, w=lv["w"].at[idx].add(0.5))
    ev = jnp.zeros((lv["w"].shape[0],), bool).at[idx].set(True)
    return lv, store.on_write(red, events={"w": ev})


def _group(store):
    return next(iter(store.groups.values()))


# ------------------------------------------------------------ retry backoff

def test_backoff_delay_exponential_and_cap():
    assert backoff_delay(1, 0.01) == pytest.approx(0.01)
    assert backoff_delay(2, 0.01) == pytest.approx(0.02)
    assert backoff_delay(3, 0.01) == pytest.approx(0.04)
    assert backoff_delay(4, 0.01, cap=0.03) == pytest.approx(0.03)
    assert backoff_delay(3, 0.0) == 0.0


def test_backoff_jitter_only_shrinks():
    import random
    rng = random.Random(7)
    for attempt in range(1, 6):
        base = backoff_delay(attempt, 0.01)
        jittered = backoff_delay(attempt, 0.01, jitter_frac=0.5, rng=rng)
        assert 0.5 * base <= jittered <= base


def test_backoff_schedule_total_budget():
    # raw [0.01, 0.02, 0.04->cap 0.02]; cumulative [0.01, 0.03, 0.05]
    # clipped to total 0.035 -> last delay degenerates to 0.005.
    ds = backoff_schedule(3, 0.01, cap=0.02, total=0.035)
    assert ds == pytest.approx([0.01, 0.02, 0.005])
    assert backoff_schedule(3, 0.0) == [0.0, 0.0, 0.0]
    assert sum(backoff_schedule(10, 0.01, total=0.02)) <= 0.02 + 1e-9


def test_read_verified_backoff_schedule_applied(monkeypatch):
    """The read-retry path uses the shared exponential schedule: with
    attempts=4, base 10ms, cap 20ms, total budget 35ms the sleeps are
    exactly [10ms, 20ms, 5ms]."""
    pol_kw = dict(read_retry_attempts=4, read_retry_backoff_s=0.01,
                  read_retry_backoff_cap_s=0.02, read_retry_total_s=0.035,
                  read_retry_jitter_frac=0.0)
    store, lv, red = _store(async_tick=False, **pol_kw)
    # Two corruptions in one stripe defeat single parity -> every retry
    # re-reads, then the typed error surfaces.
    for blk in (0, 1):
        lv, red = apply_fault(store.metas, lv, red,
                              FaultSpec("data_bitflip", "w", block=blk,
                                        lane=3, bit=7))
    sleeps = []
    monkeypatch.setattr(time, "sleep", sleeps.append)
    with pytest.raises(UnrecoverableReadError):
        store.read_verified(lv, red, "w", [0])
    assert sleeps == pytest.approx([0.01, 0.02, 0.005])


# ------------------------------------------------------- governor plumbing

def test_governor_off_by_default():
    store, lv, red = _store(health=None)
    lv, red = _write(store, lv, red)
    red, rep = store.tick(lv, red, 1, scrub_period=0)
    assert rep.health is None
    assert store._health is None


def test_governor_on_reports_healthy():
    store, lv, red = _store(HealthPolicy(violation_mode="report"))
    label = _group(store).label
    for step in range(1, 5):
        lv, red = _write(store, lv, red)
        red, rep = store.tick(lv, red, step, step_time=0.01, scrub_period=0)
        assert rep.health is not None
        assert rep.health.states[label] == HEALTHY
        assert rep.health.worst == HEALTHY
    assert rep.health.ages[label][0] >= 0


# ------------------------------------------------- rung 1: timeout + retry

def test_rung1_timeout_rolls_back_and_redispatches(monkeypatch):
    hp = HealthPolicy(dispatch_timeout_s=0.001, dispatch_retry_attempts=3,
                      retry_backoff_s=0.005, retry_jitter_frac=0.0,
                      violation_mode="report")
    store, lv, red = _store(hp)
    hg = store._health
    sleeps = []
    hg._sleep = sleeps.append
    for step in (1, 2):
        lv, red = _write(store, lv, red)
        red, rep = store.tick(lv, red, step, step_time=0.01, scrub_period=0)
    g = _group(store)
    assert g.pending is not None
    prev = g.pending.prev_step
    monkeypatch.setattr(store_mod, "_ready", lambda fits: False)
    g.pending.dispatched_at -= 10.0           # pending looks ancient
    red, rep = store.tick(lv, red, 3, step_time=0.01, scrub_period=0)
    acts = [(a.rung, a.kind) for a in rep.health.actions]
    assert (1, "retry_timeout") in acts
    assert rep.health.states[g.label] == DEGRADED
    assert sleeps == pytest.approx([0.005])    # bounded backoff slept
    # Re-dispatched THIS tick (a fresh pending), not at the next period
    # boundary — otherwise the breaker cools down between retries.
    assert g.pending is not None
    assert g.pending.prev_step <= prev


def test_rung1_exhaustion_escalates_then_recovers(monkeypatch):
    hp = HealthPolicy(dispatch_timeout_s=1e-6, dispatch_retry_attempts=1,
                      retry_backoff_s=0.0, backpressure="spin",
                      backpressure_spin_s=0.0, recovery_ticks=2,
                      violation_mode="report")
    store, lv, red = _store(hp)
    hg = store._health
    hg._sleep = lambda s: None
    monkeypatch.setattr(store_mod, "_ready", lambda fits: False)
    label = _group(store).label
    step, worst_seen = 1, []
    for _ in range(8):
        lv, red = _write(store, lv, red)
        red, rep = store.tick(lv, red, step, step_time=0.01, scrub_period=0)
        step += 1
        worst_seen.append(rep.health.states[label])
        if rep.health.states[label] == CRITICAL:
            break
    assert CRITICAL in worst_seen
    gh = hg.group(label)
    assert gh.sync_escalated and gh.backpressure
    kinds = {a.kind for a in rep.health.actions}
    assert {"retry_exhausted", "backpressure_on", "sync_escalate"} <= kinds
    # Recovery: the sync-escalated group updates via the blocking path
    # (calm), the breaker steps down one level per recovery_ticks calm
    # ticks, backpressure clears below CRITICAL, retries reset at HEALTHY.
    seen = []
    for _ in range(12):
        lv, red = _write(store, lv, red)
        red, rep = store.tick(lv, red, step, step_time=0.01, scrub_period=0)
        step += 1
        seen.append(rep.health.states[label])
        if rep.health.states[label] == HEALTHY:
            break
    assert seen[-1] == HEALTHY
    assert DEGRADED in seen                    # hysteresis: one level at a time
    assert not hg.group(label).backpressure
    assert not hg.group(label).sync_escalated
    assert hg.group(label).retries == 0


# ---------------------------------------------- rung 2: forced resolve

def test_rung2_margin_forces_blocking_resolve(monkeypatch):
    hp = HealthPolicy(dispatch_timeout_s=0.0,       # rung 1 disabled
                      deadline_margin_steps=2, violation_mode="report")
    store, lv, red = _store(hp, period=4, max_vulnerable_steps=6)
    monkeypatch.setattr(store_mod, "_ready", lambda fits: False)
    for step in range(1, 5):
        lv, red = _write(store, lv, red)
        red, rep = store.tick(lv, red, step, step_time=0.01, scrub_period=0)
    g = _group(store)
    assert g.pending is not None               # wedged probe: still in flight
    # Quiet ticks: the margin (deadline 6 - margin 2 = age 4) hits at
    # step 8; wait=True bypasses the probe and adopts the update early.
    fired = None
    for step in range(5, 9):
        red, rep = store.tick(lv, red, step, step_time=0.01, scrub_period=0)
        if any(a.kind == "forced_resolve" for a in rep.health.actions):
            fired = step
            break
    assert fired == 8, fired
    acts = [(a.rung, a.kind) for a in rep.health.actions]
    assert (2, "forced_resolve") in acts
    assert rep.health.states[g.label] == DEGRADED
    assert not rep.deadline_fired              # met early, not missed


# ------------------------------------------- rung 3: admission control

def test_backpressure_error_policy_raises_typed():
    hp = HealthPolicy(backpressure="error", violation_mode="report")
    store, lv, red = _store(hp)
    hg = store._health
    label = _group(store).label
    hg.group(label).backpressure = True
    with pytest.raises(BackpressureError) as ei:
        _write(store, lv, red)
    assert label in ei.value.groups


def test_backpressure_spin_policy_bounded_stall():
    hp = HealthPolicy(backpressure="spin", backpressure_spin_s=0.002,
                      violation_mode="report")
    store, lv, red = _store(hp)
    hg = store._health
    spins = []
    hg._sleep = spins.append
    hg.group(_group(store).label).backpressure = True
    lv, red = _write(store, lv, red)           # no raise: bounded spin
    assert spins == [0.002]


def test_backpressure_noop_under_trace():
    """Admission control must never block inside a jitted step — the
    tracer check turns it into a no-op under trace."""
    hp = HealthPolicy(backpressure="error", violation_mode="report")
    store, lv, red = _store(hp)
    store._health.group(_group(store).label).backpressure = True
    ev = jnp.zeros((lv["w"].shape[0],), bool).at[0].set(True)
    stepped = jax.jit(lambda r: store.on_write(r, events={"w": ev}))
    red2 = stepped(red)                        # would raise on the host path
    assert red2 is not None


# ----------------------------------------------- violations are typed

def _violating_governor(mode):
    hp = HealthPolicy(violation_mode=mode)
    store, lv, red = _store(hp, max_vulnerable_steps=4)
    hg = store._health
    g = _group(store)
    g.last_update_step = -10                   # ancient unprotected write
    return store, hg, g


def test_violation_reported_never_silent():
    store, hg, g = _violating_governor("report")
    now = time.monotonic()
    hg.begin_tick(20, now)
    rep = TickReport(step=20)
    hg.end_tick(rep, 20, now)
    assert rep.health.violations, "deadline excursion must be surfaced"
    v = rep.health.violations[0]
    assert v.group == g.label and v.age_steps == 30
    assert rep.health.states[g.label] == CRITICAL
    assert hg.group(g.label).backpressure or hg.group(g.label).sync_escalated


def test_violation_mode_raise_is_typed():
    store, hg, g = _violating_governor("raise")
    now = time.monotonic()
    hg.begin_tick(20, now)
    with pytest.raises(FreshnessViolationError) as ei:
        hg.end_tick(TickReport(step=20), 20, now)
    assert ei.value.violations[0].group == g.label


def test_health_policy_validation():
    with pytest.raises(ValueError):
        HealthPolicy(backpressure="bogus")
    with pytest.raises(ValueError):
        HealthPolicy(violation_mode="bogus")


# ------------------------------- patrol starvation x governor backpressure

def test_patrol_floor_survives_backpressure():
    """The patrol starvation floor keeps forcing probes while the
    governor applies backpressure, and the governor's report mirrors the
    starvation streak."""
    hp = HealthPolicy(backpressure="spin", backpressure_spin_s=0.001,
                      violation_mode="report")
    bpb = LANES * 4
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=1, lanes_per_block=LANES,
        patrol_bytes_per_tick=8 * bpb, patrol_max_starved_ticks=4,
        async_tick=False, precompile=False, health=hp)
    lv = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 512),
                                 jnp.float32)}
    store = ProtectedStore(pol).attach(lv)
    red = store.init(lv)
    hg = store._health
    spins = []
    hg._sleep = spins.append
    hg.group(_group(store).label).backpressure = True
    for step in range(1, 31):
        lv, red = _write(store, lv, red, rows=(0, 1, 2, 3))
        red, rep = store.tick(lv, red, step, step_time=0.01, scrub_period=0)
        assert rep.updated, "tick unexpectedly quiet"
        assert rep.health.patrol_starved_ticks == rep.patrol_starved_ticks
    assert store.counters["patrol.blocks_scanned"] >= 8   # floor forced probes
    assert rep.patrol_starved_ticks <= 4
    assert spins == [0.001] * 30                 # every admit spun, none raised


# ------------------------------ deadline-clock continuity across remesh

def test_remesh_adoption_carries_freshness_clocks():
    """Adoption must copy the old group's freshness clocks bit-for-bit:
    a fresh _Group would report step 0 / time.monotonic() and either
    fire a spurious steps-deadline right after adoption or silently
    extend the wall-clock one by the whole migration.  A huge period
    plus a one-tick migration budget keeps every dispatch out of the
    window, so the carry is observable exactly; the steps-deadline then
    fires at the step predicted by the *carried* clock, not rebased to
    the adoption step.  Health governor off: base store mechanics."""
    code = """
    store = mesh_store(period=64, max_vulnerable_steps=20,
                       remesh_bytes_per_tick=1 << 22)
    lv = put(make_leaves())
    red = store.init(lv)
    def write(lv, red):
        idx = jnp.asarray([0, 1])
        lv = dict(lv, w=lv["w"].at[idx].add(0.5))
        ev = jnp.zeros((64,), bool).at[idx].set(True)
        return lv, store.on_write(red, events={"w": ev})
    for step in range(1, 4):
        lv, red = write(lv, red)
        red, rep = store.tick(lv, red, step, scrub_period=0)
    g = [g for g in store.groups.values() if "w" in g.names][0]
    label = g.label
    # Pin a known freshness origin.  The wall-clock rewind makes a
    # reset-to-now at adoption visible; with max_vulnerable_seconds=0
    # it cannot trip the overdue path and refresh itself first.
    g.last_update_step = 3
    g.last_update_time -= 1000.0
    old_step, old_time = g.last_update_step, g.last_update_time
    store.remesh(make_mesh((1, 2, 2), ("pod", "data", "model")))
    step = 3
    while store.remeshing:
        step += 1
        assert step < 20, "migration outran the deadline window"
        lv, red = write(lv, red)
        red, rep = store.tick(lv, red, step, scrub_period=0)
        if rep.repaired:
            lv = dict(lv, **rep.repaired)
        assert not rep.deadline_fired, rep
    g2 = [g for g in store.groups.values() if g.label == label][0]
    assert g2 is not g
    assert g2.last_update_step == old_step, (g2.last_update_step, old_step)
    assert g2.last_update_time == old_time, (g2.last_update_time, old_time)
    fired_at = None
    while fired_at is None:
        step += 1
        assert step <= 23, "deadline never fired from carried clock"
        lv, red = write(lv, red)
        red, rep = store.tick(lv, red, step, scrub_period=0)
        if label in rep.deadline_fired:
            fired_at = step
    assert fired_at == old_step + 20, fired_at
    print("REBASE-OK")
    """
    run_snippet(code, "REBASE-OK", prelude=MESH_PRELUDE)


def test_governor_drains_remesh_at_deadline():
    """THE silent freshness hole: during a remesh the per-group update
    loop is skipped wholesale.  With the governor on, a group hitting
    its deadline mid-migration forces the remesh to drain and a blocking
    update runs — surfaced as a rung-2 remesh_drain action, never a
    silent excursion."""
    code = """
    from repro.health import HealthPolicy
    store = mesh_store(period=2, max_vulnerable_steps=6,
                       remesh_bytes_per_tick=128 * 4,
                       health=HealthPolicy(dispatch_timeout_s=0.0,
                                           deadline_margin_steps=1,
                                           violation_mode="report"))
    lv = put(make_leaves())
    red = store.init(lv)
    def write(lv, red):
        idx = jnp.asarray([0, 1])
        lv = dict(lv, w=lv["w"].at[idx].add(0.5))
        ev = jnp.zeros((64,), bool).at[idx].set(True)
        return lv, store.on_write(red, events={"w": ev})
    step = 0
    for step in range(1, 5):
        lv, red = write(lv, red)
        red, rep = store.tick(lv, red, step, scrub_period=0)
    store.remesh(make_mesh((1, 2, 2), ("pod", "data", "model")))
    drained = violated = False
    while store.remeshing:
        step += 1
        lv, red = write(lv, red)
        red, rep = store.tick(lv, red, step, scrub_period=0)
        if rep.repaired:
            lv = dict(lv, **rep.repaired)
        h = rep.health
        if h is not None:
            drained |= any(a.kind == "remesh_drain" for a in h.actions)
            violated |= bool(h.violations)
        for g in store.groups.values():
            lp = g.policy
            if lp.mode != "vilamb" or lp.max_vulnerable_steps <= 0:
                continue
            age = step - g.last_update_step
            visible = h is not None and (
                any(v.group == g.label for v in h.violations)
                or any(a.group == g.label for a in h.actions))
            assert age <= lp.max_vulnerable_steps or visible, (
                "SILENT freshness excursion", g.label, age, step)
        assert step < 600, "remesh never finished"
    assert drained, "governor never drained the remesh"
    print("DRAIN-OK")
    """
    run_snippet(code, "DRAIN-OK", prelude=MESH_PRELUDE)


# --------------------------------------------------------- chaos battery

def test_chaos_soak_machine_local():
    """Machine-local smoke soak: bitflips + straggler storm + crash under
    live traffic.  Invariants: zero silent deadline violations, zero
    stale verified reads, final state bitwise-recovered."""
    from repro.faults import run_chaos_soak
    r = run_chaos_soak(seed=0, sharded=False, smoke=True)
    assert r.ok(), r.summary()
    assert r.silent_violations == 0
    assert r.reads_stale == 0
    assert r.final_clean and r.final_bitwise
    assert r.bitflips_injected > 0 and r.crash_restores > 0


def test_chaos_schedule_is_seeded_and_composable():
    from repro.faults import ChaosSchedule, StormPhase
    a = ChaosSchedule.default(3, sharded=True, smoke=True)
    b = ChaosSchedule.default(3, sharded=True, smoke=True)
    assert [p.kind for p in a.phases] == [p.kind for p in b.phases]
    assert {"bitflips", "straggler", "crash", "shard_loss",
            "remesh", "drain"} <= {p.kind for p in a.phases}
    custom = ChaosSchedule([StormPhase("traffic", steps=2),
                            StormPhase("drain")], seed=9)
    assert custom.phases[0].steps == 2 and custom.seed == 9
