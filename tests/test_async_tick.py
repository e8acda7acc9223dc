"""Overlap-pipelined tick (PR3): sync-free speculative dispatch must stay
bitwise-identical to the blocking path — including flush/scrub called while
an update is in flight and speculative queued-vs-full mispredictions — and
the hot path must never pay a device->host round trip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.store as store_mod
from repro.core import ALL, ProtectedStore, RedundancyPolicy, bits
from repro.core import blocks as B

RED_FIELDS = ("checksums", "parity", "dirty", "shadow", "meta_ck")


def _leaves(seed=0):
    return {"w": jax.random.normal(jax.random.PRNGKey(seed), (24, 200),
                                   jnp.float32),
            "e": jax.random.normal(jax.random.PRNGKey(seed + 1), (16, 64),
                                   jnp.bfloat16)}


def _store(async_on, period=3, frac=0.5, precompile=True):
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=period, lanes_per_block=128,
        work_queue_frac=frac, async_tick=async_on, precompile=precompile)
    return ProtectedStore(pol).attach(_leaves())


def _assert_red_equal(a, b):
    for k in a:
        for f in RED_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(a[k], f)), np.asarray(getattr(b[k], f)),
                err_msg=f"{k}.{f}")


def _group(store):
    return next(iter(store.groups.values()))


def _drive(store, leaves, steps, seed=0):
    """Identical write/mark/tick sequence for any store."""
    rng = np.random.default_rng(seed)
    lv = dict(leaves)
    red = store.init(lv)
    for step in range(1, steps + 1):
        rows = rng.choice(24, size=rng.integers(1, 5), replace=False)
        ev = jnp.zeros((24,), bool).at[jnp.asarray(rows)].set(True)
        lv = dict(lv, w=lv["w"].at[jnp.asarray(rows)].add(0.25 * step))
        red = store.on_write(red, events={"w": ev})
        red, _ = store.tick(lv, red, step)
    return lv, red


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_async_end_state_bitwise_identical_to_blocking(seed):
    """Random sparse workloads: settled async state == blocking state."""
    sa, sb = _store(True), _store(False)
    lv_a, red_a = _drive(sa, _leaves(), 9, seed=seed)
    lv_b, red_b = _drive(sb, _leaves(), 9, seed=seed)
    red_a = sa.settle(red_a, lv_a)
    _assert_red_equal(red_a, red_b)
    assert sum(int(v.sum()) for v in sa.scrub(lv_a, red_a).values()) == 0


def test_flush_mid_flight_matches_blocking():
    """flush while an async update is in flight == blocking-path flush."""
    outs = []
    for async_on in (True, False):
        store = _store(async_on, period=2)
        lv = _leaves()
        red = store.init(lv)
        ev = jnp.zeros((24,), bool).at[jnp.array([1, 5])].set(True)
        red = store.on_write(red, events={"w": ev})
        lv = dict(lv, w=lv["w"].at[1].add(2.0).at[5].add(1.0))
        red, _ = store.tick(lv, red, 2)          # async: update in flight
        red = store.on_write(red, events={"w": jnp.zeros((24,), bool)
                                          .at[9].set(True)})
        lv = dict(lv, w=lv["w"].at[9].add(3.0))
        red = store.flush(lv, red, step=3)
        if async_on:
            assert _group(store).pending is None  # flush resolved it
        outs.append((lv, red))
    _assert_red_equal(outs[0][1], outs[1][1])


def test_scrub_check_mid_flight_matches_blocking():
    """Corruption of a clean block is detected mid-flight exactly as the
    blocking path would detect it, and in-flight blocks stay skipped."""
    counts = []
    for async_on in (True, False):
        store = _store(async_on, period=2)
        lv = _leaves()
        red = store.init(lv)
        ev = jnp.zeros((24,), bool).at[0].set(True)
        red = store.on_write(red, events={"w": ev})
        lv = dict(lv, w=lv["w"].at[0].add(1.0))
        red, _ = store.tick(lv, red, 2)          # async: update in flight
        meta = store.metas["w"]
        lanes = B.to_lanes(lv["w"], meta)
        bad = dict(lv, w=B.from_lanes(lanes.at[20, 3].add(99), meta))
        mm = store.scrub(bad, red)
        assert np.flatnonzero(np.asarray(mm["w"])).tolist() == [20]
        counts.append(store.scrub_check(bad, red))
    assert counts[0] == counts[1] > 0


def test_speculative_misprediction_is_bitwise_safe():
    """A queued dispatch launched on a wrong fit prediction (overflow) must
    settle to the exact blocking-path bits via the full fallback."""
    outs = []
    for async_on in (True, False):
        store = _store(async_on, period=1)
        lv = _leaves()
        red = store.init(lv)
        if async_on:
            _group(store).predicted_fits = True   # force the misprediction
        red = store.on_write(red, events={"w": ALL, "e": ALL})
        lv = {k: v + 1 for k, v in lv.items()}
        red, _ = store.tick(lv, red, 1)           # async: queued, overflows
        if async_on:
            p = _group(store).pending
            assert p is not None and p.queued
            store.sync_inflight()
            red, rep = store.tick(lv, red, 2)     # resolves -> full fallback
            assert rep.overflowed
            assert _group(store).predicted_fits is False
        red = store.settle(red, lv)
        outs.append(red)
        assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
    _assert_red_equal(outs[0], outs[1])


def test_scrub_after_overflow_leaves_callers_red_usable():
    """Regression: settle's overflow repair (run from the read-only scrub
    path) must not donate the caller's red — ticking must keep working on
    the same lineage afterwards, bitwise-equal to the blocking path."""
    outs = []
    for async_on in (True, False):
        store = _store(async_on, period=1)
        lv = _leaves()
        red = store.init(lv)
        if async_on:
            _group(store).predicted_fits = True   # force queued overflow
        red = store.on_write(red, events={"w": ALL, "e": ALL})
        lv = {k: v + 1 for k, v in lv.items()}
        red, _ = store.tick(lv, red, 1)           # async: in flight
        assert store.scrub_check(lv, red) == 0    # settles internally
        # the caller's red must still be alive and tickable
        red = store.on_write(red, events={"w": jnp.zeros((24,), bool)
                                          .at[2].set(True)})
        lv = dict(lv, w=lv["w"].at[2].add(0.5))
        red, _ = store.tick(lv, red, 2)
        red = store.settle(red, lv)
        outs.append(red)
        assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
    _assert_red_equal(outs[0], outs[1])


def test_in_flight_blocks_stay_conservatively_marked():
    """Between dispatch and resolution the live view must keep the consumed
    snapshot marked (shadow) so accounting and recovery treat those blocks
    as vulnerable, and the returned dirty bitmap is the fresh epoch B."""
    store = _store(True, period=2)
    lv = _leaves()
    red = store.init(lv)
    ev = jnp.zeros((24,), bool).at[jnp.array([0, 3])].set(True)
    red = store.on_write(red, events={"w": ev})
    lv = dict(lv, w=lv["w"].at[0].add(1.0).at[3].add(1.0))
    red, _ = store.tick(lv, red, 2)
    assert _group(store).pending is not None
    assert int(bits.popcount(red["w"].dirty)) == 0          # fresh epoch B
    assert int(bits.popcount(red["w"].shadow)) > 0          # snapshot A
    stats = store.dirty_stats(red)
    assert int(stats["w"]["dirty_blocks"]) > 0              # conservative


def test_coalescing_folds_due_ticks_into_inflight_update(monkeypatch):
    """Due ticks arriving while an update is outstanding coalesce (at most
    one in flight); the deferred update dispatches on resolution."""
    store = _store(True, period=1)
    lv = _leaves()
    red = store.init(lv)
    red = store.on_write(red, events={"w": jnp.zeros((24,), bool)
                                      .at[0].set(True)})
    red, _ = store.tick(lv, red, 1)               # dispatch
    g = _group(store)
    first = g.pending
    assert first is not None
    monkeypatch.setattr(store_mod, "_ready", lambda x: False)
    red, rep = store.tick(lv, red, 2)             # due, but still "in flight"
    assert rep.coalesced and rep.updated
    assert g.pending is first and first.coalesced == 1
    monkeypatch.undo()
    store.sync_inflight()
    red, rep = store.tick(lv, red, 3)             # resolves + deferred fires
    assert g.pending is not None and g.pending.step == 3
    red = store.settle(red, lv)
    assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0


def test_no_queue_fits_round_trip_on_async_hot_path(monkeypatch):
    """Acceptance: a due tick must never pay the host-side queue_fits
    round trip on the overlap-pipelined path."""
    store = _store(True, period=1)
    lv = _leaves()
    red = store.init(lv)

    def boom(*a, **k):
        raise AssertionError("queue_fits called on the async hot path")

    for g in store.groups.values():
        # queue_fits goes through queue_check, the blocking path's probe
        monkeypatch.setattr(g.engine, "queue_check", boom)
    for step in range(1, 6):
        red = store.on_write(red, events={"w": jnp.zeros((24,), bool)
                                          .at[step % 24].set(True)})
        lv = dict(lv, w=lv["w"].at[step % 24].add(0.5))
        red, _ = store.tick(lv, red, step)        # would raise if it synced
    monkeypatch.undo()
    red = store.settle(red, lv)
    assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0


def test_attach_precompiles_update_variants():
    """Satellite: attach warms both Algorithm-1 variants (plus the epoch
    swap) so the first due tick never hides a compile stall: the tick's
    batched program at the full variant and at every rung of the queued
    one, and settle's single-group full fallback."""
    store = _store(True)
    g = _group(store)
    label = g.label
    assert (label, "async_full") in store._jit_update
    assert ((label,), ("async_full",)) in store._jit_update
    assert g.engine.queue_ladder
    for k in g.engine.queue_ladder:
        assert ((label,), (f"async_queued@{k}",)) in store._jit_update
    assert (label, "swap") in store._jit_misc
    blocking = _store(False)
    label = _group(blocking).label
    assert (label, "full") in blocking._jit_update
    assert (label, "queued") in blocking._jit_update
    cold = _store(True, precompile=False)
    assert not cold._jit_update


def test_blocking_flush_seeds_speculation():
    """flush's exact queue_fits answer becomes the next fit prediction."""
    store = _store(True, period=4)
    lv = _leaves()
    red = store.init(lv)
    assert _group(store).predicted_fits is False  # pessimistic start
    ev = jnp.zeros((24,), bool).at[0].set(True)   # sparse: fits
    red = store.on_write(red, events={"w": ev})
    lv = dict(lv, w=lv["w"].at[0].add(1.0))
    red = store.flush(lv, red, step=0)
    assert _group(store).predicted_fits is True


def test_deadline_forces_resolution_and_update(monkeypatch):
    """An overdue freshness deadline must block-resolve the in-flight
    update rather than coalesce forever."""
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=100, max_vulnerable_steps=2,
        lanes_per_block=128, async_tick=True)
    store = ProtectedStore(pol).attach(_leaves())
    lv = _leaves()
    red = store.init(lv)
    red = store.on_write(red, events={"w": ALL})
    red, rep = store.tick(lv, red, 2)             # overdue -> dispatch
    assert rep.updated and rep.deadline_fired
    monkeypatch.setattr(store_mod, "_ready", lambda x: False)
    red = store.on_write(red, events={"w": ALL})
    red, rep = store.tick(lv, red, 4)             # overdue again: must not
    assert rep.updated                            # coalesce past the deadline
    assert _group(store).pending.step == 4
