"""Batched multi-group dispatch + off-thread tick resolver (PR10).

Covers the sharded-overlap regression fix at the unit level: a due tick
dispatches ONE batched update program for every due vilamb group, the
device->host fit fetch is owned by the resolver thread (or starts at
dispatch time in inline mode — never inside ``_resolve``), the resolver
thread's lifecycle is bounded by flush, and ``step`` threads through
settle/flush as an explicit Optional (step 0 is a real step, not
"unknown").  Multi-device batching is covered in tests/test_sharded.py.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.store as store_mod
from repro.core import LeafPolicy, ProtectedStore, RedundancyPolicy

RED_FIELDS = ("checksums", "parity", "dirty", "shadow", "meta_ck")


def _leaves(seed=0):
    return {"w": jax.random.normal(jax.random.PRNGKey(seed), (24, 200),
                                   jnp.float32),
            "e": jax.random.normal(jax.random.PRNGKey(seed + 1), (16, 64),
                                   jnp.bfloat16)}


def _store(period=1, dispatcher_thread=True, **kw):
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=period, lanes_per_block=128,
        work_queue_frac=0.5, async_tick=True, precompile=False,
        dispatcher_thread=dispatcher_thread, **kw)
    return ProtectedStore(pol).attach(_leaves())


def _group(store):
    return next(iter(store.groups.values()))


def _write(store, red, rows=(0,)):
    ev = jnp.zeros((24,), bool).at[jnp.asarray(list(rows))].set(True)
    return store.on_write(red, events={"w": ev})


def _dispatch_threads():
    return [t for t in threading.enumerate()
            if t.name == "repro-dispatch" and t.is_alive()]


@pytest.fixture()
def mkstore():
    """Store factory that joins any resolver thread at test teardown, so
    one test's parked daemon thread never leaks into the next."""
    stores = []

    def make(**kw):
        s = _store(**kw)
        stores.append(s)
        return s

    yield make
    for s in stores:
        s._stop_dispatcher()


# ------------------------------------------------------------- batching

def test_multigroup_due_tick_is_one_batched_launch():
    """Two due vilamb groups -> exactly one ``_update_many_fn`` call per
    due tick carrying both labels, sharing one stacked fits vector and
    one resolver event; the per-group programs never launch."""
    pol = RedundancyPolicy(
        default=LeafPolicy(mode="vilamb", period_steps=2,
                           work_queue_frac=0.5),
        rules=(("e", LeafPolicy(mode="vilamb", period_steps=2,
                                work_queue_frac=0.0)),),
        lanes_per_block=128, async_tick=True, precompile=False)
    store = ProtectedStore(pol).attach(_leaves())
    groups = list(store._protected())
    assert len(groups) == 2
    many_calls, single_calls = [], []
    orig_many = store._update_many_fn
    store._update_many_fn = lambda labels, variants: (
        many_calls.append((labels, variants)),
        orig_many(labels, variants))[1]
    orig = store._update_fn
    store._update_fn = lambda label, variant: (
        single_calls.append((label, variant)), orig(label, variant))[1]
    lv = _leaves()
    red = store.init(lv)
    for step in (1, 2, 3, 4):
        red = store.on_write(red, events={
            "w": jnp.zeros((24,), bool).at[step].set(True),
            "e": jnp.zeros((16,), bool).at[step].set(True)})
        store.sync_inflight()
        n = len(many_calls)
        red, _ = store.tick(lv, red, step)
        if step % 2 == 0:
            assert len(many_calls) == n + 1, many_calls
            labels, _variants = many_calls[-1]
            assert sorted(labels) == sorted(g.label for g in groups)
            p0, p1 = (g.pending for g in groups)
            assert p0 is not None and p1 is not None
            assert p0.fits is p1.fits          # one stacked fits vector
            assert p0.launched is p1.launched  # one resolver event
            assert p0.fits.shape == (2,), p0.fits.shape
            assert (p0.fits_index, p1.fits_index) == (0, 1)
        else:
            assert len(many_calls) == n
    assert not single_calls, single_calls
    red = store.settle(red, lv)
    assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
    store._stop_dispatcher()


def test_dispatcher_modes_bitwise_identical(mkstore):
    """dispatcher_thread on/off settle to bitwise-identical red state."""
    outs = []
    for thread_on in (True, False):
        store = mkstore(period=2, dispatcher_thread=thread_on)
        lv = _leaves()
        red = store.init(lv)
        for step in range(1, 8):
            rows = [(step * 3) % 24, (step * 7) % 24]
            lv = dict(lv, w=lv["w"].at[jnp.asarray(rows)].add(0.25 * step))
            red = _write(store, red, rows)
            red, _ = store.tick(lv, red, step)
        red = store.settle(red, lv)
        outs.append(red)
        assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0
    for k in outs[0]:
        for f in RED_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(outs[0][k], f)),
                np.asarray(getattr(outs[1][k], f)), err_msg=f"{k}.{f}")


# --------------------------------------------- resolve never syncs device

class _NoAsyncFits:
    """Stand-in for a backend array without ``copy_to_host_async``:
    counts host conversions so the test can pin down WHEN the fetch
    happened."""

    def __init__(self, arr):
        self._arr = np.asarray(arr)
        self.conversions = 0

    @property
    def shape(self):
        return self._arr.shape

    def is_ready(self):
        return True

    def __array__(self, dtype=None):
        self.conversions += 1
        return self._arr if dtype is None else self._arr.astype(dtype)


def test_inline_fallback_fetch_happens_at_dispatch_not_resolve():
    """Satellite regression: without ``copy_to_host_async`` the fit fetch
    must run at dispatch time — ``_resolve`` reads the cached host bool,
    never converting the device array."""
    store = _store(period=1, dispatcher_thread=False)
    proxies = []
    orig_many = store._update_many_fn

    def wrapped(labels, variants):
        fn = orig_many(labels, variants)

        def call(subs, reds):
            outs, fits, stripes = fn(subs, reds)
            proxy = _NoAsyncFits(fits)
            proxies.append(proxy)
            return outs, proxy, np.asarray(stripes)

        return call

    store._update_many_fn = wrapped
    lv = _leaves()
    red = store.init(lv)
    red = _write(store, red, (1,))
    red, _ = store.tick(lv, red, 1)             # dispatch
    p = _group(store).pending
    assert p is not None and proxies, "expected an overlapped dispatch"
    assert proxies[-1].conversions == 1, \
        "fallback fetch must run once, at dispatch time"
    assert p.fits_host is not None
    red = _write(store, red, (2,))
    red, rep = store.tick(lv, red, 2)           # adopts the pending
    assert rep.updated
    assert proxies[0].conversions == 1, \
        "_resolve must not convert the device array (no sync in resolve)"


def test_threaded_resolve_reads_cached_host_bool(monkeypatch, mkstore):
    """With the resolver thread, adoption after the join reads the folded
    host bool — poisoning the fold function proves it is not re-run on
    the tick thread."""
    store = mkstore(period=3, dispatcher_thread=True)
    lv = _leaves()
    red = store.init(lv)
    for step in (1, 2, 3):                      # dispatches at step 3
        red = _write(store, red, (step,))
        red, _ = store.tick(lv, red, step)
    store.sync_inflight()
    p = _group(store).pending
    assert p is not None and p.fits_host is not None, \
        "resolver thread must have folded the fit signal to a host bool"

    def boom(row):
        raise AssertionError("fold_fits_host re-run at resolution")

    monkeypatch.setattr(store_mod.workqueue, "fold_fits_host", boom)
    red, _ = store.tick(lv, red, 4)             # not due: lazy adoption only
    assert _group(store).pending is None, "pending must have been adopted"
    monkeypatch.undo()
    red = store.settle(red, lv)
    assert sum(int(v.sum()) for v in store.scrub(lv, red).values()) == 0


# ------------------------------------------------------------- lifecycle

def test_resolver_thread_lifecycle_bounded_by_flush(mkstore):
    """The resolver thread spins up lazily at the first overlapped
    dispatch and flush joins it — no thread outlives the quiescent
    point."""
    before = set(_dispatch_threads())
    store = mkstore(period=1, dispatcher_thread=True)
    assert store._dispatcher is None
    lv = _leaves()
    red = store.init(lv)
    red = _write(store, red, (0,))
    red, _ = store.tick(lv, red, 1)
    d = store._dispatcher
    assert d is not None and d.thread.is_alive()
    assert d.thread.daemon and d.thread.name == "repro-dispatch"
    red = store.flush(lv, red, step=1)
    assert store._dispatcher is None and not d.thread.is_alive(), \
        "flush must join the resolver thread"
    assert set(_dispatch_threads()) <= before, \
        "flush must not leave this store's resolver thread behind"
    # re-created lazily by the next overlapped dispatch
    red = _write(store, red, (2,))
    red, _ = store.tick(lv, red, 2)
    assert store._dispatcher is not None and store._dispatcher is not d
    red = store.settle(red, lv)


def test_inline_mode_never_creates_thread():
    before = set(_dispatch_threads())
    store = _store(period=1, dispatcher_thread=False)
    lv = _leaves()
    red = store.init(lv)
    red = _write(store, red, (0,))
    red, _ = store.tick(lv, red, 1)
    assert _group(store).pending is not None
    assert store._dispatcher is None
    assert set(_dispatch_threads()) <= before
    red = store.settle(red, lv)


# --------------------------------------------------- Optional step threading

def test_flush_step_zero_is_a_real_step_stamp():
    """Step 0 must stamp the freshness clock (the old ``step or 0``
    coercion treated it as "unknown" and skipped the stamp)."""
    store = _store(period=100, max_vulnerable_steps=2)
    lv = _leaves()
    red = store.init(lv)
    g = _group(store)
    g.last_update_step = 5          # pretend restored history
    red = store.flush(lv, red, step=0)
    assert g.last_update_step == 0, \
        "flush(step=0) must stamp the clock at step 0"
    red = _write(store, red, (0,))
    red, rep = store.tick(lv, red, 1)
    assert not rep.deadline_fired, \
        "deadline must count from the stamped step 0 (1 - 0 < 2)"
    red, rep = store.tick(lv, red, 2)
    assert rep.deadline_fired, "2 - 0 >= 2: deadline due now"
    store._stop_dispatcher()


def test_settle_phase_stamps_step_zero_and_omits_unknown(mkstore):
    """settle(step=0) stamps its dispatcher_join phase with step 0;
    settle() without a step omits the key entirely (so replay hooks can
    fill in their own counter) — None is never coerced to 0."""
    store = mkstore(period=1, dispatcher_thread=True)
    lv = _leaves()
    red = store.init(lv)
    seen = []
    store.add_phase_hook(lambda phase, info: seen.append((phase, info)))

    red = _write(store, red, (0,))
    red, _ = store.tick(lv, red, 1)
    assert _group(store).pending is not None
    red = store.settle(red, lv, step=0)
    joins = [i for ph, i in seen if ph == "dispatcher_join"]
    assert joins and joins[-1]["step"] == 0

    seen.clear()
    red = _write(store, red, (1,))
    red, _ = store.tick(lv, red, 2)
    assert _group(store).pending is not None
    red = store.settle(red, lv)
    joins = [i for ph, i in seen if ph == "dispatcher_join"]
    assert joins and "step" not in joins[-1]


class _NeverReady:
    """Device-array stand-in whose readiness notification never arrives
    (the value is computable, only ``is_ready`` lies — the CPU-backend
    hazard when a blocking transfer runs concurrently on the resolver
    thread)."""

    def is_ready(self):
        return False

    def __array__(self, dtype=None):
        return np.zeros((), dtype=dtype or bool)


def test_pending_ready_trusts_resolver_event_not_device_notification():
    """Thread mode: once the resolver event is set the folded fit bit is
    published — a stuck ``is_ready`` on the device array must not make
    the pending look in-flight (it would starve resolution behind a
    phantom signal).  Inline mode still gates on device readiness."""
    ev = threading.Event()
    p = store_mod._Pending(red=None, fits=_NeverReady(), queued=False,
                           step=1, launched=ev, fits_host=None)
    assert not store_mod._pending_ready(p), "resolver not done yet"
    ev.set()
    p.fits_host = True
    assert store_mod._pending_ready(p), \
        "event set + published bit => ready, device notification ignored"
    inline = store_mod._Pending(red=None, fits=_NeverReady(), queued=False,
                                step=1, launched=None)
    assert not store_mod._pending_ready(inline), \
        "inline mode still trusts the device readiness probe"


def test_patrol_probe_forces_fetch_past_stuck_readiness(monkeypatch):
    """A patrol probe whose ``is_ready`` never flips must not starve the
    patroller forever (it holds the single outstanding-probe slot): after
    PROBE_FORCE_TICKS process attempts the fetch is forced and the sweep
    continues."""
    import repro.scrub.patrol as patrol_mod

    pol = RedundancyPolicy.single(
        "vilamb", period_steps=2, lanes_per_block=8, async_tick=True,
        patrol_bytes_per_tick=2 * 8 * 4, precompile=False)
    lv = _leaves()
    store = ProtectedStore(pol).attach(lv)
    red = store.init(lv)
    monkeypatch.setattr(patrol_mod, "_ready", lambda x: False)
    patrolled = 0
    for step in range(1, 4 * patrol_mod.PROBE_FORCE_TICKS + 2):
        red, rep = store.tick(lv, red, step, scrub_period=0)
        patrolled += len(rep.patrolled)
    assert patrolled >= 2, \
        "stuck readiness must force-resolve, not wedge the probe slot"
    store._stop_dispatcher()
