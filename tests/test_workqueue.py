"""Work-queue compaction path (core/workqueue.py): bitwise identity with the
reference Algorithm-1 update, overflow dispatch, partial-stripe padding,
incremental meta-checksums, and the segment-XOR sync row path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ALL, ProtectedStore, RedundancyConfig,
                        RedundancyEngine, RedundancyPolicy, bits, checksum,
                        workqueue)
from repro.core import blocks as B

RED_FIELDS = ("checksums", "parity", "dirty", "shadow", "meta_ck")


def _mk(frac=0.5, seed=0):
    """24x200 f32 leaf: 38 blocks, 10 stripes (last one partial: 2 blocks)."""
    leaves = {
        "w": jax.random.normal(jax.random.PRNGKey(seed), (24, 200), jnp.float32),
        "e": jax.random.normal(jax.random.PRNGKey(seed + 1), (16, 64), jnp.bfloat16),
    }
    structs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in leaves.items()}
    eng = RedundancyEngine(structs, RedundancyConfig(
        lanes_per_block=128, stripe_data_blocks=4, work_queue_frac=frac))
    return eng, leaves


def _assert_red_equal(a, b):
    for k in a:
        for f in RED_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(a[k], f)), np.asarray(getattr(b[k], f)),
                err_msg=f"{k}.{f}")


def test_queue_capacity_derivation():
    eng, _ = _mk(frac=0.5)
    assert eng.metas["w"].n_stripes == 10
    assert eng.queue_capacity("w") == 5           # ceil(10 * 0.5)
    assert eng.queue_capacity("e") == 0           # 1 stripe: queue pointless
    assert eng.has_queue
    eng_off, _ = _mk(frac=0.0)
    assert not eng_off.has_queue


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_queued_bitwise_identical_random_masks(seed):
    """Compacted and reference redundancy_step agree bitwise for random
    dirty block masks that fit the queue (incl. the padded last stripe)."""
    eng, leaves = _mk(frac=0.5)
    red = eng.init(leaves)
    rng = np.random.default_rng(seed)
    # <= 5 dirty stripes on w (fits capacity 5); random row events on e
    # (capacity 0 there: always full path, must still agree)
    stripes = rng.choice(10, size=rng.integers(0, 6), replace=False)
    bmask = np.zeros((38,), bool)
    for s in stripes:
        blks = np.arange(s * 4, min((s + 1) * 4, 38))
        bmask[rng.choice(blks, size=rng.integers(1, len(blks) + 1),
                         replace=False)] = True
    red = eng.mark_dirty(red, {"e": jnp.asarray(rng.random(16) < 0.3)})
    red = {"w": dataclasses.replace(
        red["w"], dirty=bits.mark(red["w"].dirty, jnp.asarray(bmask))),
        "e": red["e"]}
    leaves2 = {k: v + 1 for k, v in leaves.items()}
    assert eng.queue_fits(red)
    _assert_red_equal(eng.redundancy_step_queued(leaves2, red),
                      eng.redundancy_step(leaves2, red))


def test_partial_last_stripe_queued():
    """Dirty bits in the padded last stripe (2 of 4 member blocks exist)."""
    eng, leaves = _mk(frac=0.5)
    red = eng.init(leaves)
    bmask = jnp.zeros((38,), bool).at[jnp.array([36, 37])].set(True)
    red = {"w": dataclasses.replace(
        red["w"], dirty=bits.mark(red["w"].dirty, bmask)), "e": red["e"]}
    # mutate only data inside the marked blocks (elem 4750 -> lane 4750
    # -> block 37), so clean blocks stay scrub-consistent
    leaves2 = dict(leaves, w=leaves["w"].at[23, 150].add(2.0))
    assert eng.queue_fits(red)
    out_q = eng.redundancy_step_queued(leaves2, red)
    _assert_red_equal(out_q, eng.redundancy_step(leaves2, red))
    # postcondition: scrub-clean and verifiable meta
    assert all(int(v.sum()) == 0 for v in eng.scrub(leaves2, out_q).values())
    assert all(bool(v) for v in eng.verify_meta(out_q).values())


def test_queue_overflow_detected_and_full_fallback():
    """fits==False past capacity; the store then dispatches the reference
    program, so state stays bitwise-identical to a no-queue engine."""
    eng, leaves = _mk(frac=0.5)
    red = eng.init(leaves)
    red_all = eng.mark_dirty(red, {"w": ALL, "e": ALL})
    assert not eng.queue_fits(red_all)            # 10 stripes > capacity 5
    # boundary: exactly capacity stripes still fits
    bmask = jnp.zeros((38,), bool).at[jnp.arange(5) * 4].set(True)
    red_fit = {"w": dataclasses.replace(
        red["w"], dirty=bits.mark(red["w"].dirty, bmask)), "e": red["e"]}
    assert eng.queue_fits(red_fit)

    pol_q = RedundancyPolicy.single("vilamb", period_steps=1,
                                    lanes_per_block=128, work_queue_frac=0.5)
    pol_f = RedundancyPolicy.single("vilamb", period_steps=1,
                                    lanes_per_block=128, work_queue_frac=0.0)
    leaves2 = {k: v + 3 for k, v in leaves.items()}
    outs = []
    for pol in (pol_q, pol_f):
        store = ProtectedStore(pol).attach(leaves)
        r0 = store.init(leaves)
        r0 = store.on_write(r0, events={"w": ALL, "e": ALL})  # overflow
        r1, rep = store.tick(leaves2, r0, 1)
        assert rep.updated
        # settle adopts the overlapped dispatch (and would repair a
        # speculative overflow via the full fallback) before comparing
        outs.append(store.settle(r1, leaves2))
    _assert_red_equal(outs[0], outs[1])


def test_store_tick_dispatches_queued_and_matches_reference():
    """Sparse dirty state through store.tick (speculative queued dispatch
    once the fit signal resolves) must equal a work-queue-disabled store
    byte for byte."""
    _, leaves = _mk()
    ev = jnp.zeros((24,), bool).at[jnp.array([0, 7])].set(True)
    outs = []
    for frac in (0.5, 0.0):
        pol = RedundancyPolicy.single("vilamb", period_steps=1,
                                      lanes_per_block=128,
                                      work_queue_frac=frac)
        store = ProtectedStore(pol).attach(leaves)
        r0 = store.init(leaves)
        lv = dict(leaves)
        # two rounds: the pessimistic first dispatch goes full and resolves
        # the fit signal; the second round then speculates queued
        for step in (1, 2):
            r0 = store.on_write(r0, events={"w": ev})
            # only the marked rows change (dirty tracking covers every write)
            lv = dict(lv, w=lv["w"].at[jnp.array([0, 7])].add(-0.5 * step))
            r1, rep = store.tick(lv, r0, step)
            assert rep.updated
            r0 = r1
            # deterministic resolution timing (joins the launch thread too)
            store.sync_inflight()
        if frac > 0:
            g = next(iter(store.groups.values()))
            if store.policy.async_tick:    # overlap: speculation went queued
                assert g.pending is not None and g.pending.queued
            else:                          # blocking: exact fit check agreed
                assert g.predicted_fits
        r1 = store.settle(r1, lv)
        outs.append(r1)
        assert sum(int(v.sum()) for v in store.scrub(lv, r1).values()) == 0
    _assert_red_equal(outs[0], outs[1])


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_incremental_meta_checksum_matches_full(seed):
    """meta ^ meta_checksum_delta(changed) == full rehash, bitwise."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 200))
    cks = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
    k = int(rng.integers(0, n + 1))
    idx = rng.choice(n, size=k, replace=False).astype(np.int32)
    new_vals = jnp.asarray(rng.integers(0, 2**32, size=k, dtype=np.uint32))
    cks2 = cks.at[jnp.asarray(idx)].set(new_vals) if k else cks
    meta0 = checksum.meta_checksum(cks)
    delta = checksum.meta_checksum_delta(
        cks[jnp.asarray(idx)], new_vals, jnp.asarray(idx)) if k else jnp.uint32(0)
    np.testing.assert_array_equal(
        np.asarray(meta0 ^ delta), np.asarray(checksum.meta_checksum(cks2)))


def test_sync_update_rows_duplicate_stripe_regression():
    """Unique rows sharing a stripe must XOR-accumulate parity deltas (the
    segment-XOR scatter), matching the dense sync_update oracle — including
    the incremental meta-checksum; order of rows must not matter."""
    heap = jax.random.normal(jax.random.PRNGKey(2), (16, 32), jnp.float32)
    eng = RedundancyEngine(
        {"h": jax.ShapeDtypeStruct(heap.shape, heap.dtype)},
        RedundancyConfig(mode="sync", lanes_per_block=32, stripe_data_blocks=4))
    red = eng.init({"h": heap})
    for rows in ([0, 1, 2, 9], [9, 2, 0, 1], [4, 5, 6, 7], [15]):
        rows = jnp.asarray(rows, jnp.int32)
        new_rows = heap[rows] + 3.0
        new_heap = heap.at[rows].set(new_rows)
        got = eng.sync_update_rows("h", red["h"], rows, heap[rows], new_rows)
        want = eng.sync_update({"h": heap}, {"h": new_heap}, red)["h"]
        np.testing.assert_array_equal(np.asarray(got.checksums),
                                      np.asarray(want.checksums))
        np.testing.assert_array_equal(np.asarray(got.parity),
                                      np.asarray(want.parity))
        np.testing.assert_array_equal(np.asarray(got.meta_ck),
                                      np.asarray(want.meta_ck))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_row_mask_block_mask_matches_nonzero_oracle(seed):
    """mark_dirty's direct row->block reduction == nonzero + row_block_mask
    across straddling and packed row geometries."""
    rng = np.random.default_rng(seed)
    for shape, lanes in (((24, 200), 128), ((16, 64), 128), ((7, 130), 128),
                         ((5, 7, 11), 64), ((64, 32), 128)):
        meta = B.make_meta(jax.ShapeDtypeStruct(shape, jnp.float32),
                           lanes_per_block=lanes, stripe_data_blocks=4)
        m = rng.random(shape[0]) < rng.random()
        got = B.row_mask_block_mask(meta, jnp.asarray(m), row_dims=1)
        ids = (jnp.asarray(np.flatnonzero(m).astype(np.int32))
               if m.any() else jnp.asarray([-1], jnp.int32))
        want = B.row_block_mask(meta, ids, row_dims=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"{shape} lanes={lanes}")


def test_compact_stripe_ids_contract():
    sd = jnp.asarray([False, True, False, True, True, False])
    ids, count, overflow = workqueue.compact_stripe_ids(sd, 4)
    assert ids.tolist() == [1, 3, 4, 6] and int(count) == 3 and not bool(overflow)
    ids, count, overflow = workqueue.compact_stripe_ids(sd, 2)
    assert int(count) == 3 and bool(overflow)
    # kernel convention: pad by repeating the last live id
    ids, count, _ = workqueue.compact_stripe_ids(sd, 6, pad_repeat_last=True)
    assert ids.tolist() == [1, 3, 4, 4, 4, 4]


# Adversarial payloads: float32 NaN/Inf bit patterns and saturated words.
# The redundancy path is pure bit manipulation — special float values must
# round-trip bitwise and never weaken detection.
SPECIALS = np.array([0x7FC00000, 0x7F800000, 0xFF800000, 0x7F800001,
                     0x00000000, 0xFFFFFFFF], dtype=np.uint32)


def test_nan_inf_payloads_bitwise_identical_and_detected():
    """NaN/Inf-laden leaves: queued == full bitwise, scrub stays clean, and
    a single-bit NaN->Inf flip on a clean block is still caught."""
    eng, leaves = _mk(frac=0.5)
    shape = leaves["w"].shape
    pattern = SPECIALS[np.arange(np.prod(shape)) % len(SPECIALS)]
    leaves = dict(leaves, w=jnp.asarray(
        pattern.reshape(shape).view(np.float32)))
    red = eng.init(leaves)
    bmask = jnp.zeros((38,), bool).at[jnp.array([0, 1, 17])].set(True)
    red = {"w": dataclasses.replace(
        red["w"], dirty=bits.mark(red["w"].dirty, bmask)), "e": red["e"]}
    # overwrite the dirty blocks with a *different* special pattern
    meta = eng.metas["w"]
    lanes = B.to_lanes(leaves["w"], meta)
    rolled = jnp.asarray(np.roll(SPECIALS, 1)[
        np.arange(meta.lanes_per_block) % len(SPECIALS)].astype(np.uint32))
    for b in (0, 1, 17):
        lanes = lanes.at[b].set(rolled)
    leaves2 = dict(leaves, w=B.from_lanes(lanes, meta))
    assert eng.queue_fits(red)
    out_q = eng.redundancy_step_queued(leaves2, red)
    _assert_red_equal(out_q, eng.redundancy_step(leaves2, red))
    assert all(int(v.sum()) == 0 for v in eng.scrub(leaves2, out_q).values())
    # NaN (0x7FC00000) -> +Inf (0x7F800000) is one bit (22) on a clean block
    corrupt = B.from_lanes(
        B.to_lanes(leaves2["w"], meta).at[20, 4].set(
            B.to_lanes(leaves2["w"], meta)[20, 4] ^ jnp.uint32(1 << 22)),
        meta)
    mm = eng.scrub(dict(leaves2, w=corrupt), out_q)
    assert np.flatnonzero(np.asarray(mm["w"])).tolist() == [20]


def test_zero_dirty_update_is_bitwise_noop():
    """Zero dirty bits: both Algorithm-1 variants and a due store tick must
    leave every redundancy field bitwise untouched (sentinel-only queues)."""
    eng, leaves = _mk(frac=0.5)
    red = eng.init(leaves)
    _assert_red_equal(eng.redundancy_step(leaves, red), red)
    _assert_red_equal(eng.redundancy_step_queued(leaves, red), red)
    for async_on in (True, False):
        pol = RedundancyPolicy.single(
            "vilamb", period_steps=1, lanes_per_block=128,
            work_queue_frac=0.5, async_tick=async_on)
        store = ProtectedStore(pol).attach(leaves)
        r0 = store.init(leaves)
        r0_host = jax.tree.map(np.asarray, r0)  # blocking tick donates r0
        r1, rep = store.tick(leaves, r0, 1)     # due, nothing dirty
        assert rep.updated
        _assert_red_equal(store.settle(r1, leaves), r0_host)


def test_exactly_at_capacity_queue_including_partial_stripe():
    """Dirty stripes == capacity exactly, with the sentinel-adjacent last
    (partial, 2-block) stripe in the set: queued must match full bitwise."""
    eng, leaves = _mk(frac=0.5)
    assert eng.queue_capacity("w") == 5
    red = eng.init(leaves)
    # stripes {0, 3, 5, 7, 9}; 9 is the partial last stripe (blocks 36, 37)
    blks = jnp.array([0, 12, 20, 28, 36, 37])
    bmask = jnp.zeros((38,), bool).at[blks].set(True)
    red = {"w": dataclasses.replace(
        red["w"], dirty=bits.mark(red["w"].dirty, bmask)), "e": red["e"]}
    meta = eng.metas["w"]
    lanes = B.to_lanes(leaves["w"], meta)
    for b in [0, 12, 20, 28, 36, 37]:
        lanes = lanes.at[b, 0].add(jnp.uint32(b + 1))
    leaves2 = dict(leaves, w=B.from_lanes(lanes, meta))
    assert eng.queue_fits(red)
    out_q = eng.redundancy_step_queued(leaves2, red)
    _assert_red_equal(out_q, eng.redundancy_step(leaves2, red))
    assert all(int(v.sum()) == 0 for v in eng.scrub(leaves2, out_q).values())
    # one more stripe is one too many
    over = {"w": dataclasses.replace(
        red["w"], dirty=bits.mark(red["w"].dirty,
                                  jnp.zeros((38,), bool).at[4].set(True))),
        "e": red["e"]}
    assert not eng.queue_fits(over)


def test_sentinel_colliding_ids_drop_not_wrap():
    """ids equal to the sentinel (n_stripes / n_blocks) must be dropped by
    every scatter — never wrap around or clobber stripe 0."""
    from repro.core import parity
    par = jnp.arange(12, dtype=jnp.uint32).reshape(3, 4)
    deltas = jnp.full((2, 4), 0xFFFFFFFF, jnp.uint32)
    out = parity.scatter_xor_stripes(
        par, jnp.asarray([3, 3], jnp.int32), deltas)   # 3 == ns sentinel
    np.testing.assert_array_equal(np.asarray(out), np.asarray(par))
    # queued_update with an all-sentinel queue over special-value lanes
    lanes = jnp.asarray(SPECIALS[np.arange(8 * 128) % len(SPECIALS)]
                        .reshape(8, 128))
    old_cks = checksum.block_checksums(lanes)
    old_par = jnp.zeros((2, 128), jnp.uint32)
    ids = jnp.full((4,), 2, jnp.int32)                 # 2 == n_stripes here
    cks, par2, meta = workqueue.queued_update(
        lanes, old_cks, old_par, checksum.meta_checksum(old_cks),
        jnp.zeros((8,), bool), ids, 4)
    np.testing.assert_array_equal(np.asarray(cks), np.asarray(old_cks))
    np.testing.assert_array_equal(np.asarray(par2), np.asarray(old_par))
    np.testing.assert_array_equal(
        np.asarray(meta), np.asarray(checksum.meta_checksum(old_cks)))


def test_queued_preserves_scrub_detection():
    """After a queued pass, corruption of a *clean* block is still caught —
    checksums of untouched blocks must not be disturbed by the scatter."""
    eng, leaves = _mk(frac=0.5)
    red = eng.init(leaves)
    red = eng.mark_dirty(red, {"w": jnp.zeros((24,), bool).at[0].set(True)})
    leaves2 = dict(leaves, w=leaves["w"].at[0, 0].add(1.0))
    red = eng.redundancy_step_queued(leaves2, red)
    meta = eng.metas["w"]
    lanes = B.to_lanes(leaves2["w"], meta)
    corrupted = B.from_lanes(lanes.at[20, 3].add(99), meta)
    mm = eng.scrub(dict(leaves2, w=corrupted), red)
    assert np.flatnonzero(np.asarray(mm["w"])).tolist() == [20]


# ------------------------------------------------------- the rung ladder
TOP_2G = (256, 1024, 4096, 16384)


@pytest.mark.parametrize("n_stripes, frac, ladder", [
    (131072, 0.125, TOP_2G),          # a 2 GiB heap of 4 KiB pages, 4+1
    (65536, 0.125, (512, 2048, 8192)),  # one shard of four over 4 GiB
    (20000, 0.25, (312, 1250, 5000)),
    (8000, 0.125, (1000,)),           # 250 would fall under the floor
    (10, 0.5, (5,)),                  # a top under 256 is its own rung
    (1, 0.5, ()),                     # no queue, no ladder
])
def test_queue_ladder_derivation(n_stripes, frac, ladder):
    assert workqueue.queue_ladder(
        workqueue.queue_capacity(n_stripes, frac)) == ladder


@pytest.mark.parametrize("stripes, rung", [
    (0, 256), (204, 256), (205, 1024), (819, 1024), (931, 4096),
    (13107, 16384), (13108, 16384), (16384, 16384), (16385, 0)])
def test_pick_rung(stripes, rung):
    """Smallest rung with 1.25x room; the top where only the room
    overshoots it; the full program (0) past the top."""
    assert workqueue.pick_rung(TOP_2G, stripes) == rung


BIG_ROWS = 32766                 # 512 B blocks: 8192 stripes, the last of 2
BIG_STRIPES = 8192
BIG_RUNGS = (256, 1024, 4096)    # frac 0.5: top 4096, divided by 4 to 256


def _big_leaves(seed=0):
    words = np.random.default_rng(seed).integers(
        0, 2**32, (BIG_ROWS, 128), dtype=np.uint32)
    return {"h": jnp.asarray(words)}


def _big_rows(rng, n, last=False):
    """Rows (= blocks) dirtying ``n`` distinct stripes, one block each;
    ``last`` puts the partial last stripe among them."""
    end = BIG_STRIPES - 1
    sids = rng.choice(end if last else BIG_STRIPES, size=n - last,
                      replace=False)
    if last:
        sids = np.append(sids, end)
    off = rng.integers(0, 4, size=len(sids))
    return np.sort(sids * 4 + np.where(sids == end, off % 2, off))


def _row_mask(rows):
    mask = np.zeros((BIG_ROWS,), bool)
    mask[rows] = True
    return jnp.asarray(mask)


@pytest.mark.parametrize("rung", BIG_RUNGS)
def test_every_rung_bitwise_identical_to_full(rung):
    """The overlap program at every rung equals the full recompute on
    random masks that fit it (empty, sparse, exactly at the rung with the
    partial last stripe); one stripe more overflows and keeps the whole
    snapshot marked."""
    leaves = _big_leaves()
    eng = RedundancyEngine(
        {"h": jax.ShapeDtypeStruct((BIG_ROWS, 128), jnp.uint32)},
        RedundancyConfig(lanes_per_block=128, stripe_data_blocks=4,
                         work_queue_frac=0.5))
    assert eng.queue_ladder == BIG_RUNGS
    assert eng.queue_rows(rung) == rung
    red = eng.init(leaves)
    step = jax.jit(lambda lv, rd: eng.redundancy_step_async(
        lv, rd, queued=True, rung=rung))
    rng = np.random.default_rng(rung)
    for n, last in ((0, False), (rung // 3, False), (rung, True),
                    (rung + 1, False)):
        rows = _big_rows(rng, n, last)
        r = eng.mark_dirty(red, {"h": _row_mask(rows)})
        lv = {"h": leaves["h"].at[rows].add(jnp.uint32(n + 1))}
        out, fits, counts = step(lv, r)
        need = n if n <= BIG_RUNGS[-1] else workqueue.NO_RUNG
        assert np.asarray(counts).tolist() == [n, need]
        if n <= rung:
            assert bool(fits)
            _assert_red_equal(out, eng.redundancy_step(lv, r))
        else:
            assert not bool(fits)
            np.testing.assert_array_equal(np.asarray(out["h"].shadow),
                                          np.asarray(r["h"].dirty))


def _big_store():
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=1, lanes_per_block=128, work_queue_frac=0.5,
        async_tick=True, precompile=False)
    return ProtectedStore(pol).attach(_big_leaves())


def _big_tick(store, lv, red, step, rows):
    """Write ``rows``, then a due tick that sees the in-flight pass ready."""
    lv = {"h": lv["h"].at[rows].add(jnp.uint32(step))}
    red = store.on_write(red, events={"h": _row_mask(rows)})
    store.sync_inflight()
    red, rep = store.tick(lv, red, step)
    return lv, red, rep


def _assert_fresh(store, lv, red):
    """After ``settle`` every checksum, stripe and meta checksum is the
    full recompute's, and nothing is left marked."""
    red = store.settle(red, lv)
    eng = next(iter(store.groups.values())).engine
    _assert_red_equal(red, eng.init(lv))


def test_tick_steady_state_dispatches_smallest_fitting_rung():
    """300 dirty stripes a pass: after the pessimistic full first pass,
    every pass runs at 1024 (the smallest rung >= 375, below the top) and
    adds exactly 1024 queue rows."""
    store = _big_store()
    g = next(iter(store.groups.values()))
    lv = _big_leaves()
    red = store.init(lv)
    rng = np.random.default_rng(1)
    for step in range(1, 6):
        before = dict(store.counters)
        lv, red, rep = _big_tick(store, lv, red, step, _big_rows(rng, 300))
        assert rep.updated and not rep.overflowed
        grew = {k: store.counters[k] - before[k] for k in before}
        if step == 1:
            assert g.rung == 0 and not g.pending.queued
        else:
            assert g.rung == 1024 and g.pending.queued
        if step >= 3:
            assert grew["update.queue_rows"] == 1024, grew
            assert grew["update.passes_queued"] == 1, grew
            assert grew["update.passes_full"] == 0, grew
            assert grew["update.stripes"] == 300, grew
    _assert_fresh(store, lv, red)


def test_empty_snapshot_takes_bottom_rung_and_is_bitwise_noop():
    store = _big_store()
    g = next(iter(store.groups.values()))
    lv = _big_leaves()
    red = store.init(lv)
    red0 = jax.tree.map(np.asarray, red)
    red, _ = store.tick(lv, red, 1)              # pessimistic full pass
    store.sync_inflight()
    red, rep = store.tick(lv, red, 2)            # its count: 0 stripes
    assert rep.updated
    assert g.rung == BIG_RUNGS[0] and g.pending.queued
    before = store.counters["update.queue_rows"]
    red = store.settle(red, lv)
    assert store.counters["update.queue_rows"] - before == BIG_RUNGS[0]
    _assert_red_equal(red, red0)


def test_overflow_redispatches_at_fitting_rung_not_full():
    """A burst past the rung the last count chose overflows it; the
    re-dispatch takes the rung that the burst's own count fits (600 ->
    1024, below the top), not the full program, and freshness holds after
    settle."""
    store = _big_store()
    g = next(iter(store.groups.values()))
    lv = _big_leaves()
    red = store.init(lv)
    rng = np.random.default_rng(2)
    for step in (1, 2):                          # full, then rung 256
        lv, red, _ = _big_tick(store, lv, red, step, _big_rows(rng, 100))
    assert g.rung == 256
    full = store.counters["update.passes_full"]
    overflowed = store.counters["update.overflowed"]
    lv, red, rep = _big_tick(store, lv, red, 3, _big_rows(rng, 600))
    assert g.rung == 256 and not rep.overflowed  # chosen from 100
    lv, red, rep = _big_tick(store, lv, red, 4, _big_rows(rng, 100))
    assert rep.overflowed
    assert g.rung == 1024 and g.pending.queued
    lv, red, rep = _big_tick(store, lv, red, 5, _big_rows(rng, 100))
    assert not rep.overflowed
    assert store.counters["update.overflowed"] == overflowed + 1
    assert store.counters["update.passes_full"] == full
    _assert_fresh(store, lv, red)
    assert store.counters["update.passes_full"] == full


def test_rung_follows_the_largest_leaf_not_the_sum():
    """A group's rung is sized by its largest leaf's count: two leaves
    with 2500 dirty stripes each (5000 in all, past the 4096 top, but
    each within its own 4096 queue) stay queued at the top rung; a
    third leaf too small for a queue counts in the sum and not in the
    rung.  No pass falls back to the full program."""
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=1, lanes_per_block=128, work_queue_frac=0.5,
        async_tick=True, precompile=False)
    lv = {"a": _big_leaves(1)["h"], "b": _big_leaves(2)["h"],
          "c": jnp.zeros((8, 128), jnp.uint32)}
    store = ProtectedStore(pol).attach(lv)
    (g,) = store.groups.values()
    assert g.engine.queue_ladder == BIG_RUNGS
    assert [g.engine.queue_capacity(n) for n in "abc"] == [4096, 4096, 0]
    red = store.init(lv)
    rng = np.random.default_rng(3)
    full = None
    for step in range(1, 5):
        rows = {"a": _big_rows(rng, 2500), "b": _big_rows(rng, 2500),
                "c": np.arange(8)}
        lv = {n: lv[n].at[r].add(jnp.uint32(step)) for n, r in rows.items()}
        red = store.on_write(red, events={
            n: jnp.zeros((lv[n].shape[0],), bool).at[r].set(True)
            for n, r in rows.items()})
        store.sync_inflight()
        before = dict(store.counters)
        red, rep = store.tick(lv, red, step)
        assert not rep.overflowed
        grew = {k: store.counters[k] - before[k] for k in before}
        if step == 1:
            continue
        if step == 2:                   # the first pass resolved: full
            full = store.counters["update.passes_full"]
        assert g.rung == BIG_RUNGS[-1] and g.pending.queued, g.rung
        if step >= 3:
            assert grew["update.queue_rows"] == 2 * BIG_RUNGS[-1], grew
            assert grew["update.stripes"] == 2 * 2500 + 2, grew
    red = store.settle(red, lv)
    assert store.counters["update.passes_full"] == full
    _assert_red_equal(red, g.engine.init(lv))


def test_burst_is_covered_within_the_deadline():
    """A burst that climbs over several ticks (10, 300, 900, 3000
    stripes) overflows the rung the last count chose, then the rung its
    own count fits; the second overflow goes to the full program, not up
    the ladder again.  No block stays marked for more than
    max_vulnerable_steps ticks.  Two passes run full: the cover of the
    burst, and the next one, since the burst's 4200 stripes are past the
    top queue."""
    deadline = 2
    pol = RedundancyPolicy.single(
        "vilamb", period_steps=1, lanes_per_block=128, work_queue_frac=0.5,
        max_vulnerable_steps=deadline, async_tick=True, precompile=False)
    store = ProtectedStore(pol).attach(_big_leaves())
    (g,) = store.groups.values()
    lv = _big_leaves()
    red = store.init(lv)
    order = iter(np.random.default_rng(4).permutation(BIG_STRIPES - 1))
    since = np.full((BIG_ROWS,), -1)
    full = overflowed = None
    for step, n in enumerate((10, 10, 300, 900, 3000, 10, 10, 10), 1):
        rows = np.sort([next(order) * 4 for _ in range(n)])
        since[rows[since[rows] < 0]] = step
        lv, red, rep = _big_tick(store, lv, red, step, rows)
        if step == 2:
            full = store.counters["update.passes_full"]
            overflowed = store.counters["update.overflowed"]
        if step in (4, 5):
            assert rep.overflowed, step
        if step == 4:
            assert g.rung == 1024 and g.pending.queued   # fits 300
        if step == 5:
            assert g.rung == 0 and not g.pending.queued  # not 4096
        marked = np.asarray(store.vulnerable_masks(red)["h"])
        since[~marked] = -1
        age = step - since[marked]
        assert age.size == 0 or age.max() <= deadline, (step, age.max())
    assert store.counters["update.overflowed"] == overflowed + 2
    assert store.counters["update.passes_full"] == full + 2
    _assert_fresh(store, lv, red)
