"""RedundancyEngine: Algorithm-1 invariants, scrub, recovery, sync mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ALL, RedundancyConfig, RedundancyEngine
from repro.core import bits, blocks as B

CFG = RedundancyConfig(lanes_per_block=128, stripe_data_blocks=4)


def _mk(seed=0, use_kernels=False):
    leaves = {
        "w": jax.random.normal(jax.random.PRNGKey(seed), (24, 200), jnp.float32),
        "e": jax.random.normal(jax.random.PRNGKey(seed + 1), (16, 64), jnp.bfloat16),
    }
    cfg = dataclasses.replace(CFG, use_kernels=use_kernels,
                              kernel_interpret=use_kernels)
    eng = RedundancyEngine(
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in leaves.items()}, cfg)
    return eng, leaves


@pytest.mark.parametrize("use_kernels", [False, True])
def test_algorithm1_invariant(use_kernels):
    """After redundancy_step, every clean block verifies and bitvectors are
    empty (paper Alg. 1 postcondition)."""
    eng, leaves = _mk(use_kernels=use_kernels)
    red = eng.init(leaves)
    assert all(int(v.sum()) == 0 for v in eng.scrub(leaves, red).values())
    leaves2 = dict(leaves, w=leaves["w"].at[5, 7].add(1.0))
    red = eng.mark_dirty(red, {"w": ALL})
    # dirty blocks are never flagged by scrub (no spurious alarms)
    assert all(int(v.sum()) == 0 for v in eng.scrub(leaves2, red).values())
    red = eng.redundancy_step(leaves2, red)
    assert all(int(v.sum()) == 0 for v in eng.scrub(leaves2, red).values())
    for r in red.values():
        assert int(bits.popcount(r.dirty)) == 0
        assert int(bits.popcount(r.shadow)) == 0
    assert all(bool(v) for v in eng.verify_meta(red).values())


def test_sparse_row_marking_limits_dirty_blocks():
    eng, leaves = _mk()
    red = eng.init(leaves)
    ev = jnp.zeros((16,), bool).at[3].set(True)  # one row of e
    red = eng.mark_dirty(red, {"e": ev})
    stats = eng.dirty_stats(red)
    assert int(stats["e"]["dirty_blocks"]) == 1
    assert int(stats["w"]["dirty_blocks"]) == 0


def test_sync_equals_async_checksums():
    """Pangolin-mode diffs land on the same redundancy as Algorithm 1."""
    eng, leaves = _mk()
    red0 = eng.init(leaves)
    leaves2 = {k: v + 1 for k, v in leaves.items()}
    red_sync = eng.sync_update(leaves, leaves2, red0)
    red_async = eng.redundancy_step(leaves2, eng.mark_dirty(red0, {"w": ALL, "e": ALL}))
    for k in leaves:
        np.testing.assert_array_equal(np.asarray(red_sync[k].checksums),
                                      np.asarray(red_async[k].checksums))
        np.testing.assert_array_equal(np.asarray(red_sync[k].parity),
                                      np.asarray(red_async[k].parity))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 23), st.integers(0, 40))
def test_detect_and_recover_property(bad_block, lane):
    eng, leaves = _mk(seed=3)
    red = eng.init(leaves)
    meta = eng.metas["w"]
    lanes = B.to_lanes(leaves["w"], meta)
    lane = lane % meta.lanes_per_block
    corrupted = B.from_lanes(lanes.at[bad_block, lane].add(7777), meta)
    mm = eng.scrub(dict(leaves, w=corrupted), red)
    flagged = np.nonzero(np.asarray(mm["w"]))[0]
    assert flagged.tolist() == [bad_block]
    fixed, ok = eng.recover_block(corrupted, red["w"], "w", bad_block)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(leaves["w"]))


def test_vulnerable_stripe_not_recoverable():
    eng, leaves = _mk(seed=4)
    red = eng.init(leaves)
    # dirty a sibling block in the same stripe -> vulnerable (paper §3.3)
    sibling = jnp.zeros((24 * 200,))  # mark via row mask on row covering block 1
    red = eng.mark_dirty(red, {"w": jnp.zeros((24,), bool).at[2].set(True)})
    meta = eng.metas["w"]
    lanes = B.to_lanes(leaves["w"], meta)
    corrupted = B.from_lanes(lanes.at[0, 0].add(1), meta)
    _, ok = eng.recover_block(corrupted, red["w"], "w", 0)
    assert not bool(ok)


def test_mttdl_stats_monotone_in_dirty_fraction():
    eng, leaves = _mk(seed=5)
    red = eng.init(leaves)
    s0 = eng.dirty_stats(red)
    red1 = eng.mark_dirty(red, {"w": jnp.zeros((24,), bool).at[0].set(True)})
    s1 = eng.dirty_stats(red1)
    red2 = eng.mark_dirty(red1, {"w": ALL})
    s2 = eng.dirty_stats(red2)
    assert (int(s0["w"]["vulnerable_stripes"]) <= int(s1["w"]["vulnerable_stripes"])
            <= int(s2["w"]["vulnerable_stripes"]))
