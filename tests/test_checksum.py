"""Checksum properties — the CRC-32C replacement must detect what the paper
needs detected (single-lane corruption, lane/block swaps) and support
Pangolin-style incremental diffs."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import checksum as C


def _lanes(seed, nb=6, L=64):
    return jax.random.randint(jax.random.PRNGKey(seed), (nb, L), 0, 2**31 - 1, jnp.uint32)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 1000), st.integers(0, 5), st.integers(0, 63), st.integers(1, 2**32 - 1))
def test_single_lane_change_detected(seed, b, l, delta):
    lanes = _lanes(seed)
    c0 = C.block_checksums(lanes)
    lanes2 = lanes.at[b, l].set(lanes[b, l] ^ jnp.uint32(delta))
    c1 = C.block_checksums(lanes2)
    assert c0[b] != c1[b]
    mask = np.ones(6, bool); mask[b] = False
    np.testing.assert_array_equal(np.asarray(c0)[mask], np.asarray(c1)[mask])


def test_lane_swap_detected():
    lanes = _lanes(1)
    a, b = int(lanes[2, 3]), int(lanes[2, 40])
    if a == b:
        return
    swapped = lanes.at[2, 3].set(b).at[2, 40].set(a)
    assert C.block_checksums(lanes)[2] != C.block_checksums(swapped)[2]


def test_block_position_salting():
    """Identical content in different block slots yields different checksums
    (misdirected-write detection, paper §2.2)."""
    row = jax.random.randint(jax.random.PRNGKey(3), (1, 64), 0, 2**31 - 1, jnp.uint32)
    lanes = jnp.concatenate([row, row], axis=0)
    c = C.block_checksums(lanes)
    assert c[0] != c[1]


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 1000), st.integers(0, 1000))
def test_incremental_diff_equals_recompute(seed1, seed2):
    old = _lanes(seed1)
    new = _lanes(seed2)
    c_old = C.block_checksums(old)
    c_new = C.block_checksums(new)
    delta = C.checksum_diff(old, new)
    np.testing.assert_array_equal(np.asarray(c_old ^ delta), np.asarray(c_new))


def test_meta_checksum_detects_checksum_corruption():
    c = C.block_checksums(_lanes(7))
    m0 = C.meta_checksum(c)
    c2 = c.at[1].set(c[1] ^ jnp.uint32(1))
    assert m0 != C.meta_checksum(c2)
