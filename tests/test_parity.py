"""Stripe parity: reconstruction inverts corruption; diffs compose."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import parity as P


def _lanes(seed, nb=11, L=64):
    return jax.random.randint(jax.random.PRNGKey(seed), (nb, L), 0, 2**31 - 1, jnp.uint32)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 500), st.sampled_from([2, 4, 5]), st.integers(0, 10))
def test_reconstruct_inverts_corruption(seed, sw, bad_block):
    lanes = _lanes(seed)
    par = P.stripe_parity(lanes, sw)
    sid = bad_block // sw
    corrupted = lanes.at[bad_block].set(lanes[bad_block] ^ jnp.uint32(0xBEEF))
    rebuilt = P.reconstruct_block(corrupted, par[sid], sw, bad_block, sid)
    np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(lanes[bad_block]))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 500), st.integers(0, 500), st.sampled_from([4, 5]))
def test_parity_diff_equals_recompute(s1, s2, sw):
    old, new = _lanes(s1), _lanes(s2)
    p_old = P.stripe_parity(old, sw)
    p_new = P.stripe_parity(new, sw)
    np.testing.assert_array_equal(
        np.asarray(p_old ^ P.parity_diff(old, new, sw)), np.asarray(p_new))


def test_masked_parity_keeps_clean_rows():
    lanes = _lanes(9)
    old = P.stripe_parity(lanes, 4) ^ jnp.uint32(123)  # stale everywhere
    sdirty = jnp.zeros((3,), bool).at[1].set(True)
    out = P.stripe_parity_masked(lanes, old, sdirty, 4)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(old[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(old[2]))
    np.testing.assert_array_equal(
        np.asarray(out[1]), np.asarray(P.stripe_parity(lanes, 4)[1]))
