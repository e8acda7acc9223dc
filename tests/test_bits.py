"""Packed dirty-bitvector properties (paper §3.2 metadata)."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import bits


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 200), st.data())
def test_pack_unpack_roundtrip(n, data):
    mask = np.array(data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
    words = bits.pack_mask(jnp.asarray(mask))
    back = np.asarray(bits.unpack(words, n))
    np.testing.assert_array_equal(back, mask)
    assert int(bits.popcount(words)) == int(mask.sum())


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 100), st.data())
def test_mark_is_or(n, data):
    m1 = np.array(data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
    m2 = np.array(data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
    w = bits.pack_mask(jnp.asarray(m1))
    w = bits.mark(w, jnp.asarray(m2))
    np.testing.assert_array_equal(np.asarray(bits.unpack(w, n)), m1 | m2)


def test_mark_ids_idempotent_and_ignores_negative():
    w = bits.zeros(70)
    ids = jnp.array([3, 3, 64, -1, -5, 69])
    w = bits.mark_ids(w, 70, ids)
    got = np.asarray(bits.unpack(w, 70))
    want = np.zeros(70, bool)
    want[[3, 64, 69]] = True
    np.testing.assert_array_equal(got, want)


def test_test_bit_and_any():
    w = bits.zeros(40)
    assert not bool(bits.any_set(w))
    w = bits.mark_ids(w, 40, jnp.array([33]))
    assert bool(bits.test_bit(w, 33))
    assert not bool(bits.test_bit(w, 32))
    assert bool(bits.any_set(w))
