"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.checksum import ops as cops
from repro.kernels.checksum import ref as cref
from repro.kernels.parity import ops as pops
from repro.kernels.parity import ref as pref
from repro.kernels.redundancy import ops as rops
from repro.kernels.redundancy import ref as rref


def _lanes(seed, nb, L):
    return jax.random.randint(jax.random.PRNGKey(seed), (nb, L), 0, 2**31 - 1, jnp.uint32)


@pytest.mark.parametrize("nb,L", [(1, 128), (3, 128), (13, 512), (8, 1024), (5, 4096 * 2)])
def test_checksum_kernel_shapes(nb, L):
    lanes = _lanes(0, nb, L)
    k = cops.block_checksums(lanes, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(cref.block_checksums(lanes)))


@pytest.mark.parametrize("nb,L,sw", [(1, 128, 4), (9, 256, 2), (13, 512, 4),
                                     (10, 128, 5), (16, 8192, 4)])
def test_parity_kernel_shapes(nb, L, sw):
    lanes = _lanes(1, nb, L)
    k = pops.stripe_parity(lanes, stripe_width=sw, interpret=True)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(pref.stripe_parity(lanes, sw)))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 100), st.integers(1, 14), st.sampled_from([128, 256]),
       st.sampled_from([2, 4]), st.data())
def test_fused_kernel_property(seed, nb, L, sw, data):
    lanes = _lanes(seed, nb, L)
    bd = np.array(data.draw(st.lists(st.booleans(), min_size=nb, max_size=nb)))
    ns = -(-nb // sw)
    pad = np.zeros(ns * sw, bool)
    pad[:nb] = bd
    sd = pad.reshape(ns, sw).any(axis=1)
    old_cks = cref.block_checksums(lanes) ^ jnp.uint32(99)
    old_par = pref.stripe_parity(lanes, sw) ^ jnp.uint32(7)
    ck_k, pr_k = rops.fused_update(lanes, old_cks, old_par, jnp.asarray(bd),
                                   jnp.asarray(sd), sw, use_pallas=True, interpret=True)
    ck_r, pr_r = rref.fused_update(lanes, old_cks, old_par, jnp.asarray(bd),
                                   jnp.asarray(sd), sw)
    np.testing.assert_array_equal(np.asarray(ck_k), np.asarray(ck_r))
    np.testing.assert_array_equal(np.asarray(pr_k), np.asarray(pr_r))


# Adversarial lane payloads: float32 NaN/Inf patterns, zeros (XOR
# absorbing) and saturated words — kernels treat lanes as raw bits, so
# these must match the oracles exactly, not merely numerically.
SPECIALS = np.array([0x7FC00000, 0x7F800000, 0xFF800000, 0x7F800001,
                     0x00000000, 0xFFFFFFFF], dtype=np.uint32)


def _special_lanes(nb, L, offset=0):
    return jnp.asarray(
        SPECIALS[(np.arange(nb * L) + offset) % len(SPECIALS)]
        .reshape(nb, L))


@pytest.mark.parametrize("nb,L", [(1, 128), (5, 256), (13, 512)])
def test_checksum_kernel_special_values(nb, L):
    lanes = _special_lanes(nb, L)
    k = cops.block_checksums(lanes, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(k), np.asarray(cref.block_checksums(lanes)))
    # identical NaN-pattern blocks must still checksum differently
    # (position salting defeats block-swap aliasing)
    if nb > 1:
        assert len(set(np.asarray(k).tolist())) == nb


@pytest.mark.parametrize("nb,L,sw", [(4, 128, 4), (10, 256, 5)])
def test_parity_kernel_special_values(nb, L, sw):
    lanes = _special_lanes(nb, L, offset=1)
    k = pops.stripe_parity(lanes, stripe_width=sw, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(k), np.asarray(pref.stripe_parity(lanes, sw)))


def test_fused_kernel_special_values_and_zero_dirty():
    """NaN/Inf slabs through the fused kernel: dirty blocks refresh to the
    oracle's bits, a zero-dirty call is a bitwise no-op."""
    lanes = _special_lanes(12, 256, offset=2)
    old_cks = cref.block_checksums(lanes) ^ jnp.uint32(0xDEAD)
    old_par = pref.stripe_parity(lanes, 4) ^ jnp.uint32(0xBEEF)
    bd = jnp.zeros(12, bool).at[jnp.array([0, 5, 11])].set(True)
    sd = jnp.zeros(3, bool).at[jnp.array([0, 1, 2])].set(True)
    ck_k, pr_k = rops.fused_update(lanes, old_cks, old_par, bd, sd, 4,
                                   use_pallas=True, interpret=True)
    ck_r, pr_r = rref.fused_update(lanes, old_cks, old_par, bd, sd, 4)
    np.testing.assert_array_equal(np.asarray(ck_k), np.asarray(ck_r))
    np.testing.assert_array_equal(np.asarray(pr_k), np.asarray(pr_r))
    zd = jnp.zeros(12, bool)
    ck0, pr0 = rops.fused_update(lanes, old_cks, old_par, zd,
                                 jnp.zeros(3, bool), 4,
                                 use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ck0), np.asarray(old_cks))
    np.testing.assert_array_equal(np.asarray(pr0), np.asarray(old_par))


def test_fused_kernel_work_queue_semantics():
    """Clean stripes' outputs must be byte-identical to old values even when
    the kernel never visits them (the work-queue skip, DESIGN.md kernels)."""
    lanes = _lanes(5, 12, 256)
    old_cks = jnp.arange(12, dtype=jnp.uint32) * 7
    old_par = jnp.full((3, 256), 0xABC, jnp.uint32)
    bd = jnp.zeros(12, bool)  # nothing dirty
    sd = jnp.zeros(3, bool)
    cks, par = rops.fused_update(lanes, old_cks, old_par, bd, sd, 4,
                                 use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(cks), np.asarray(old_cks))
    np.testing.assert_array_equal(np.asarray(par), np.asarray(old_par))
