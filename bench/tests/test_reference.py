"""The plain redundancy and record references agree with the library's
checksum and parity at small sizes, under numpy and jax.numpy alike."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import records, redundancy as R


def library_redundancy(leaf, lanes, stripe):
    from repro.core import ProtectedStore, RedundancyPolicy
    store = ProtectedStore(RedundancyPolicy.single(
        "vilamb", lanes_per_block=lanes, stripe_data_blocks=stripe,
        precompile=False)).attach({"x": leaf})
    r = store.init({"x": leaf})["x"]
    return np.asarray(r.checksums), np.asarray(r.parity), int(r.meta_ck)


CASES = [((64, 1024), jnp.float32, 1024, 4),
         ((37, 100), jnp.float32, 1024, 4),
         ((3, 1000), jnp.bfloat16, 512, 4),
         ((5, 7, 33), jnp.bfloat16, 128, 3),
         ((9, 300), jnp.uint32, 256, 4)]


@pytest.mark.parametrize("shape,dtype,lanes,stripe", CASES)
def test_numpy_reference_equals_library(shape, dtype, lanes, stripe):
    key = jax.random.PRNGKey(len(shape) * 7 + lanes)
    if dtype == jnp.uint32:
        leaf = jax.random.bits(key, shape, jnp.uint32)
    else:
        leaf = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    ck, par, meta = library_redundancy(leaf, lanes, stripe)
    host = np.asarray(leaf)
    rck, rpar, rmeta = R.leaf_redundancy(
        np, R.as_lanes(np, host), shape, host.dtype.itemsize, lanes, stripe)
    np.testing.assert_array_equal(rck, ck)
    np.testing.assert_array_equal(rpar, par)
    assert int(rmeta) == meta


@pytest.mark.parametrize("shape,dtype,lanes,stripe", CASES)
def test_jax_reference_equals_numpy_reference(shape, dtype, lanes, stripe):
    leaf = jax.random.normal(jax.random.PRNGKey(3), shape,
                             jnp.float32).astype(dtype)
    host = np.asarray(leaf)
    size = host.dtype.itemsize
    want = R.leaf_redundancy(np, R.as_lanes(np, host), shape, size, lanes,
                             stripe)
    got = jax.jit(lambda x: R.leaf_redundancy(
        jnp, R.as_lanes(jnp, x), shape, size, lanes, stripe))(leaf)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_checksum_salt_starts_at_first_block():
    blocks = np.random.default_rng(0).integers(
        0, 2 ** 32, size=(8, 128), dtype=np.uint64).astype(np.uint32)
    whole = R.block_checksums(np, blocks)
    np.testing.assert_array_equal(R.block_checksums(np, blocks[4:], 4),
                                  whole[4:])


def test_records_same_under_numpy_and_jax():
    seed = 2 ** 40 + 17
    ver = np.array([[records.INITIAL] * 10, [0] * 10, list(range(5, 15)),
                    [2 ** 31] * 5 + [7] * 5], np.uint32)
    keys = np.array([0, 1, 99, 524287], np.uint32)
    want = records.record_words(np, seed, ver, keys, 25)
    got = records.record_words(jnp, seed, jnp.asarray(ver), jnp.asarray(keys),
                               25)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert want.shape == (4, 250)
    assert len({row.tobytes() for row in want}) == 4
    other = records.record_words(np, seed + (1 << 32), ver, keys, 25)
    assert not np.array_equal(other, want)


def test_pages_hold_records_and_writes_change_one_field():
    seed, fw = 2 ** 33 + 9, 25
    rng = np.random.default_rng(4)
    ver = rng.integers(0, 1000, size=(3, 4, 10)).astype(np.uint32)
    pages = np.array([0, 7, 1000], np.uint32)
    got = records.page_words(np, seed, ver, pages, 1024, fw)
    np.testing.assert_array_equal(
        np.asarray(records.page_words(jnp, seed, jnp.asarray(ver),
                                      jnp.asarray(pages), 1024, fw)), got)
    keys = (pages[:, None] * 4 + np.arange(4)).reshape(-1)
    rec = records.record_words(np, seed, ver.reshape(12, 10), keys, fw)
    np.testing.assert_array_equal(got[:, :1000].reshape(12, 250), rec)
    assert not got[:, 1000:].any()
    # Writing field 3 of record 5 at version 77 changes its 25 words only.
    ver2 = ver.reshape(12, 10).copy()
    ver2[5, 3] = 77
    rec2 = records.record_words(np, seed, ver2, keys, fw)
    changed = np.nonzero((rec2 != rec).any(axis=0))[0]
    assert set(np.nonzero((rec2 != rec).any(axis=1))[0]) == {5}
    np.testing.assert_array_equal(changed, np.arange(75, 100))
    np.testing.assert_array_equal(
        records.field_values(np, seed, 77, keys[5:6], np.array([3]), fw),
        rec2[5:6, 75:100])
