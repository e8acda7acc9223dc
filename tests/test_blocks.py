"""Block-view (page) geometry and bitcast roundtrips."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import blocks as B

DTYPES = ["float32", "bfloat16", "int32", "float16", "int8"]


@settings(max_examples=40, deadline=None)
@given(
    st.sampled_from(DTYPES),
    st.lists(st.integers(1, 40), min_size=1, max_size=3),
    st.sampled_from([128, 256, 512]),
    st.sampled_from([2, 4, 5]),
)
def test_lanes_roundtrip(dtype, shape, lpb, sw):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    x = (x * 100).astype(jnp.dtype(dtype))
    meta = B.make_meta(x, lanes_per_block=lpb, stripe_data_blocks=sw)
    lanes = B.to_lanes(x, meta)
    assert lanes.shape == (meta.n_blocks, meta.lanes_per_block)
    assert lanes.dtype == jnp.uint32
    back = B.from_lanes(lanes, meta)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    assert meta.n_stripes == -(-meta.n_blocks // sw)


def test_row_block_mask_basic():
    x = jnp.zeros((10, 70), jnp.float32)  # 70 lanes per row
    meta = B.make_meta(x, lanes_per_block=128)
    # row 0 covers lanes [0,70) -> block 0; row 3 lanes [210,280) -> blocks 1,2
    m = B.row_block_mask(meta, jnp.array([0]))
    assert bool(m[0]) and int(m.sum()) == 1
    m = B.row_block_mask(meta, jnp.array([3]))
    got = np.nonzero(np.asarray(m))[0].tolist()
    assert got == [1, 2]
    # negative ids ignored
    m = B.row_block_mask(meta, jnp.array([-1]))
    assert int(m.sum()) == 0


def test_row_block_mask_multidim():
    x = jnp.zeros((4, 8, 32), jnp.float32)  # rows over first 2 dims
    meta = B.make_meta(x, lanes_per_block=128)
    # flattened row (1, 2) = row 10 -> lanes [320, 352) -> block 2
    m = B.row_block_mask(meta, jnp.array([10]), row_dims=2)
    got = np.nonzero(np.asarray(m))[0].tolist()
    assert got == [2]
