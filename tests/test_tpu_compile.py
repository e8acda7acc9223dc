"""Compile the main path for a described TPU v5e (no chip needed).

The Pallas kernels must lower to Mosaic (``tpu_custom_call``), not merely
run in interpret mode, and the store's update program must fit one chip's
HBM at the region size the chip smoke run uses.  The topology is described
inside a fixture, never at import: only one process at a time may load the
TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ProtectedStore, RedundancyPolicy
from repro.core.store import _queued_variant
from repro.kernels.checksum import ops as cops
from repro.kernels.parity import ops as pops
from repro.kernels.redundancy import ops as rops

HBM_BYTES = 16 * 10**9          # one v5e chip
LANES = 1024                    # 4 KiB blocks
KERNEL_BLOCKS = 16384           # a 64 MiB leaf
REGION_ROWS = 512 * 1024        # the 2 GiB chip-smoke region


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back without one, so
    # keep these compiles out of any persistent cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _struct(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["checksum", "parity", "fused_update"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    nb, ns = KERNEL_BLOCKS, KERNEL_BLOCKS // 4
    lanes = _struct(one_chip, (nb, LANES))
    if kernel == "checksum":
        fn, args = cops.block_checksums, (lanes,)
    elif kernel == "parity":
        fn, args = (lambda x: pops.stripe_parity(x, 4)), (lanes,)
    else:
        fn = lambda x, c, p, bd, sd: rops.fused_update(x, c, p, bd, sd, 4)
        args = (lanes, _struct(one_chip, (nb,)),
                _struct(one_chip, (ns, LANES)),
                _struct(one_chip, (nb,), jnp.bool_),
                _struct(one_chip, (ns,), jnp.bool_))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_region_queued_update_fits_one_chip(one_chip):
    heap = jax.ShapeDtypeStruct((REGION_ROWS, LANES), jnp.float32)
    store = ProtectedStore(RedundancyPolicy.single(
        "vilamb", lanes_per_block=LANES, stripe_data_blocks=4,
        precompile=False)).attach({"heap": heap})
    (group,) = store._protected()
    put = lambda t: jax.tree.map(
        lambda s: _struct(one_chip, s.shape, s.dtype), t)
    top = _queued_variant(group.engine.queue_ladder[-1])
    compiled = store._build_update_many((group.label,), (top,)).lower(
        (put({"heap": heap}),), (put(store.red_structs()),)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used


def test_overlap_program_compiles_for_every_rung(one_chip):
    """Every rung of the 2 GiB region's queue ladder compiles into the
    tick's batched overlap program and fits one chip, and the bottom rung
    moves under a quarter of the top rung's bytes: padding rows cost a
    gather, a reduce and a scatter each, so a pass pays for its K.  The
    heap holds uint32 words, as a key-value region's pages do."""
    heap = jax.ShapeDtypeStruct((REGION_ROWS, LANES), jnp.uint32)
    store = ProtectedStore(RedundancyPolicy.single(
        "vilamb", lanes_per_block=LANES, stripe_data_blocks=4,
        precompile=False)).attach({"heap": heap})
    (group,) = store._protected()
    ladder = group.engine.queue_ladder
    assert ladder == (256, 1024, 4096, 16384), ladder
    put = lambda t: jax.tree.map(
        lambda s: _struct(one_chip, s.shape, s.dtype), t)
    moved = {}
    for k in ladder:
        compiled = store._build_update_many(
            (group.label,), (_queued_variant(k),)).lower(
            (put({"heap": heap}),), (put(store.red_structs()),)).compile()
        mem = compiled.memory_analysis()
        used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert used < HBM_BYTES, (k, used)
        cost = compiled.cost_analysis()
        moved[k] = (cost[0] if isinstance(cost, list) else cost)[
            "bytes accessed"]
    assert moved[ladder[0]] < moved[ladder[-1]] / 4, moved
