"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e
that is described, not attached: at real widths (1024 x 256 words; the
graph push at the chip smoke's 2^20 nodes and node block 8192, at the
benchmark cell's 647,168 nodes and at node block 128), with
``interpret=False``. Mosaic refuses here what it would refuse on the chip
(unsupported reductions and reshapes, misaligned blocks, SMEM and VMEM
overflows), at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitflip, burst, dected, parity, secded, segsum

M, W = 1024, 256                 # packed rows x 64-bit-word lanes
NODES = 1 << 20
NODE_BLOCK = 8192
TILES = 20_000                   # ~ the smoke graph's edge tiles
CELL_NODES, CELL_TILES = 647_168, 64_512
TE = segsum.EDGE_TILE
OFF = {"interpret": False}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _words(sharding, cols=W):
    return jax.ShapeDtypeStruct((M, cols), jnp.uint32, sharding=sharding)


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


CASES = {
    "parity_encode": (lambda lo, hi: parity.parity_encode_words(
        lo, hi, **OFF), lambda s: (_words(s), _words(s))),
    "parity_check": (lambda lo, hi, p: parity.parity_check_words(
        lo, hi, p, **OFF),
        lambda s: (_words(s), _words(s), _words(s, W // 8))),
    "secded_encode": (lambda lo, hi: secded.secded_encode_words(
        lo, hi, **OFF), lambda s: (_words(s), _words(s))),
    "secded_scrub": (lambda lo, hi, e: secded.secded_scrub_words(
        lo, hi, e, **OFF), lambda s: (_words(s),) * 3),
    "dected_scrub": (lambda lo, hi, e: dected.dected_scrub_words(
        lo, hi, e, **OFF), lambda s: (_words(s),) * 3),
    "burst_scrub": (lambda lo, hi, e: burst.burst_scrub_words(
        lo, hi, e, **OFF), lambda s: (_words(s),) * 3),
    "bitflip": (lambda lo, hi, w, b: bitflip.bitflip_words(
        lo, hi, w, b, **OFF),
        lambda s: (_words(s), _words(s), _i32(s, 8), _i32(s, 8))),
    "segsum_push": (lambda src, dst, x: segsum.edge_segment_push(
        src, dst, x, **OFF),
        lambda s: (_i32(s, 64 * TE), _i32(s, 64 * TE), _f32(s, 1, 1024))),
    "segsum_push_blocked": (
        lambda src, dst, sb, db, x: segsum.edge_segment_push_blocked(
            src, dst, sb, db, x, node_block=NODE_BLOCK, **OFF),
        lambda s: (_i32(s, TILES * TE), _i32(s, TILES * TE),
                   _i32(s, TILES), _i32(s, TILES), _f32(s, 1, NODES))),
    # the pagerank20 benchmark cell's own shapes: graph500 scale 20 laid
    # out in 8192-node blocks, 64,512 edge tiles of 512
    "segsum_push_blocked_cell": (
        lambda src, dst, sb, db, x: segsum.edge_segment_push_blocked(
            src, dst, sb, db, x, node_block=NODE_BLOCK, **OFF),
        lambda s: (_i32(s, CELL_TILES * TE), _i32(s, CELL_TILES * TE),
                   _i32(s, CELL_TILES), _i32(s, CELL_TILES),
                   _f32(s, 1, CELL_NODES))),
    # node block 128: each block is a single row of 128 lanes
    "segsum_push_blocked_bn128": (
        lambda src, dst, sb, db, x: segsum.edge_segment_push_blocked(
            src, dst, sb, db, x, node_block=128, **OFF),
        lambda s: (_i32(s, 64 * TE), _i32(s, 64 * TE), _i32(s, 64),
                   _i32(s, 64), _f32(s, 1, 1 << 16))),
    "frontier_update": (
        lambda p, v, d, lvl: segsum.frontier_update(p, v, d, lvl, **OFF),
        lambda s: (_f32(s, 1, NODES), _i32(s, 1, NODES), _i32(s, 1, NODES),
                   _i32(s))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    compiled = jax.jit(fn).lower(*shapes(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
