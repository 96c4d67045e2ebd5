"""The names in ``kernels.json`` are the names the TPU compiler gives the
kernels' operations, which a v5e trace shows: each group's kernels are
compiled for a described v5e (no chip needed) and every custom call they
hold must carry one of the group's names.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library."""
import re

import pytest

import bench

M, W = 1024, 256                     # packed rows x 64-bit-word lanes


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _programs(one_chip):
    import jax
    import jax.numpy as jnp
    bench.add_program_path()
    from repro.kernels import parity, secded, segsum
    words = jax.ShapeDtypeStruct((M, W), jnp.uint32, sharding=one_chip)
    par = jax.ShapeDtypeStruct((M, W // 8), jnp.uint32, sharding=one_chip)
    te = segsum.EDGE_TILE

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def ecc(lo, hi, e, p):
        return (secded.secded_encode_words(lo, hi, interpret=False),
                secded.secded_scrub_words(lo, hi, e, interpret=False),
                parity.parity_encode_words(lo, hi, interpret=False),
                parity.parity_check_words(lo, hi, p, interpret=False))

    def push(src, dst, sb, db, x):
        return segsum.edge_segment_push_blocked(src, dst, sb, db, x,
                                                node_block=8192,
                                                interpret=False)
    x = jax.ShapeDtypeStruct((1, 1 << 16), jnp.float32, sharding=one_chip)
    return {"ecc": (ecc, (words, words, words, par)),
            "push": (push, (i32(64 * te), i32(64 * te), i32(64), i32(64),
                            x))}


@pytest.mark.parametrize("group", ["ecc", "push"])
def test_kernel_names_are_the_compilers(one_chip, group):
    import jax
    fn, shapes = _programs(one_chip)[group]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    calls = [ln.split(" = ")[0].strip().lstrip("%")
             for ln in text.splitlines() if "custom-call(" in ln
             and 'custom_call_target="tpu_custom_call"' in ln]
    names = bench.kernel_groups()[group]
    assert calls and all(any(c.startswith(n) for n in names)
                         for c in calls), (calls, names)
    # and every name of the group is found
    assert all(any(re.match(re.escape(n) + r"(\.\d+)?$", c) for c in calls)
               for n in names), (calls, names)
