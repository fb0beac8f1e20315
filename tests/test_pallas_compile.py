"""The Pallas GROUP BY kernels compile with Mosaic for a described v5e at
the north-star cell's size (2^27 lanes), with no chip: what interpret mode
cannot show (tiling, VMEM, the Mosaic verifier's types).  The topology is
described inside a fixture, never at import (one process at a time may
load the TPU's library), and the persistent compile cache is off around
these compiles (nothing compiled for a described chip can be read back)."""

import os

import pytest

import jax
import jax.numpy as jnp

from baikaldb_tpu.ops import pallas_kernels as pk

LANES = 1 << 27


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", before)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel, ng, want_min, want_max", [
    ("agg", 1000, True, False),          # the north star's g1000 / g4000
    ("agg", 4000, True, False),
    ("agg", 4000, True, True),
    ("agg", 600, False, True),           # tiles padded 5 -> 8
    ("sum", 4000, False, False),
    ("hist", 4096, False, False)])
def test_kernel_compiles_for_the_chip(one_chip, kernel, ng, want_min,
                                      want_max):
    def arg(dtype):
        return jax.ShapeDtypeStruct((LANES,), dtype, sharding=one_chip)

    codes, vals, mask = arg(jnp.int32), arg(jnp.float32), arg(jnp.bool_)
    if kernel == "agg":
        def f(c, v, m):
            return pk.fused_group_aggregate(c, v, m, ng, want_min=want_min,
                                            want_max=want_max)
        args = (codes, vals, mask)
    elif kernel == "sum":
        def f(c, v, m):
            return pk.filtered_group_sum(c, v, m, ng)
        args = (codes, vals, mask)
    else:
        def f(c, m):
            return pk.partition_histogram(c, m, ng)
        args = (codes, mask)
    text = jax.jit(f).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
