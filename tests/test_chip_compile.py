"""The main path's device programs, compiled for a TPU v5e at real widths.

Nothing runs: the chip's compiler, installed here, compiles for a
described ``v5e:2x2`` topology with no chip attached, so an alignment,
memory or lowering refusal surfaces in the test suite instead of on the
chip. These are the programs the chip runs: the front-end rows program
for the reference recipe's 512x512 RGB tiles at 6 levels, the fused
jnp CX/D+MQ Tier-1 program at its smallest block bucket, and the decode
inverse. (The Pallas Tier-1 kernels are not here: the chip's compiler
refuses them, ``cxd.MOSAIC_REFUSAL``.)

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bucketeer_tpu.codec import cxd, frontend
from bucketeer_tpu.codec.decode import device as ddevice
from bucketeer_tpu.codec.pipeline import make_plan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_frontend_rows_512_rgb_l6_compiles_for_v5e(one_chip):
    plan = make_plan(512, 512, 3, 6, False, 8)
    fn, _ = frontend.frontend_program(plan, frontend.layout_for(plan).P)
    compiled = _compile(fn, [((8, 512, 512, 3), jnp.int32)], one_chip)
    assert compiled.memory_analysis().argument_size_in_bytes == \
        8 * 512 * 512 * 3 * 4


def test_fused_tier1_smallest_bucket_compiles_for_v5e(one_chip):
    L, n = cxd.LAUNCH_PLANE_BUCKETS[0], cxd.GROUP_BATCH_FLOOR
    fn, _ = cxd.fused_program(L, pallas=False)
    compiled = _compile(fn, [((n, 64, 64), jnp.int32)]
                        + [((n,), jnp.int32)] * 5 + [((), jnp.int32)],
                        one_chip)
    assert "tpu_custom_call" not in compiled.as_text()


def test_decode_inverse_512_rgb_compiles_for_v5e(one_chip):
    plan = ddevice.make_inverse_plan(512, 512, 3, 6, False, 8, True,
                                     lambda level, name: 0.5)
    fn, _ = ddevice.inverse_program(plan)
    compiled = _compile(fn, [((1, 3, 512, 512), jnp.int32)], one_chip)
    assert compiled.memory_analysis() is not None
