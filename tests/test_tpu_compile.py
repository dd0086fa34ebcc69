"""Kernels of the main path compiled for a v5e chip that is described,
not attached: what interpret mode cannot show (a slice off the tiling,
too much VMEM, an op Mosaic has no rule for) fails here, at no chip
time. Nothing runs, so no result and no time comes out of this file.

The topology is described inside a fixture: only the worker that is
given this file loads the TPU's library, and it compiles in its own
process. Keep such tests in this one file."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from cxxnet_tpu.ops import pallas_lrn

_HYPER = (5, 0.001, 0.75, 1.0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 - any cause skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shape, dtype, sharding) -> str:
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)
    return jax.jit(fn).lower(x).compile().as_text()


# the benchmark's two maps (alexnet.train_resident) and AlexNet.conf's,
# a four-chip shard, GoogLeNet's, Server buckets of the 256-channel map
@pytest.mark.parametrize("shape,dtype", [
    ((2048, 96, 27, 27), "bfloat16"),
    ((2048, 256, 13, 13), "bfloat16"),
    ((256, 96, 27, 27), "float32"),
    ((512, 256, 13, 13), "float32"),
    ((128, 64, 56, 56), "bfloat16"),
    ((128, 192, 56, 56), "bfloat16"),
    ((1, 256, 13, 13), "bfloat16"),
    ((8, 256, 13, 13), "float32"),
    ((128, 1024, 7, 7), "float32"),         # the most channels taken
])
def test_lrn_kernels_compile_for_the_chip(one_chip, shape, dtype):
    assert pallas_lrn._tile_ok(jax.ShapeDtypeStruct(shape, dtype))
    fwd = _compile(lambda x: pallas_lrn.lrn_pallas(x, *_HYPER, False),
                   shape, dtype, one_chip)
    assert fwd.count("tpu_custom_call") == 1 and "lrn_fwd" in fwd
    grad = _compile(jax.grad(lambda x: jnp.sum(pallas_lrn.lrn_pallas(
        x, *_HYPER, False).astype(jnp.float32))), shape, dtype, one_chip)
    assert "lrn_bwd" in grad


def test_lrn_kernel_with_power_compiles_for_the_chip(one_chip):
    """`knorm = 0` keeps `jnp.power` in both kernels."""
    grad = _compile(jax.grad(lambda x: jnp.sum(pallas_lrn.lrn_pallas(
        x, 5, 1.0, 0.75, 0.0, False).astype(jnp.float32))),
        (256, 96, 27, 27), "bfloat16", one_chip)
    assert "lrn_bwd" in grad
