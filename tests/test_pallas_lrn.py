"""Differential tests: pallas LRN kernel (interpret mode) vs the XLA
reduce_window implementation - the pairtest discipline (SURVEY.md par.4.1)
applied to the hand-written TPU kernel."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.ops.nn import lrn
from cxxnet_tpu.ops.pallas_lrn import lrn_pallas, use_pallas_lrn


@pytest.mark.parametrize("shape,n", [
    ((2, 16, 7, 9), 5),
    ((2, 8, 5, 5), 3),
    ((1, 32, 3, 3), 7),
    ((3, 8, 1, 1), 1),
])
def test_forward_matches_xla(shape, n):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    ref = lrn(x, n, 0.001, 0.75, 1.0)
    got = lrn_pallas(x, n, 0.001, 0.75, 1.0, True)
    np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape,n", [((2, 16, 7, 9), 5), ((2, 8, 5, 5), 3)])
def test_grad_matches_xla(shape, n):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g = jnp.asarray(rng.randn(*shape).astype(np.float32))
    gr = jax.grad(lambda x: jnp.sum(lrn(x, n, 0.001, 0.75, 1.0) * g))(x)
    gp = jax.grad(
        lambda x: jnp.sum(lrn_pallas(x, n, 0.001, 0.75, 1.0, True) * g))(x)
    np.testing.assert_allclose(gr, gp, rtol=1e-4, atol=1e-5)


def test_sharded_matches_xla_multi_device(monkeypatch):
    """shard_map route on the 8-device virtual mesh (interpret mode) ==
    XLA path, forward and grad - the multi-chip flagship scenario the
    kernel used to be hard-disabled in."""
    from cxxnet_tpu.ops import pallas_lrn
    from cxxnet_tpu.parallel.mesh import MeshSpec, build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert len(jax.devices()) == 8
    monkeypatch.setattr(pallas_lrn, "_FORCE_INTERPRET", True)
    mesh = build_mesh(MeshSpec(device_indices=list(range(8))), 16)
    rng = np.random.RandomState(2)
    x = jax.device_put(rng.randn(16, 16, 5, 7).astype(np.float32),
                       NamedSharding(mesh, P("data")))
    n, alpha, beta, knorm = 5, 0.001, 0.75, 1.0
    assert pallas_lrn.use_pallas_lrn_sharded(x, mesh)

    ref = lrn(x, n, alpha, beta, knorm)  # XLA (CPU backend -> not pallas)
    got = jax.jit(lambda x: pallas_lrn.lrn_pallas_sharded(
        x, mesh, n, alpha, beta, knorm))(x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-6)

    g = rng.randn(*x.shape).astype(np.float32)
    gr = jax.grad(lambda x: jnp.sum(lrn(x, n, alpha, beta, knorm) * g))(x)
    gp = jax.jit(jax.grad(lambda x: jnp.sum(
        pallas_lrn.lrn_pallas_sharded(x, mesh, n, alpha, beta, knorm)
        * g)))(x)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(gp),
                               rtol=1e-4, atol=1e-5)


def test_sharded_eligibility():
    from cxxnet_tpu.ops import pallas_lrn
    from cxxnet_tpu.parallel.mesh import MeshSpec, build_mesh
    mesh = build_mesh(MeshSpec(device_indices=list(range(8))), 16)
    x = jnp.zeros((16, 16, 5, 7), jnp.float32)
    # CPU backend without the interpret override -> ineligible
    assert not pallas_lrn.use_pallas_lrn_sharded(x, mesh)
    # batch not divisible by the data axis -> ineligible even forced
    try:
        pallas_lrn._FORCE_INTERPRET = True
        bad = jnp.zeros((12, 16, 5, 7), jnp.float32)
        assert not pallas_lrn.use_pallas_lrn_sharded(bad, mesh)
        assert pallas_lrn.use_pallas_lrn_sharded(x, mesh)
    finally:
        pallas_lrn._FORCE_INTERPRET = False


def test_eligibility_gate():
    # CPU backend in tests -> never eligible; odd channel counts never
    x32 = jnp.zeros((1, 96, 4, 4), jnp.float32)
    assert not use_pallas_lrn(x32) or jax.default_backend() == "tpu"
    x_odd = jnp.zeros((1, 7, 4, 4), jnp.float32)
    from cxxnet_tpu.ops.pallas_lrn import _tile_ok
    assert not _tile_ok(x_odd)
    x_bf = jnp.zeros((1, 24, 4, 4), jnp.bfloat16)
    assert not _tile_ok(x_bf)       # 24 % 16 != 0
    assert _tile_ok(jnp.zeros((1, 32, 4, 4), jnp.bfloat16))


_LRN_NET = """
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  pad = 1
  nchannel = 16
layer[1->2] = lrn
  local_size = 5
layer[2->3] = flatten
layer[3->4] = fullc:fc1
  nhidden = 4
layer[4->4] = softmax
netconfig=end
input_shape = 3,6,6
batch_size = 8
eta = 0.1
silent = 1
"""


@pytest.mark.parametrize("dev,sharded", [("cpu", False),
                                         ("cpu:0-3", True)])
def test_train_step_route_follows_mesh_size(monkeypatch, dev, sharded):
    """The traced train step takes the kernel route its MESH calls
    for while the host shows 8 devices: a one-device mesh gets the
    single-device kernel (it used to fall through to reduce_window
    because jax.device_count() != 1), a 4-device mesh the shard_map
    route - forward and backward kernels either way."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.ops import pallas_lrn
    assert jax.device_count() == 8
    monkeypatch.setattr(pallas_lrn, "_FORCE_INTERPRET", True)
    tr = NetTrainer(dev=dev, cfg=_LRN_NET)
    tr.init_model()
    assert tr.mesh.devices.size == (4 if sharded else 1)
    rng = np.random.RandomState(0)
    sb = tr.stage_batch(DataBatch(
        data=rng.randn(8, 3, 6, 6).astype(np.float32),
        label=rng.randint(0, 4, (8, 1)).astype(np.float32)))
    txt = str(tr._train_step.trace(
        tr.state, sb.data, sb.extras, sb.labels, sb.mask,
        jax.random.PRNGKey(0)).jaxpr)
    assert txt.count("name=lrn_fwd") == 1
    assert txt.count("name=lrn_bwd") == 1
    assert ("shard_map" in txt) == sharded
    tr.update(sb)
    assert np.isfinite(np.asarray(tr.state["params"]["cv1"]["wmat"])).all()
