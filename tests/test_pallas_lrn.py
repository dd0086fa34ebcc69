"""Differential tests: pallas LRN kernel (interpret mode) vs the XLA
reduce_window implementation - the pairtest discipline (SURVEY.md par.4.1)
applied to the hand-written TPU kernel."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.ops.nn import lrn
from cxxnet_tpu.ops.pallas_lrn import lrn_pallas, use_pallas_lrn


# cut small: AlexNet's and GoogLeNet's channel counts over spatial sizes
# that are no multiple of the lane width, batches on either side of the
# 128 that decides whether batch or positions sit on lanes (300 images
# are no multiple of it), every window size at one shape
_SHAPES = [
    ((2, 16, 7, 9), 5),
    ((2, 8, 5, 5), 3),
    ((1, 32, 3, 3), 7),
    ((3, 8, 1, 1), 1),
    ((8, 96, 5, 5), 5),
    ((3, 256, 3, 3), 5),
    ((5, 64, 7, 7), 5),
    ((128, 96, 3, 3), 5),
    ((256, 16, 2, 3), 3),
    ((300, 16, 2, 2), 5),
    ((384, 8, 2, 2), 7),
    ((9, 128, 5, 5), 1),
    ((9, 128, 5, 5), 3),
    ((9, 128, 5, 5), 7),
]


@pytest.mark.parametrize("shape,n", _SHAPES)
def test_forward_matches_xla(shape, n):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    ref = lrn(x, n, 0.001, 0.75, 1.0)
    got = lrn_pallas(x, n, 0.001, 0.75, 1.0, True)
    np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape,n", [((2, 16, 7, 9), 5), ((2, 8, 5, 5), 3),
                                     ((8, 96, 5, 5), 5), ((3, 256, 3, 3), 5),
                                     ((5, 64, 7, 7), 3), ((128, 96, 3, 3), 5),
                                     ((300, 16, 2, 2), 7),
                                     ((9, 128, 5, 5), 1)])
def test_grad_matches_xla(shape, n):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g = jnp.asarray(rng.randn(*shape).astype(np.float32))
    gr = jax.grad(lambda x: jnp.sum(lrn(x, n, 0.001, 0.75, 1.0) * g))(x)
    gp = jax.grad(
        lambda x: jnp.sum(lrn_pallas(x, n, 0.001, 0.75, 1.0, True) * g))(x)
    np.testing.assert_allclose(gr, gp, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 96, 5, 5), (3, 256, 3, 3),
                                   (128, 32, 3, 3)])
def test_bf16_in_and_out_matches_the_float32_reference(shape):
    """bf16 operands, float32 inside: the result is the float32
    reference's, rounded once (chip_smoke.py's bf16 tolerance)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    g = jnp.asarray(rng.randn(*shape), jnp.float32)
    x32 = x.astype(jnp.float32)
    hyper = (5, 0.001, 0.75, 1.0)
    got = lrn_pallas(x, *hyper, True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), lrn(x32, *hyper),
                               rtol=8e-3, atol=8e-3)
    gp = jax.grad(lambda x: jnp.sum(
        lrn_pallas(x, *hyper, True).astype(jnp.float32) * g))(x)
    gr = jax.grad(lambda x: jnp.sum(lrn(x, *hyper) * g))(x32)
    assert gp.dtype == jnp.bfloat16
    np.testing.assert_allclose(gp.astype(jnp.float32), gr,
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("alpha,knorm", [(1.0, 0.0), (-0.001, 1.0)])
def test_constants_that_do_not_keep_norm_positive(monkeypatch, alpha,
                                                  knorm):
    """`knorm <= 0` or `alpha < 0`: exp(-beta log norm) has no answer for
    a norm of zero or below, so the kernel keeps `jnp.power` - and still
    matches."""
    from cxxnet_tpu.ops import pallas_lrn
    taken = []
    real = jnp.power
    monkeypatch.setattr(pallas_lrn.jnp, "power",
                        lambda *a: taken.append(1) or real(*a))
    monkeypatch.setattr(pallas_lrn.jnp, "log", lambda *a: 1 / 0)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(4, 16, 3, 5).astype(np.float32))
    g = jnp.asarray(rng.randn(4, 16, 3, 5).astype(np.float32))
    got = lrn_pallas(x, 5, alpha, 0.75, knorm, True)
    gp = jax.grad(lambda x: jnp.sum(
        lrn_pallas(x, 5, alpha, 0.75, knorm, True) * g))(x)
    assert len(taken) >= 3              # one forward, two backward
    monkeypatch.undo()
    np.testing.assert_allclose(lrn(x, 5, alpha, 0.75, knorm), got,
                               rtol=1e-5, atol=1e-6)
    gr = jax.grad(lambda x: jnp.sum(lrn(x, 5, alpha, 0.75, knorm) * g))(x)
    np.testing.assert_allclose(gr, gp, rtol=1e-4, atol=1e-5)


def test_positive_constants_take_exp_of_log(monkeypatch):
    from cxxnet_tpu.ops import pallas_lrn
    monkeypatch.setattr(pallas_lrn.jnp, "power", lambda *a: 1 / 0)
    x = jnp.ones((2, 8, 2, 2), jnp.float32)
    jax.grad(lambda x: jnp.sum(lrn_pallas(x, 5, 0.001, 0.75, 1.0, True)))(x)


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
    assert _tile_ok(x_bf.astype(jnp.float32))
    assert _tile_ok(jnp.zeros((1, 32, 4, 4), jnp.bfloat16))
    # more channels than a (c, 128) float32 chunk may hold
    assert _tile_ok(jnp.zeros((1, 1024, 2, 2), jnp.bfloat16))
    assert not _tile_ok(jnp.zeros((1, 2048, 2, 2), jnp.bfloat16))
    # the batch decides the order the kernel reads in, never whether it
    # runs: Server buckets and training batches take the same route
    for batch in (1, 8, 64, 200, 256, 2048):
        assert _tile_ok(jnp.zeros((batch, 96, 4, 4), jnp.bfloat16))


@pytest.mark.parametrize("shape,perm,block,chunk", [
    # a training batch goes on lanes, about a megabyte a block
    ((2048, 96, 27, 27), (2, 1, 0), (2, 96, 2048), 128),
    ((2048, 256, 13, 13), (2, 1, 0), (1, 256, 2048), 128),
    ((512, 96, 27, 27), (2, 1, 0), (10, 96, 512), 128),
    ((4096, 64, 7, 7), (2, 1, 0), (4, 64, 2048), 128),
    # many channels: fewer lanes a block, never under 128
    ((1024, 1024, 3, 3), (2, 1, 0), (1, 1024, 512), 128),
    # any other batch leaves the positions there: whole tiles of lanes
    # past the end where there are 128 or more, else the ragged whole
    ((8, 96, 27, 27), (0, 1, 2), (7, 96, 768), 128),
    ((200, 256, 13, 13), (0, 1, 2), (8, 256, 256), 128),
    ((1, 64, 56, 56), (0, 1, 2), (1, 64, 2048), 128),
    ((3, 256, 3, 3), (0, 1, 2), (3, 256, 9), 9),
])
def test_plan_follows_the_batch(shape, perm, block, chunk):
    from cxxnet_tpu.ops.pallas_lrn import _plan
    assert _plan(shape, jnp.bfloat16) == (perm, block, chunk)


@pytest.mark.parametrize("shape", [(5, 16, 3, 3), (128, 8, 1, 5),
                                   (384, 8, 2, 2), (3, 8, 13, 13)])
def test_blocks_that_do_not_divide_the_operand(monkeypatch, shape):
    """Two images or positions a block where there are five, 256 lanes
    a block where there are 384 images or 169 positions: the last block
    of either grid axis hangs over the end."""
    from cxxnet_tpu.ops import pallas_lrn
    b, c, h, w = shape
    lanes = b if b % 128 == 0 else h * w
    monkeypatch.setattr(pallas_lrn, "_MAX_LANE_TILE", 256)
    tile = lanes if lanes < 128 else min(-(-lanes // 128) * 128, 256)
    monkeypatch.setattr(pallas_lrn, "_BLOCK_BYTES", 2 * c * tile * 4)
    assert pallas_lrn._plan(shape, jnp.float32).block == (2, c, tile)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g = jnp.asarray(rng.randn(*shape).astype(np.float32))
    hyper = (5, 0.001, 0.75, 1.0)
    np.testing.assert_allclose(lrn(x, *hyper), lrn_pallas(x, *hyper, True),
                               rtol=1e-5, atol=1e-6)
    gr = jax.grad(lambda x: jnp.sum(lrn(x, *hyper) * g))(x)
    gp = jax.grad(lambda x: jnp.sum(lrn_pallas(x, *hyper, True) * g))(x)
    np.testing.assert_allclose(gr, gp, rtol=1e-4, atol=1e-5)


def test_rows_do_not_depend_on_the_batch_they_came_in():
    """A Server bucket's rows come out bit for bit as in a batch that
    goes on lanes: the two orders run the same arithmetic an element
    (task=serve == task=pred, chip_smoke.py)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(128, 16, 3, 5), jnp.bfloat16)
    whole = lrn_pallas(x, 5, 0.001, 0.75, 1.0, True)
    for rows in (1, 8):
        part = lrn_pallas(x[:rows], 5, 0.001, 0.75, 1.0, True)
        np.testing.assert_array_equal(np.asarray(whole[:rows], np.float32),
                                      np.asarray(part, np.float32))


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
    # the route is named in the compiled step's text, and only the one
    # taken
    hlo = tr.step_hlo(sb)
    routes = {"route.pallas", "route.sharded", "route.xla"}
    took = "route.sharded" if sharded else "route.pallas"
    assert {r for r in routes if f"/{r}/" in hlo} == {took}
    tr.update(sb)
    assert np.isfinite(np.asarray(tr.state["params"]["cv1"]["wmat"])).all()
