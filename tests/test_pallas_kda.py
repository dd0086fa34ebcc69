"""The Pallas kernels for KDA's chunk-local part (ops/pallas_kda.py) in
interpret mode on the CPU, against the XLA route they replace on the
chip (`ops/kda.py local_xla`) and, end to end through `kda_chunked`,
against the recurrence a position at a time; which route `kda_chunked`
takes and what it imports on the way; and how many kernel bodies a
step's text holds."""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.ops import kda as ops_kda
from cxxnet_tpu.parallel.mesh import active_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D = 128         # the kernels take whole lane tiles of channels
PK = "cxxnet_tpu.ops.pallas_kda"


def _inputs(t, gscale, dtype, b=1, h=2, seed=0, d=D):
    """q, k, v, g, beta as `KDALayer.apply` hands them over: unit-norm
    q and k, g <= 0, beta in (0, 1)."""
    r = np.random.RandomState(seed)
    q = r.randn(b, t, h, d).astype(np.float32)
    k = r.randn(b, t, h, d).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(b, t, h, d).astype(np.float32)
    g = -gscale * np.abs(r.randn(b, t, h, d)).astype(np.float32)
    beta = (1 / (1 + np.exp(-r.randn(b, t, h)))).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g), jnp.asarray(beta))


def _units(t, chunk, gscale, dtype):
    """The chunk-local part's operands, a unit a row: q, k, g
    `(N, C, d)`, beta `(N, C, 1)`."""
    q, k, _, g, beta = _inputs(t, gscale, dtype)

    def units(a):
        b, _, h, d = a.shape
        a = jnp.moveaxis(a.reshape(b, t // chunk, chunk, h, d), 3, 1)
        return a.reshape(-1, chunk, d)

    return units(q), units(k), units(g), units(beta[..., None])


def _local_pallas(*args):
    from cxxnet_tpu.ops import pallas_kda
    return pallas_kda.local_pallas(*args, True)


def _close(got, want, tol):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    np.testing.assert_allclose(got, want, atol=tol * np.abs(want).max())


# float32 agrees to rounding; in bf16 both routes round the strips'
# operands and the gradients' last cast at slightly different points
_TOL = {"float32": 2e-5, "bfloat16": 1e-2}

_CASES = [
    (128, 64, 1.0, "float32"),      # the cell's chunk: four sub-blocks
    (64, 16, 1.0, "float32"),       # a chunk of one sub-block: no strips
    (64, 32, 0.01, "float32"),      # hardly any decay
    (64, 64, 5.0, "float32"),       # e^-320 a chunk
    (64, 64, 30.0, "float32"),      # e^-1920: exp(-G) would overflow
    (64, 64, 1.0, "bfloat16"),
    (32, 16, 0.1, "bfloat16"),
    (64, 64, 30.0, "bfloat16"),
]


@pytest.mark.parametrize("t,chunk,gscale,dtype", _CASES)
def test_kernel_forward_is_the_xla_route(t, chunk, gscale, dtype):
    """A (beta applied, zero from the diagonal up), P (zero above it)
    and the running sum G."""
    args = _units(t, chunk, gscale, jnp.dtype(dtype))
    (a, p, g_cum), want = _local_pallas(*args), ops_kda.local_xla(*args)
    assert a.shape == p.shape == (args[0].shape[0], chunk, chunk)
    for got, ref, tol in zip((a, p, g_cum), want, (2e-5, 2e-5, 1e-6)):
        assert bool(jnp.all(jnp.isfinite(got)))
        _close(got, ref, tol)
    assert not np.triu(a).any() and not np.triu(p, 1).any()


@pytest.mark.parametrize("t,chunk,gscale,dtype", _CASES)
def test_kernel_backward_is_the_xla_routes_vjp(t, chunk, gscale, dtype):
    """dq, dk, dg, dbeta of random cotangents of A, P and G: the
    backward kernel against `jax.vjp` of the XLA route."""
    args = _units(t, chunk, gscale, jnp.dtype(dtype))
    r = np.random.RandomState(1)
    want_out, vjp_w = jax.vjp(ops_kda.local_xla, *args)
    cts = tuple(jnp.asarray(r.randn(*x.shape).astype(np.float32))
                for x in want_out)
    _, vjp = jax.vjp(_local_pallas, *args)
    for got, want in zip(vjp(cts), vjp_w(cts)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
        _close(got, want, _TOL[dtype])


@pytest.mark.parametrize("t,gscale,dtype", [
    (64, 1.0, "float32"),
    (100, 1.0, "float32"),      # a tail that fills no chunk
    (192, 0.05, "float32"),     # three chunks: the state is carried
    (128, 30.0, "float32"),     # e^-1920 a chunk: past e^88 by far
    (100, 1.0, "bfloat16"),
    (128, 30.0, "bfloat16"),
])
def test_chunked_kda_through_both_routes_is_the_recurrence(
        monkeypatch, t, gscale, dtype):
    """`kda_chunked` with the kernels (interpret mode) and without,
    outputs and the gradients of all five inputs, against
    `kda_recurrent`; and each against the other."""
    args = _inputs(t, gscale, jnp.dtype(dtype))
    tol = 1e-4 if dtype == "float32" else 3e-2

    def run(fn):
        o = fn(*args)
        gr = jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a).astype(
            jnp.float32))), argnums=(0, 1, 2, 3, 4))(*args)
        return (o,) + gr

    want = run(ops_kda.kda_recurrent)
    routes, outs = {}, {}
    for force in (False, True):
        monkeypatch.setattr(ops_kda, "_FORCE_INTERPRET", force)
        fn = lambda *a: ops_kda.kda_chunked(*a, chunk=64)  # noqa: E731
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        routes[force] = ("route.pallas" in text, "route.xla" in text)
        outs[force] = run(fn)
        for got, ref in zip(outs[force], want):
            assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
            _close(got, ref, tol)
    assert routes == {False: (False, True), True: (True, False)}
    for got, ref in zip(outs[True], outs[False]):
        _close(got, ref, tol)


@pytest.fixture
def no_kernel_module(monkeypatch):
    """`import cxxnet_tpu.ops.pallas_kda` raises from here on: a route
    that reaches for the kernels fails the test."""
    monkeypatch.setitem(sys.modules, PK, None)


@pytest.mark.parametrize("force,chunk,d_k,d_v", [
    (False, 64, 128, 128),      # off the TPU
    (True, 32, 128, 128),       # not the chunk the kernels are sized for
    (True, 16, 128, 128),
    (True, 64, 64, 128),        # half a lane tile of key channels
    (True, 64, 128, 64),        # ... of value channels
    (True, 64, 192, 192),
])
def test_every_other_case_runs_the_xla_code_and_imports_nothing(
        monkeypatch, no_kernel_module, force, chunk, d_k, d_v):
    monkeypatch.setattr(ops_kda, "_FORCE_INTERPRET", force)
    assert ops_kda._kernel_route(d_k, d_v, chunk) is None
    q, k, _, g, beta = _inputs(2 * chunk, 1.0, jnp.float32, d=d_k)
    v = _inputs(2 * chunk, 1.0, jnp.float32, d=d_v)[2]
    fn = lambda *a: ops_kda.kda_chunked(*a, chunk=chunk)    # noqa: E731
    text = jax.jit(fn).lower(q, k, v, g, beta).as_text(debug_info=True)
    assert "route.xla" in text and "route.pallas" not in text
    _close(fn(q, k, v, g, beta), ops_kda.kda_recurrent(q, k, v, g, beta),
           1e-4)


def test_the_kernel_route_is_taken_where_all_of_it_holds(monkeypatch):
    monkeypatch.setattr(ops_kda, "_FORCE_INTERPRET", True)
    assert ops_kda._kernel_route(D, D, 64).__name__ == PK
    assert ops_kda._kernel_route(2 * D, D, 64).__name__ == PK


def test_a_mesh_of_two_devices_takes_the_xla_route(monkeypatch):
    """pallas_call has no partitioning rule: a step over more than one
    device, or inside the zero_stage >= 2 region (None bound), declines
    the kernels."""
    monkeypatch.setattr(ops_kda, "_FORCE_INTERPRET", True)
    assert ops_kda._kernel_route(D, D, 64)
    devs = np.array(jax.devices()[:2])
    assert devs.size == 2
    with active_mesh(jax.sharding.Mesh(devs, ("data",))):
        assert ops_kda._kernel_route(D, D, 64) is None
        args = _inputs(64, 1.0, jnp.float32)
        text = jax.jit(lambda *a: ops_kda.kda_chunked(
            *a, chunk=64)).lower(*args).as_text(debug_info=True)
        assert "route.xla" in text and "route.pallas" not in text
    with active_mesh(jax.sharding.Mesh(devs[:1], ("data",))):
        assert ops_kda._kernel_route(D, D, 64)
    with active_mesh(None):
        assert ops_kda._kernel_route(D, D, 64) is None


def test_kda_on_the_cpu_imports_nothing_of_pallas():
    """A process that builds the layers and runs `kda_chunked` at the
    kernels' own shapes off the TPU has no Pallas module loaded: the
    import (1.3-2 s of set-up) is paid only where the kernels run."""
    code = (
        "import sys, jax.numpy as jnp, cxxnet_tpu.nnet.trainer\n"
        "from cxxnet_tpu.ops import kda\n"
        "x = jnp.ones((1, 128, 1, 128)); g = -0.1 * x\n"
        "kda.kda_chunked(x, x, x, g, jnp.ones((1, 128, 1)) / 2, 64)\n"
        "print(sorted(m for m in sys.modules if 'pallas' in m))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.stdout.strip() == "[]", out.stdout + out.stderr[-500:]


_NET = """
netconfig=start
layer[0->x0] = embed:embed
  nvocab = 64
  nhidden = 32
{layers}
layer[{last},0->logits] = lm_head:lm_head
  nvocab = 64
  loss_block = 64
netconfig=end
input_shape = 1,128,1
batch_size = 1
dev = cpu
dtype = bfloat16
remat = 1
random_type = gaussian
init_sigma = 0.2
updater = adam
eta = 0.001
silent = 1
eval_train = 0
"""
_KDA = ("layer[x{i}->x{j}] = kda:k{j}\n  nhead = 1\n  head_dim = 128\n"
        "  gate_rank = 4\n  kda_chunk = 64")


def _step_text(nlayers, monkeypatch):
    """The train step of a net with `nlayers` kda layers under `remat =
    1`, lowered for the TPU platform (the kernels as Mosaic bodies)
    without a chip."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    monkeypatch.setattr(ops_kda, "_backend_ok", lambda: True)
    text = _NET.format(
        layers="\n".join(_KDA.format(i=i, j=i + 1) for i in range(nlayers)),
        last=f"x{nlayers}")
    t = NetTrainer()
    for k, v in parse_config_string(text):
        t.set_param(k, v)
    t.init_model()
    staged = t.stage_batch(DataBatch(
        data=np.zeros((1, 1, 128, 1), np.int32),
        label=np.zeros((1, 1), np.float32)))
    lowered = t._train_step.trace(
        t.state, staged.data, staged.extras, staged.labels, staged.mask,
        jax.random.PRNGKey(0)).lower(lowering_platforms=("tpu",))
    return lowered.as_text()


def _kernel_bodies(text):
    return {name: len(re.findall(rf'kernel_name = "{name}"', text))
            for name in ("kda_local_fwd", "kda_local_bwd")}


def test_layers_of_one_shape_share_one_lowered_kernel(monkeypatch):
    """Every process lowers the step before it can ask the compile
    cache for it, so each kernel body in the step's text is set-up that
    no cache saves. The kernels are called through one jitted function
    a direction: a second `kda` layer of the same shape adds calls, not
    bodies (forward: the layer's own and `remat`'s second run)."""
    one = _kernel_bodies(_step_text(1, monkeypatch))
    two = _kernel_bodies(_step_text(2, monkeypatch))
    assert one["kda_local_bwd"] == 1 and 1 <= one["kda_local_fwd"] <= 2
    assert two == one
