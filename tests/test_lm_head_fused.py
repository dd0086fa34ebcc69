"""The head's loss with its gradient made in the forward (layers/lm.py
`head_loss`): against the checkpointed scan it replaces, kept here as the
reference; the products it runs where it is differentiated and where it
is not; and the scopes the benchmark's readers find the head by in a
compiled step (benchmark/scope_map.py)."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import scope_map as sm
from cxxnet_tpu.layers import create_layer
from cxxnet_tpu.layers.lm import head_loss
from test_kimi_linear import batch_of, build, tokens

E, V, ROWS, BLOCK = 16, 64, 3, 8


def checkpointed_loss(w, y, ids, mask, k, loss_block, weight):
    """The loss as the head computed it before: the sum over rows of the
    mean over t < T-k of CE(y_t w, id_{t+k}), times `weight`, in blocks
    of `loss_block` positions under `jax.checkpoint`, so that the
    backward computes each block's logits again."""
    b, t, e = y.shape
    blk = min(loss_block, t)
    pad = (-t) % blk
    target = jnp.concatenate([ids[:, k:], ids[:, :k]], axis=1)
    rows = jnp.broadcast_to(
        (jnp.arange(t) < t - k).astype(jnp.float32), (b, t))
    if mask is not None:
        rows = rows * mask.astype(jnp.float32)[:, None]
    if pad:
        y = jnp.pad(y, ((0, 0), (0, pad), (0, 0)))
        target = jnp.pad(target, ((0, 0), (0, pad)))
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    n = (t + pad) // blk

    @jax.checkpoint
    def part(total, xs):
        yb, tb, wb = xs
        logits = jnp.einsum("bte,ev->btv", yb, w.astype(yb.dtype),
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        got = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return total + jnp.sum((logz - got) * wb), None

    def blocks(a):
        return jnp.moveaxis(a.reshape((b, n, blk) + a.shape[2:]), 1, 0)

    total, _ = jax.lax.scan(part, jnp.zeros((), jnp.float32),
                            (blocks(y), blocks(target), blocks(rows)))
    return weight * (total / max(t - k, 1))


def _inputs(t, dtype, seed=0):
    rs = np.random.RandomState(seed)
    w = jnp.asarray(rs.randn(E, V) * 0.3, jnp.float32).astype(dtype)
    y = jnp.asarray(rs.randn(ROWS, t, E), jnp.float32).astype(dtype)
    ids = jnp.asarray(rs.randint(0, V, (ROWS, t)), jnp.int32)
    return w, y, ids


def _grads(fn, w, y):
    return jax.value_and_grad(fn, (0, 1))(w, y)


def _f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32), np.float64)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("t", [16, 13])
@pytest.mark.parametrize("weight", [1.0, 0.1])
@pytest.mark.parametrize("k", [1, 2])
def test_loss_and_gradients_agree_with_the_checkpointed_scan(
        k, weight, t, masked, dtype):
    """T = 16 is two whole blocks, 13 pads the second; a mask drops the
    middle row. float32: the same arithmetic in another order. bf16 y
    and w: the loss as before, each gradient within bf16's rounding of
    the float32 reference's (the old dW summed its blocks in bf16, the
    new one in float32: the new one is as close or closer)."""
    dtype = jnp.dtype(dtype)
    w, y, ids = _inputs(t, dtype)
    mask = jnp.asarray([1.0, 0.0, 1.0]) if masked else None

    def fused(w, y):
        return head_loss(w, y, ids, mask, k, min(BLOCK, t), weight)[0]

    def before(w, y):
        return checkpointed_loss(w, y, ids, mask, k, BLOCK, weight)

    loss, (dw, dy) = _grads(fused, w, y)
    ref, (rdw, rdy) = _grads(before, w, y)
    assert dw.dtype == w.dtype and dy.dtype == y.dtype
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    if dtype == jnp.float32:
        for got, want in ((dw, rdw), (dy, rdy)):
            np.testing.assert_allclose(got, want, rtol=1e-5,
                                       atol=1e-6 * np.abs(want).max())
        return
    # the float32 reference on the same (bf16-valued) inputs
    _, (tdw, tdy) = _grads(before, w.astype(jnp.float32),
                           y.astype(jnp.float32))
    for got, old, truth in ((dw, rdw, tdw), (dy, rdy, tdy)):
        got, old, truth = _f32(got), _f32(old), _f32(truth)
        np.testing.assert_allclose(got, truth, rtol=2.0 ** -8,
                                   atol=2.0 ** -8 * np.abs(truth).max())
        assert (np.abs(got - truth).max()
                <= np.abs(old - truth).max() + 1e-9)
    if masked:
        assert not np.asarray(dy[1]).any()


@pytest.mark.parametrize("weight", [0.1, 0.0])
def test_the_unweighted_mean_has_its_own_gradient(weight):
    """The second output, the mean the head counts as `loss`, is
    differentiable too: its gradient is the unweighted loss's, also
    where `loss_weight` = 0 leaves the weighted one none."""
    w, y, ids = _inputs(13, jnp.float32, seed=1)
    _, (dw, dy) = _grads(
        lambda w, y: head_loss(w, y, ids, None, 2, BLOCK, weight)[1], w, y)
    _, (rdw, rdy) = _grads(
        lambda w, y: checkpointed_loss(w, y, ids, None, 2, BLOCK, 1.0), w, y)
    for got, want in ((dw, rdw), (dy, rdy)):
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-6 * np.abs(want).max())
    _, (zdw, zdy) = _grads(
        lambda w, y: head_loss(w, y, ids, None, 2, BLOCK, 0.0)[0], w, y)
    assert not np.asarray(zdw).any() and not np.asarray(zdy).any()


def _head():
    lay = create_layer("lm_head", "lm_head")
    for key, val in (("nvocab", V), ("loss_block", BLOCK)):
        lay.set_param(key, str(val))
    shapes = [(ROWS, 1, 13, E), (ROWS, 1, 13, 1)]
    lay.infer_shapes(shapes)
    return lay, lay.init_params(jax.random.PRNGKey(5), shapes)


def test_two_passes_sharing_one_weight_sum_their_gradients():
    """`share[lm_head]:mtp_head` with `target_offset = 2`, `loss_weight
    = 0.1`: the shared weight's gradient is the sum of the two passes'
    as the checkpointed scan gives it, and the second pass counts its
    unweighted loss."""
    plain, p = _head()
    mtp = plain.for_connection([("target_offset", "2"),
                                ("loss_weight", "0.1")])
    _, h1, ids = _inputs(13, jnp.float32, seed=2)
    h2 = jnp.asarray(np.random.RandomState(3).randn(ROWS, 13, E),
                     jnp.float32)
    tok = ids[:, None, :, None]

    def both(p, h1, h2):
        _, l1, _ = plain.apply_with_stats(p, [h1[:, None], tok], train=True)
        _, l2, st = mtp.apply_with_stats(p, [h2[:, None], tok], train=True)
        return l1 + l2, st["loss"]

    def before(w, h1, h2):
        return (checkpointed_loss(w, h1, ids, None, 1, BLOCK, 1.0)
                + checkpointed_loss(w, h2, ids, None, 2, BLOCK, 0.1))

    (loss, counted), grads = jax.value_and_grad(
        both, (0, 1, 2), has_aux=True)(p, h1, h2)
    ref, rgrads = jax.value_and_grad(before, (0, 1, 2))(p["wmat"], h1, h2)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    np.testing.assert_allclose(
        float(counted),
        float(checkpointed_loss(p["wmat"], h2, ids, None, 2, BLOCK, 1.0)),
        rtol=1e-6)
    for got, want in ((grads[0]["wmat"], rgrads[0]), (grads[1], rgrads[1]),
                      (grads[2], rgrads[2])):
        np.testing.assert_allclose(np.asarray(got).reshape(want.shape), want,
                                   rtol=1e-5, atol=1e-6 * np.abs(want).max())


def _dots(jaxpr, in_scan=False):
    """(in a scan's body, elsewhere): the dot_generals of a jaxpr and of
    every jaxpr inside it."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found[in_scan] += 1
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _dots(sub, in_scan or eqn.primitive.name
                                   == "scan")
    return found


def test_differentiated_the_scan_runs_three_products_and_the_backward_none():
    """Logits, dy and dW in the forward's one scan; the backward only
    scales. Not differentiated: the logits product alone."""
    w, y, ids = _inputs(13, jnp.bfloat16)

    def loss(w, y):
        return head_loss(w, y, ids, None, 1, BLOCK, 1.0)[0]

    graded = jax.make_jaxpr(jax.grad(loss, (0, 1)))(w, y).jaxpr
    assert _dots(graded) == {True: 3}
    plain = jax.make_jaxpr(loss)(w, y).jaxpr
    assert _dots(plain) == {True: 1}
    # the parent's: the logits twice
    before = jax.make_jaxpr(jax.grad(
        lambda w, y: checkpointed_loss(w, y, ids, None, 1, BLOCK, 1.0),
        (0, 1)))(w, y).jaxpr
    assert _dots(before) == {True: 4}


SCOPE_CONF = """
netconfig=start
layer[0->x0] = embed:embed
  nvocab = 64
  nhidden = 32
layer[x0->x1] = rms_norm:final_norm
layer[x1,0->logits] = lm_head:lm_head
  nvocab = 64
layer[x0->m1] = rms_norm:mtp_norm
layer[m1,0->mtp_logits] = share[lm_head]:mtp_head
  target_offset = 2
  loss_weight = 0.1
netconfig=end
input_shape = 1,40,1
batch_size = 2
updater = adam
"""


def test_the_compiled_step_reads_as_the_head_in_both_passes():
    """benchmark/scope_map.py over a small token net's `step_hlo()`: all
    that names a pass of the head maps to (fwd or bwd, lm_head, <key>),
    so `lm_head_ms` and `mtp_ms` still hold the head's whole time; each
    pass's loop runs its three products in the forward, and no product
    of the head is left in the backward."""
    t = build(SCOPE_CONF, {"dev": "cpu", "loss_block": "16", "silent": "1",
                           "dtype": "bfloat16"})
    text = t.step_hlo(t.stage_batch(batch_of(tokens())))
    scopes = sm.scopes_of(text)
    comps = sm._computations(text)
    for key in ("lm_head", "mtp_head"):
        named = [i for instrs in comps.values() for i in instrs
                 if f"lm_head.{key})" in i.op_name]
        assert named
        for ins in named:
            phase, kind, got = scopes[ins.name]
            assert (kind, got) == ("lm_head", key), ins.op_name
            assert phase == ("bwd" if "transpose(" in ins.op_name
                             else "fwd"), ins.op_name
        loops = [i for i in named if i.opcode == "while"]
        assert len(loops) == 1
        dots = [scopes[i.name] for i in named if i.opcode == "dot"]
        assert dots == [("fwd", "lm_head", key)] * 3
        assert sum("/loss_grad/" in i.op_name for i in named
                   if i.opcode == "dot") == 2
