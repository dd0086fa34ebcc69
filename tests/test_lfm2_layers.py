"""The layers the LFM2 stack brought, one at a time: the double-gated
short convolution (`gconv`) against a position-at-a-time loop, `gqa`
with `qk_norm = 1` against `naive_attention` over normed and turned
heads (and with `qk_norm` unset tracing what it traced before the key
existed), the flash kernels in interpret mode at a 64-wide head with 4:1
grouped heads, and the share test of the sigmoid router with a selection
bias; and the whole stack of tests/test_lfm2.py once more with its
attention through those kernels.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import lfm2 as ref_mod
from cxxnet_tpu.layers import create_layer, lm
from cxxnet_tpu.ops import attention as ops_attn
from cxxnet_tpu.ops import pallas_attention as pa
from test_lfm2 import _stack_against_reference, small_blocks  # noqa: F401

SHAPE = (2, 1, 12, 16)


def _layer(kind, pairs, shapes=(SHAPE,), seed=11):
    lay = create_layer(kind, "l")
    for k, v in pairs:
        lay.set_param(k, str(v))
    lay.infer_shapes(list(shapes))
    return lay, lay.init_params(jax.random.PRNGKey(seed), list(shapes))


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("taps", [3, 1, 4])
def test_gconv_is_the_loop_over_positions(taps):
    """(B, C, z) = a W_in; c[t] = sum_j w[j] (B * z)[t - (K-1) + j],
    nothing before the start; m = (C * c) W_out: no activation, so the
    layer is cubic in its input (twice the input gives eight times the
    output)."""
    lay, p = _layer("gconv", [("conv_size", taps), ("init_sigma", 0.3)])
    assert {k: v.shape for k, v in p.items()} == {
        "win": (16, 48), "conv": (taps, 16), "wout": (16, 16)}
    assert lay.param_tags() == {"win": "wmat", "conv": "wmat", "wout": "wmat"}
    x = np.random.RandomState(3).randn(*SHAPE).astype(np.float32)
    (got,) = lay.apply(p, [jnp.asarray(x)], train=True)
    win, w, wout = (np.asarray(p[k], np.float64)
                    for k in ("win", "conv", "wout"))
    want = np.zeros(SHAPE, np.float64)
    for r in range(SHAPE[0]):
        u = []
        for t in range(SHAPE[2]):
            bcz = x[r, 0, t].astype(np.float64) @ win
            b, c, z = bcz[:16], bcz[16:32], bcz[32:]
            u.append(b * z)
            conv = sum(w[j] * u[t - (taps - 1) + j] for j in range(taps)
                       if t - (taps - 1) + j >= 0)
            want[r, 0, t] = (c * conv) @ wout
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    (twice,) = lay.apply(p, [jnp.asarray(2 * x)], train=True)
    np.testing.assert_allclose(twice, 8 * np.asarray(got), rtol=1e-4,
                               atol=1e-5)
    # and the reference's own mixer says the same
    text = ("netconfig=start\nlayer[0->x0] = embed:embed\n  nvocab = 64\n"
            "  nhidden = 16\nlayer[x0->x1] = gconv:l\n"
            f"  conv_size = {taps}\n"
            "layer[x1,0->logits] = lm_head:lm_head\n  nvocab = 64\n"
            "netconfig=end\ninput_shape = 1,12,1\nbatch_size = 2\n"
            "updater = adam\n")
    ref = ref_mod.Reference(text, {})
    rlay = next(l for l in ref.conf_layers if l.type == "gconv")
    for r in range(SHAPE[0]):
        np.testing.assert_allclose(
            ref._gconv(rlay, p, jnp.asarray(x[r, 0])), want[r, 0],
            rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="conv_size"):
        _layer("gconv", [("conv_size", 0)])


# ---------------------------------------------------------------------------
# QK-norm
# ---------------------------------------------------------------------------
GQA = [("nhead", 4), ("nkvhead", 2), ("head_dim", 8), ("rope_theta", 1e6),
       ("init_sigma", 0.3), ("eps", 1e-5)]


def test_gqa_qk_norm_is_naive_attention_over_normed_and_turned_heads():
    lay, p = _layer("gqa", GQA + [("qk_norm", 1)])
    assert p["qnorm"].shape == p["knorm"].shape == (8,)
    assert lay.param_tags()["qnorm"] == "bias"
    r = np.random.RandomState(5)
    # slopes that are not 1, so that their place shows
    p = dict(p, qnorm=jnp.asarray(1 + 0.3 * r.randn(8), jnp.float32),
             knorm=jnp.asarray(1 + 0.3 * r.randn(8), jnp.float32))
    x = jnp.asarray(r.randn(*SHAPE), jnp.float32)
    (got,) = lay.apply(p, [x], train=True)

    def heads(w, n):
        return jnp.einsum("bte,ehd->bhtd", x[:, 0], w.reshape(16, n, 8))

    def normed(a, slope):
        return a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5) * slope

    q = lm.rotary(normed(heads(p["wq"], 4), p["qnorm"]), 1e6)
    k = lm.rotary(normed(heads(p["wk"], 2), p["knorm"]), 1e6)
    o = ops_attn.naive_attention(q, k, heads(p["wv"], 2), causal=True)
    want = jnp.einsum("bhtd,hde->bte", o, p["wo"].reshape(4, 8, 16))
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-4, atol=1e-5)
    # the norm comes BEFORE the turn: the other order is another number
    q2 = normed(lm.rotary(heads(p["wq"], 4), 1e6), p["qnorm"])
    assert not np.allclose(q, q2, atol=1e-3)
    # a normed head forgets the scale of its projection
    (same,) = lay.apply(dict(p, wq=3 * p["wq"]), [x], train=True)
    np.testing.assert_allclose(same, got, rtol=1e-4, atol=1e-5)


PARENT_JAXPR_SHA256 = (
    "b3ba048be77fe7b74457f5546c8200405d76be407eae63b16b508aa00d03e77a")


def _traced(pairs) -> str:
    lay, p = _layer("gqa", pairs)
    x = jnp.zeros(SHAPE, jnp.float32)
    return str(jax.make_jaxpr(
        lambda p, x: lay.apply_with_stats(p, [x], train=True))(p, x))


def test_gqa_without_qk_norm_traces_what_it_traced_before_the_key():
    """A program's rate on the chip is a property of its whole text (PR
    35), so a `gqa` layer that does not ask for the norm keeps its four
    leaves and its text: no `rsqrt`, and letter for letter the jaxpr the
    layer gave at the commit before `qk_norm` existed (PR 37's tree,
    jax 0.9.0; a newer JAX that prints jaxprs otherwise needs the digest
    taken again from that commit, not from this file's layer)."""
    lay, p = _layer("gqa", GQA)
    assert sorted(p) == ["wk", "wo", "wq", "wv"]
    text = _traced(GQA)
    assert "rsqrt" not in text and text == _traced(GQA + [("qk_norm", 0)])
    assert "rsqrt" in _traced(GQA + [("qk_norm", 1)])
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_JAXPR_SHA256


# ---------------------------------------------------------------------------
# the kernels at a 64-wide head
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,bq,bk", [(64, 16, 16), (48, 16, 8)])
def test_flash_kernels_at_a_64_wide_head_with_grouped_heads(
        s, bq, bk, monkeypatch):
    """Forward and all three gradients in interpret mode, 8 query heads
    on 2 key/value heads of 64 (the cell's 32 on 8), causal; dk and dv
    sum over the four query heads of their group."""
    monkeypatch.setattr(pa, "BLOCK_Q", bq)
    monkeypatch.setattr(pa, "BLOCK_K", bk)
    monkeypatch.setattr(pa, "_LANE", 8)
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 8, s, 64), jnp.float32)
    k = jnp.asarray(r.randn(2, 2, s, 64), jnp.float32)
    v = jnp.asarray(r.randn(2, 2, s, 64), jnp.float32)
    assert pa._tile_ok(q, s) and pa._tiles_of(q, s) == (bq, bk)

    def kern(q, k, v):
        return pa.flash_attention(q, k, v, True, None, True, 0)

    def naive(q, k, v):
        return ops_attn.naive_attention(q, k, v, causal=True)

    np.testing.assert_allclose(kern(q, k, v), naive(q, k, v),
                               rtol=1e-5, atol=1e-5)
    gk = jax.grad(lambda *a: jnp.sum(jnp.cos(kern(*a))), (0, 1, 2))(q, k, v)
    gn = jax.grad(lambda *a: jnp.sum(jnp.cos(naive(*a))), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gn):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5,
                                   err_msg=f"d{name}")


@pytest.fixture
def kernels(monkeypatch):
    """The flash kernels in interpret mode, 8 x 8 tiles."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "BLOCK_Q", 8)
    monkeypatch.setattr(pa, "BLOCK_K", 8)
    monkeypatch.setattr(pa, "_LANE", 8)


def test_five_layer_stack_matches_the_reference_through_the_kernels(
        kernels, small_blocks):
    """The same with the attention layer's core in the flash kernels
    (interpret mode, 5 x 5 tiles of 8, four query heads a key/value
    head)."""
    trainer, _ = _stack_against_reference()
    assert trainer.fetch_counters()["l2_gqa.tiles"] == 1.0


def test_the_cells_shape_takes_whole_tiles():
    q = jax.ShapeDtypeStruct((1, 32, 32768, 64), jnp.bfloat16)
    assert pa._tile_ok(q, 32768)
    assert pa._tiles_of(q, 32768) == (1024, 1024)
    assert pa.tile_share(q, 0) == 1.0


# ---------------------------------------------------------------------------
# the experts
# ---------------------------------------------------------------------------
def _moe(held=None):
    m = create_layer("moe", "e")
    for k, v in (("nexpert", "16"), ("moe_top_k", "4"), ("nhidden", "8"),
                 ("moe_glu", "1"), ("moe_score", "sigmoid"),
                 ("moe_scale", "1"), ("moe_norm_eps", "0.000001"),
                 ("moe_bias_sigma", "0.3"), ("moe_aux", "0"),
                 ("init_sigma", "0.3")):
        m.set_param(k, v)
    if held:
        m.set_param("moe_held", held)
    return m


def test_four_shares_add_up_to_the_uncut_reference():
    """The share test of the model-configs guide: 16 experts held as
    0-3, 4-7, 8-11, 12-15. Each share scores all 16, chooses the top 4 of
    score + bias and computes its own experts' part; the four parts are
    what the plain reference gives for the whole layer (float32: 1e-5).
    There is no shared expert to count once."""
    text = ("netconfig=start\n"
            "layer[0->x0] = embed:embed\n  nvocab = 64\n  nhidden = 16\n"
            "layer[x0->f] = moe:e\n  nexpert = 16\n  moe_top_k = 4\n"
            "  nhidden = 8\n  moe_glu = 1\n  moe_score = sigmoid\n"
            "  moe_scale = 1\n  moe_norm_eps = 0.000001\n"
            "  moe_bias_sigma = 0.3\n  moe_aux = 0\n"
            "layer[f,0->logits] = lm_head:lm_head\n  nvocab = 64\n"
            "netconfig=end\ninput_shape = 1,12,1\nbatch_size = 2\n"
            "updater = adam\n")
    ref = ref_mod.Reference(text, {})
    lay = next(l for l in ref.conf_layers if l.type == "moe")
    assert ref_mod.held_of(lay) == (0, 16)
    whole = _moe()
    whole.infer_shapes([SHAPE])
    p = whole.init_params(jax.random.PRNGKey(11), [SHAPE])
    assert float(jnp.abs(p["sbias"]).max()) > 0.1
    x = jnp.asarray(np.random.RandomState(2).randn(*SHAPE), jnp.float32)
    want = np.stack([np.asarray(ref._moe(lay, p, x[i, 0]))
                     for i in range(2)])
    total, held_sum = 0.0, 0.0
    for first in (0, 4, 8, 12):
        share = _moe(held=f"{first},4")
        share.infer_shapes([SHAPE])
        ps = dict(p, **{k: p[k][first:first + 4] for k in ("w1", "w2", "w3")})
        outs, _, stats = share.apply_with_stats(ps, [x], train=True)
        total = total + outs[0]
        held_sum += float(stats["held"])
        assert float(stats["dropped"]) == 0
    assert held_sum == 2 * 12 * 4          # every assignment held once
    np.testing.assert_allclose(total[:, 0], want, rtol=1e-5, atol=1e-5)
    # the bias chooses and does not weigh: the weights are the chosen
    # scores over their sum + 1e-6
    weights, chosen, _ = whole._route(p, x[:, 0])
    s = 1 / (1 + np.exp(-(np.asarray(x[:, 0]) @ np.asarray(p["gate"]).T)))
    order = np.argsort(-(s + np.asarray(p["sbias"])), axis=-1)[..., :4]
    assert np.array_equal(np.sort(order, -1), np.sort(chosen, -1))
    assert not np.array_equal(
        np.sort(np.argsort(-s, axis=-1)[..., :4], -1), np.sort(chosen, -1))
    picked = np.take_along_axis(s, np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)


@pytest.mark.parametrize("tokens,top_k,nexpert,rows", [
    (8192, 8, 256, 1024),     # the Kimi cell: 256 expected, one tile
    (16384, 6, 64, 1024),     # the SmallThinker cell: 1,536, mid-tile
    (32768, 4, 64, 1408),     # the LFM2 cell: 2,048 would end on an edge
    (4096, 1, 4, 2048),       # 1,024 expected: one tile of twice that
    (40, 2, 16, 16),          # tiny sizes
])
def test_tile_rows_keeps_an_experts_expected_run_off_a_tiles_edge(
        tokens, top_k, nexpert, rows):
    from cxxnet_tpu.layers import moe
    assert moe.tile_rows(tokens * top_k, nexpert) == rows
    if rows > 16:
        run = tokens * top_k / nexpert / rows
        assert 0.2 <= run - int(run) <= 0.8 and rows % 128 == 0


def test_moe_norm_eps_unset_leaves_the_router_as_it_was():
    """The Kimi cell's router states no `moe_norm_eps`: its text has no
    added constant."""
    def traced(extra):
        m = _moe()
        m.norm_eps = 0.0
        for k, v in extra:
            m.set_param(k, v)
        m.infer_shapes([SHAPE])
        p = m.init_params(jax.random.PRNGKey(1), [SHAPE])
        return str(jax.make_jaxpr(lambda p, x: m._route(p, x))(
            p, jnp.zeros((2, 12, 16), jnp.float32)))

    assert traced([]) == traced([("moe_norm_eps", "0")])
    assert traced([]) != traced([("moe_norm_eps", "0.000001")])
