"""The layers the Nemotron-H stack brought, one at a time, on the CPU in
float32 at widths cut to a few: the chunked SSD (ops/ssd.py) against
the recurrence a position at a time and against the reference's dual
form; `mamba2`, the latent squared-ReLU `moe` and `mtp` against the
plain reference (benchmark/reference/nemotron_h.py); the chip's shares
of a Mamba-2, an attention and a LatentMoE layer adding up to the uncut
layer; and the keys the older cells do not set leaving their layers'
traced text as it was. The whole conf through the trainer against the
reference is the cell's dry run (benchmark/tests/test_run_nemotron.py).

Tolerances: the same float32 arithmetic in another order (chunks
against a position at a time or the quadratic form), to 1e-4 of the
largest entry, unless a test says otherwise.
"""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h as ref_mod
from cxxnet_tpu.layers import create_layer
from cxxnet_tpu.ops import ssd

SHAPE = (2, 1, 12, 16)


def _layer(kind, pairs, shapes=(SHAPE,), seed=11):
    lay = create_layer(kind, "l")
    for k, v in pairs:
        lay.set_param(k, str(v))
    lay.infer_shapes(list(shapes))
    return lay, lay.init_params(jax.random.PRNGKey(seed), list(shapes))


def _run(lay, p, xs, **kw):
    """The layer's outputs (`apply_with_stats` where it counts), one
    compile."""
    if hasattr(lay, "apply_with_stats"):
        return jax.jit(lambda p, xs: lay.apply_with_stats(
            p, xs, train=True, **kw))(p, xs)
    return jax.jit(lambda p, xs: lay.apply(p, xs, train=True, **kw))(p, xs)


def _rows(fn, *xs):
    """A reference function of one row's (T, d) maps over the rows of
    (b, 1, T, d) inputs, one compile."""
    return jax.jit(jax.vmap(fn))(*(x[:, 0] for x in xs))


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * np.abs(want).max(), \
        np.abs(got - want).max() / np.abs(want).max()


def _ref_layer(text_layer, d=16, t=12):
    """The reference's reading of one layer of a conf."""
    text = ("netconfig=start\nlayer[0->x0] = embed:embed\n  nvocab = 64\n"
            f"  nhidden = {d}\n{text_layer}\n"
            "layer[x1,0->logits] = lm_head:lm_head\n  nvocab = 64\n"
            f"netconfig=end\ninput_shape = 1,{t},1\nbatch_size = 2\n"
            "updater = adam\n")
    ref = ref_mod.Reference(text, {})
    return ref, ref.conf_layers[1]


# ---------------------------------------------------------------------------
# the SSD
# ---------------------------------------------------------------------------
def _ssd_inputs(t, seed=0, b=2, h=4, p=8, g=2, n=6, dmax=1.0):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(dmax),
                                      (b, t, h))), jnp.float32)
    a = -jnp.asarray(r.uniform(1, 16, h), jnp.float32)
    return f(b, t, h, p), dt, a, f(b, t, g, n), f(b, t, g, n)


@pytest.mark.parametrize("t,chunk", [(37, 8), (32, 16), (5, 8)])
def test_chunked_ssd_is_the_recurrence_and_the_dual_form(t, chunk):
    """Chunks of 8 over 37 positions (the tail padded), whole chunks,
    and one part-chunk; forward and every input's gradient against the
    recurrence; the forward against the reference's quadratic form,
    row by row, in blocks of 8 query positions."""
    x, dt, a, b, c = _ssd_inputs(t)
    chunked = functools.partial(ssd.ssd_chunked, chunk=chunk)
    got = jax.jit(chunked)(x, dt, a, b, c)
    _close(got, jax.jit(ssd.ssd_recurrent)(x, dt, a, b, c))
    dual = jax.jit(jax.vmap(ref_mod.ssd_dual, (0, 0, None, 0, 0)))
    _close(got, dual(x, dt, a, b, c))

    def grads(f):
        return jax.jit(jax.grad(lambda *v: jnp.sum(jnp.sin(f(*v))),
                                (0, 1, 2, 3, 4)))(x, dt, a, b, c)

    gc, gr = grads(chunked), grads(ssd.ssd_recurrent)
    for name, u, v in zip(("x", "dt", "a", "b", "c"), gc, gr):
        _close(u, v, 2e-4)


def test_ssd_decays_past_float32_within_a_chunk_stay_finite():
    """Steps of up to dt a = -160: a chunk's running sum passes e^-88,
    where exp(-G) would overflow; built from differences, every decay is
    at most 1 and the output and its gradient stay finite and equal to
    the recurrence's."""
    x, dt, a, b, c = _ssd_inputs(24, seed=3, dmax=10.0)
    got = jax.jit(ssd.ssd_chunked, static_argnums=5)(x, dt, a, b, c, 8)
    _close(got, jax.jit(ssd.ssd_recurrent)(x, dt, a, b, c))
    g = jax.jit(jax.grad(
        lambda x: jnp.sum(ssd.ssd_chunked(x, dt, a, b, c, 8))))(x)
    assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------
MAMBA = [("nhead", 4), ("head_dim", 4), ("ngroups", 2), ("ssm_state", 4),
         ("ssd_chunk", 8), ("init_sigma", 0.3)]


def _mamba_text(pairs):
    return "layer[x0->x1] = mamba2:m1\n" + "\n".join(
        f"  {k} = {v}" for k, v in pairs if k != "init_sigma")


def test_mamba2_leaves_and_the_reference_forward():
    lay, p = _layer("mamba2", MAMBA)
    # W_in: z 16, x 16, B 8, C 8, dt 4; the conv over x, B and C
    assert {k: v.shape for k, v in p.items()} == {
        "win": (16, 52), "conv": (4, 32), "conv_b": (32,), "dt_bias": (4,),
        "a_log": (4,), "d": (4,), "norm": (16,), "wout": (16, 16)}
    assert lay.param_tags()["conv"] == "wmat" and \
        lay.param_tags()["d"] == "bias"
    assert float(p["a_log"].min()) >= 0 and float(p["a_log"].max()) <= \
        np.log(16) + 1e-6
    step = np.log1p(np.exp(np.asarray(p["dt_bias"])))
    assert (step >= 1e-3 - 1e-7).all() and (step <= 0.1 + 1e-7).all()
    r = np.random.RandomState(5)
    # a conv bias, D and norm slopes that are not their start, so that
    # their places show
    p = dict(p, conv_b=jnp.asarray(0.3 * r.randn(32), jnp.float32),
             d=jnp.asarray(1 + 0.5 * r.randn(4), jnp.float32),
             norm=jnp.asarray(1 + 0.3 * r.randn(16), jnp.float32))
    x = jnp.asarray(r.randn(*SHAPE), jnp.float32)
    (got,) = _run(lay, p, [x])
    ref, rlay = _ref_layer(_mamba_text(MAMBA))
    _close(got[:, 0], _rows(lambda x: ref._mamba2(rlay, p, x), x))
    # each broken reference reads otherwise
    for fault in ("no_D", "ungated_norm"):
        ref.fault = fault
        assert not np.allclose(
            got[:, 0], _rows(lambda x: ref._mamba2(rlay, p, x), x),
            atol=1e-3), fault
    with pytest.raises(ValueError, match="multiple of ngroups"):
        _layer("mamba2", MAMBA[:2] + [("ngroups", 3)] + MAMBA[3:])


def test_eight_head_shares_of_mamba2_add_up_to_the_uncut_layer():
    """16 heads of 4 in 8 groups of B/C (two heads a group, as the
    published 128 heads read 8 groups), cut as the cell cuts them: a
    share holds 2 heads and their group, its columns of W_in, its
    channels of the conv, its slopes of the gated norm (whose group of
    2 x 4 channels is then whole) and its rows of W_out. The shares'
    outputs add up to what the reference gives for the uncut layer."""
    full = [("nhead", 16), ("head_dim", 4), ("ngroups", 8),
            ("ssm_state", 3), ("ssd_chunk", 8), ("init_sigma", 0.3)]
    _, p = _layer("mamba2", full)
    ref, rlay = _ref_layer(_mamba_text(full))
    x = jnp.asarray(np.random.RandomState(2).randn(*SHAPE), jnp.float32)
    want = _rows(lambda x: ref._mamba2(rlay, p, x), x)
    d_in, gn = 64, 24                       # 16 x 4; 8 groups x 3
    share, _ = _layer("mamba2", [("nhead", 2), ("head_dim", 4),
                                 ("ngroups", 1), ("ssm_state", 3),
                                 ("ssd_chunk", 8)])
    run = jax.jit(lambda ps, x: share.apply(ps, [x], train=True)[0])
    total = 0.0
    for s in range(8):
        xs = np.arange(s * 8, s * 8 + 8)              # x and z channels
        bs = d_in + np.arange(s * 3, s * 3 + 3)       # its group's B
        cs = d_in + gn + np.arange(s * 3, s * 3 + 3)  # and C
        conv = np.concatenate([xs, bs, cs])
        cols = np.concatenate([xs, d_in + conv,
                               2 * d_in + 2 * gn + np.arange(2 * s, 2 * s + 2)])
        heads = np.arange(2 * s, 2 * s + 2)
        ps = {"win": p["win"][:, cols], "conv": p["conv"][:, conv],
              "conv_b": p["conv_b"][conv], "dt_bias": p["dt_bias"][heads],
              "a_log": p["a_log"][heads], "d": p["d"][heads],
              "norm": p["norm"][xs], "wout": p["wout"][xs]}
        total = total + run(ps, x)
    _close(total[:, 0], want)


# ---------------------------------------------------------------------------
# grouped-query attention without a positional encoding
# ---------------------------------------------------------------------------
def test_eight_head_shares_of_nope_gqa_add_up_to_the_uncut_layer():
    """16 query heads on 2 key/value heads of 4 (the published 32 on 2),
    cut into 8 shares of 2 query heads and the key/value head they read
    (shares 0-3 head 0, 4-7 head 1), no positional encoding: the shares'
    outputs add up to the reference's uncut layer."""
    full = [("nhead", 16), ("nkvhead", 2), ("head_dim", 4),
            ("init_sigma", 0.3)]
    _, p = _layer("gqa", full)
    text = "layer[x0->x1] = gqa:a1\n  nhead = 16\n  nkvhead = 2\n  head_dim = 4"
    ref, rlay = _ref_layer(text)
    x = jnp.asarray(np.random.RandomState(4).randn(*SHAPE), jnp.float32)
    want = _rows(lambda x: ref._gqa(rlay, p, x), x)
    share, _ = _layer("gqa", [("nhead", 2), ("nkvhead", 1), ("head_dim", 4)])
    run = jax.jit(lambda ps, x: share.apply(ps, [x], train=True)[0])
    total = 0.0
    for s in range(8):
        q = np.arange(8 * s, 8 * s + 8)
        kv = np.arange(4 * (s // 4), 4 * (s // 4) + 4)
        ps = {"wq": p["wq"][:, q], "wk": p["wk"][:, kv], "wv": p["wv"][:, kv],
              "wo": p["wo"][q]}
        total = total + run(ps, x)
    _close(total[:, 0], want)
    ref.fault = "rotary_on"
    assert not np.allclose(want, _rows(lambda x: ref._gqa(rlay, p, x), x),
                           atol=1e-3)


# ---------------------------------------------------------------------------
# the LatentMoE
# ---------------------------------------------------------------------------
def _latent_moe(held=None, shared=24):
    pairs = [("nexpert", 16), ("moe_top_k", 4), ("nhidden", 8),
             ("moe_act", "relu2"), ("no_bias", 1), ("moe_latent", 12),
             ("moe_shared_width", shared), ("moe_score", "sigmoid"),
             ("moe_scale", 5), ("moe_bias_sigma", 0.3), ("moe_aux", 0),
             ("init_sigma", 0.3)]
    if held:
        pairs.append(("moe_held", held))
    return _layer("moe", pairs)


MOE_TEXT = ("layer[x0->x1] = moe:e1\n  nexpert = 16\n  moe_top_k = 4\n"
            "  nhidden = 8\n  moe_act = relu2\n  no_bias = 1\n"
            "  moe_latent = 12\n  moe_shared_width = 24\n"
            "  moe_score = sigmoid\n  moe_scale = 5\n"
            "  moe_bias_sigma = 0.3\n  moe_aux = 0")


def test_latent_moe_leaves_and_the_reference():
    lay, p = _latent_moe()
    assert {k: v.shape for k, v in p.items()} == {
        "gate": (16, 16), "w1": (16, 8, 12), "w2": (16, 12, 8),
        "s1": (24, 16), "s2": (16, 24), "sbias": (16,),
        "lat_in": (12, 16), "lat_out": (16, 12)}
    assert lay.param_tags()["lat_in"] == "wmat"
    ref, rlay = _ref_layer(MOE_TEXT)
    x = jnp.asarray(np.random.RandomState(6).randn(*SHAPE), jnp.float32)
    (got,), _, _ = _run(lay, p, [x])
    _close(got[:, 0], _rows(lambda x: ref._moe(rlay, p, x), x))


def test_expert_and_column_shares_add_up_to_the_uncut_latent_moe():
    """The expert-share test for a LatentMoE: 16
    experts held four a share and the shared expert's 24 columns six a
    share, over 4 shares. Each share scores all 16, chooses the top 4
    of score + bias, runs its experts in the latent width through the
    whole W_down and W_up (which every chip computes alike: counted once
    by being linear) and its columns of the shared expert: the parts add
    up to the reference's uncut layer."""
    _, p = _latent_moe()
    ref, rlay = _ref_layer(MOE_TEXT)
    x = jnp.asarray(np.random.RandomState(2).randn(*SHAPE), jnp.float32)
    want = _rows(lambda x: ref._moe(rlay, p, x), x)
    total, held = 0.0, 0.0
    for s in range(4):
        share, _ = _latent_moe(held=f"{4 * s},4", shared=6)
        cols = slice(6 * s, 6 * s + 6)
        ps = dict(p, w1=p["w1"][4 * s:4 * s + 4], w2=p["w2"][4 * s:4 * s + 4],
                  s1=p["s1"][cols], s2=p["s2"][:, cols])
        outs, _, stats = _run(share, ps, [x])
        total = total + outs[0]
        held += float(stats["held"])
        assert float(stats["dropped"]) == 0
    assert held == 2 * 12 * 4                # every assignment held once
    _close(total[:, 0], want)


# ---------------------------------------------------------------------------
# the MTP projection and the head's second pass
# ---------------------------------------------------------------------------
def test_mtp_reads_the_next_tokens_embedding_and_the_hidden_state():
    lay, p = _layer("mtp", [("init_sigma", 0.3)], (SHAPE, SHAPE))
    assert {k: v.shape for k, v in p.items()} == {
        "enorm": (16,), "hnorm": (16,), "weh": (32, 16)}
    r = np.random.RandomState(8)
    h, emb = (jnp.asarray(r.randn(*SHAPE), jnp.float32) for _ in range(2))
    (got,) = _run(lay, p, [h, emb])
    text = ("netconfig=start\nlayer[0->x0] = embed:embed\n  nvocab = 64\n"
            "  nhidden = 16\nlayer[x0,x0->x1] = mtp:m\n"
            "layer[x1,0->logits] = lm_head:lm_head\n  nvocab = 64\n"
            "netconfig=end\ninput_shape = 1,12,1\nbatch_size = 2\n"
            "updater = adam\n")
    ref = ref_mod.Reference(text, {})
    rlay = ref.conf_layers[1]
    _close(got[:, 0], _rows(lambda h, e: ref._mtp(rlay, p, h, e), h, emb))
    # position t reads token t+1's embedding: changing token 5's row
    # moves position 4 and no other
    (moved,) = _run(lay, p, [h, emb.at[:, :, 5].add(1.0)])
    changed = np.abs(np.asarray(moved - got)).max(axis=(0, 1, 3)) > 0
    assert changed.tolist() == [t == 4 for t in range(12)]


def test_lm_head_target_offset_weight_and_counter():
    """With `target_offset = 2` the head's loss is the mean over
    t < T-2 of CE(logits_t, token t+2), weighted by `loss_weight` in
    the total and counted unweighted as `loss`; the plain head counts
    nothing."""
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 1, 12, 1)),
                      jnp.int32)
    y = jnp.asarray(np.random.RandomState(2).randn(*SHAPE), jnp.float32)
    plain, p = _layer("lm_head", [("nvocab", 64), ("loss_block", 5)],
                      (SHAPE, ids.shape))
    assert plain.stat_names == ()
    mtp = plain.for_connection([("target_offset", "2"),
                                ("loss_weight", "0.1")])
    assert mtp.stat_names == ("loss",) and plain.offset == 1
    _, weighted, stats = _run(mtp, p, [y, ids])
    logits = np.asarray(y[:, 0] @ p["wmat"], np.float64)
    logz = np.log(np.exp(logits).sum(-1))
    tok = np.asarray(ids[:, 0, :, 0])
    want = sum(np.mean([logz[r, t] - logits[r, t, tok[r, t + 2]]
                        for t in range(10)]) for r in range(2))
    np.testing.assert_allclose(float(stats["loss"]), want, rtol=1e-5)
    np.testing.assert_allclose(float(weighted), 0.1 * want, rtol=1e-5)
    with pytest.raises(ValueError, match="may set only"):
        plain.for_connection([("nvocab", "32")])


# ---------------------------------------------------------------------------
# the older cells' layers trace what they traced
# ---------------------------------------------------------------------------
# sha256 of the jaxpr of `moe.apply_with_stats` (train) as each older
# token cell configures the layer, at the commit before `moe_latent`,
# `moe_shared_width`, `relu2` and `target_offset` existed (jax 0.9.0);
# a newer JAX that prints jaxprs otherwise needs them taken again from
# that commit
PARENT_SHA256 = {
    "kimi": "c1fcc4e013e4045c4392e7129a6a2a976d195313df9c834689d6ac60cf3ee77e",
    "smallthinker":
        "efebaa2b0fe638fd8b0936902ebc11c73968afb1dd213427e3c3e81eaab3188e",
    "lfm2": "a19ce00fea2a71dae9393d8e33d9b4a66a1b7a51dee335c55bea07b23db4fa7c",
}
OLDER_MOE = {
    "kimi": [("nexpert", 16), ("moe_top_k", 2), ("nhidden", 8),
             ("moe_glu", 1), ("moe_score", "sigmoid"), ("moe_scale", 2.446),
             ("moe_shared", 1), ("moe_held", "4,4"), ("moe_bias_sigma", 0.05),
             ("moe_aux", 0)],
    "smallthinker": [("nexpert", 16), ("moe_top_k", 4), ("nhidden", 8),
                     ("moe_glu", 1), ("moe_act", "relu"), ("moe_norm_topk", 1),
                     ("moe_held", "0,4"), ("moe_aux", 0)],
    "lfm2": [("nexpert", 16), ("moe_top_k", 4), ("nhidden", 8),
             ("moe_glu", 1), ("moe_score", "sigmoid"), ("moe_scale", 1),
             ("moe_norm_eps", 0.000001), ("moe_held", "0,4"),
             ("moe_bias_sigma", 0.3), ("moe_aux", 0)],
}


@pytest.mark.parametrize("cell", sorted(OLDER_MOE))
def test_the_older_cells_layers_trace_what_they_traced(cell):
    """A rate on the chip is a property of the step's whole text: the
    expert layer of each older cell gives letter for letter the jaxpr it
    gave before this layer's new keys. (The head's loss changed on
    purpose since: tests/test_lm_head_fused.py holds it to the scan it
    replaced.)"""
    shapes = [SHAPE] * (2 if cell == "smallthinker" else 1)
    lay = create_layer("moe", "l")
    for k, v in OLDER_MOE[cell]:
        lay.set_param(k, str(v))
    lay.infer_shapes(shapes)
    p = lay.init_params(jax.random.PRNGKey(3), shapes)
    xs = [jnp.zeros(s, jnp.float32) for s in shapes]
    text = str(jax.make_jaxpr(
        lambda p, xs: lay.apply_with_stats(p, xs, train=True))(p, xs))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_SHA256[cell]
