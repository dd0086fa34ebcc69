"""The Kimi-Linear layers (layers/lm.py, layers/moe.py, ops/kda.py)
against the plain reference (benchmark/reference/kimi_linear.py), on the
CPU in float32 at widths cut to tens, from the example conf itself
(examples/LongSeq/kimi_linear_5l.conf) with its keys overridden as the
benchmark's dry run overrides them.

Tolerances. Program and reference run the same float32 arithmetic in
another order (chunks against a position at a time, a sorted dispatch
against a loop over experts, blocks of logits of another size), so a
loss agrees to 1e-5 of itself and a gradient leaf to 2e-4 of its largest
entry; where a test states another tolerance it says why.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import kimi_linear as ref_mod
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import create_layer
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.ops import attention as ops_attn
from cxxnet_tpu.ops import kda as ops_kda
from cxxnet_tpu.utils.config import parse_config_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF = os.path.join(ROOT, "examples", "LongSeq", "kimi_linear_5l.conf")
TINY = {
    "nhidden": "32", "nhead": "2", "head_dim": "8", "gate_rank": "4",
    "kv_rank": "16", "qk_nope_dim": "8", "qk_rope_dim": "4", "v_dim": "8",
    "nvocab": "64", "nexpert": "16", "moe_top_k": "2", "moe_held": "0,4",
    "input_shape": "1,40,1", "dtype": "float32", "batch_size": "2",
    "dev": "cpu", "kda_chunk": "16", "loss_block": "16", "silent": "1",
    # starts wide enough that every leaf has a gradient to compare
    "init_sigma": "0.2", "moe_bias_sigma": "0.05",
}
SEED = 7
LOSS_TOL = 1e-5
GRAD_TOL = 2e-4

ONE_LAYER = """
netconfig=start
layer[0->x0] = embed:embed
  nvocab = 64
  nhidden = 32
{layer}
layer[x1,0->logits] = lm_head:lm_head
  nvocab = 64
  loss_block = 16
netconfig=end
input_shape = 1,40,1
batch_size = 2
dev = cpu
dtype = float32
random_type = gaussian
init_sigma = 0.2
updater = adam
eta = 0.001
silent = 1
eval_train = 0
"""
LAYERS = {
    "rms_norm": "layer[x0->x1] = rms_norm:n1",
    "glu_ffn": "layer[x0->x1] = glu_ffn:f1\n  nhidden = 24",
    "kda": ("layer[x0->x1] = kda:k1\n  nhead = 2\n  head_dim = 8\n"
            "  gate_rank = 4\n  kda_chunk = 16"),
    "kda_ragged": ("layer[x0->x1] = kda:k1\n  nhead = 2\n  head_dim = 8\n"
                   "  gate_rank = 4\n  kda_chunk = 7"),
    "mla": ("layer[x0->x1] = mla:m1\n  nhead = 2\n  kv_rank = 16\n"
            "  qk_nope_dim = 8\n  qk_rope_dim = 4\n  v_dim = 8"),
    "moe": ("layer[x0->x1] = moe:e1\n  nexpert = 16\n  moe_top_k = 2\n"
            "  nhidden = 8\n  moe_glu = 1\n  moe_score = sigmoid\n"
            "  moe_scale = 2.446\n  moe_shared = 1\n  moe_held = 4,4\n"
            "  moe_bias_sigma = 0.05\n  moe_aux = 0"),
    "moe_whole": ("layer[x0->x1] = moe:e1\n  nexpert = 8\n  moe_top_k = 3\n"
                  "  nhidden = 8\n  moe_glu = 1\n  moe_score = sigmoid\n"
                  "  moe_aux = 0"),
}


def conf_text() -> str:
    with open(CONF) as f:
        return f.read()


def build(text: str, overrides) -> NetTrainer:
    """As `main.py` and the benchmark's driver build a trainer: the
    conf's pairs, then the overrides (an overridden key leaves every
    layer and is stated once for all)."""
    t = NetTrainer()
    for k, v in parse_config_string(text):
        if k not in overrides:
            t.set_param(k, v)
    for k, v in overrides.items():
        t.set_param(k, v)
    t.set_param("seed", str(SEED))
    t.init_model()
    return t


def tokens(rows=2, seq=40, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, size=(rows, 1, seq, 1), dtype=np.int32)


def batch_of(tok) -> DataBatch:
    return DataBatch(data=tok, label=np.zeros((len(tok), 1), np.float32))


def first_step(trainer, tok):
    """(loss, first gradient by leaf) of the program: the gradient is
    Adam's first moment after one step over its decay."""
    losses = []
    inner = trainer._train_step

    def recording(*a):
        out = inner(*a)
        losses.append(out[1])
        return out

    trainer._train_step = recording
    trainer.update(batch_of(tok))
    trainer._train_step = inner
    ust = jax.device_get(trainer.state["ustate"])
    grads = {lk: {pn: np.asarray(st["m1"]) / 0.1 for pn, st in d.items()}
             for lk, d in ust.items()}
    return float(losses[0]), grads


def assert_grads_agree(prog, ref, tol=GRAD_TOL):
    assert set(prog) == set(ref)
    for lk, d in ref.items():
        assert set(d) == set(prog[lk]), lk
        for pn, r in d.items():
            r = np.asarray(r)
            gap = np.abs(prog[lk][pn] - r).max() / (np.abs(r).max() + 1e-12)
            assert gap <= tol, (lk, pn, gap)
            assert np.abs(r).max() > 0, (lk, pn, "no gradient to compare")


def program_against_reference(text, overrides, tok, module=ref_mod):
    """Start, logits, loss and every gradient leaf of the program
    against `module.Reference` (tests/test_smallthinker.py hands its
    own)."""
    trainer = build(text, overrides)
    ref = module.Reference(text, overrides)
    params = jax.jit(ref.init)(SEED)
    # the same start: the program's own leaves, to an ulp of float32
    for lk, d in jax.device_get(trainer.state["params"]).items():
        for pn, w in d.items():
            np.testing.assert_allclose(w, np.asarray(params[lk][pn]),
                                       rtol=1e-6, atol=1e-7)
    logits = trainer.extract_feature(batch_of(tok), "logits")
    want = np.stack([np.asarray(ref.row_loss(params, tok[r, 0, :, 0],
                                             logits=True))
                     for r in range(len(tok))])
    np.testing.assert_allclose(logits[:, 0], want, rtol=2e-4, atol=2e-5)
    loss, grads = first_step(trainer, tok)
    rloss, rgrads = ref.grads(params, tok[:, 0, :, 0])
    assert abs(loss - float(rloss)) <= LOSS_TOL * abs(float(rloss))
    assert_grads_agree(grads, jax.device_get(rgrads))
    return trainer, ref, params


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_layer_alone_matches_the_reference(kind):
    """One new layer between an embedding and the head: the logits, the
    loss and the gradient of every leaf (the layer's, and through it the
    embedding's)."""
    text = ONE_LAYER.format(layer=LAYERS[kind])
    program_against_reference(text, {}, tokens())


def adam_steps_against_reference(trainer, ref, params, tok):
    """Three Adam steps of the program (one is behind it:
    `program_against_reference` read its gradient from it) against the
    reference's, leaf by leaf: the parameters' change to 5e-3 of its
    norm (Adam divides by sqrt(m2) + 1e-8, which turns the last digits
    of a gradient entry near 1e-8 into that entry's step).
    tests/test_smallthinker.py and tests/test_lfm2.py use it too."""
    mom = jax.tree.map(lambda a: {"m1": jnp.zeros_like(a),
                                  "m2": jnp.zeros_like(a)}, params)
    p = params
    for k in range(3):
        _, g = ref.grads(p, tok[:, 0, :, 0])
        p, mom = ref.update(p, mom, g, k)
    for _ in range(2):
        trainer.update(batch_of(tok))
    got = jax.device_get(trainer.state["params"])
    for lk, d in p.items():
        for pn, w in d.items():
            dr = np.asarray(w) - np.asarray(params[lk][pn])
            dp = got[lk][pn] - np.asarray(params[lk][pn])
            if pn == "sbias":
                # takes no step (the two starts differ by an ulp)
                assert np.abs(dp).max() < 1e-7 and not dr.any()
                continue
            # ... by the leaf's norm: single entries near eps move
            # freely; plus an ulp of the leaf, which the change is added to
            room = 5e-3 * np.linalg.norm(dr) + np.sqrt(dr.size) * np.spacing(
                np.abs(np.asarray(w)).max())
            assert np.linalg.norm(dp - dr) <= room, (lk, pn)


def test_five_layer_stack_matches_the_reference():
    """The example conf at tiny widths: loss and gradients of all 33
    conf layers' leaves, then three Adam steps against the reference's
    (the parameters' change, to 5e-3 of its norm, leaf by leaf:
    Adam divides by sqrt(m2) + 1e-8, which turns the last digits of a
    gradient entry near 1e-8 into that entry's step)."""
    tok = tokens()
    trainer, ref, params = program_against_reference(conf_text(), TINY, tok)
    adam_steps_against_reference(trainer, ref, params, tok)
    counted = trainer.fetch_counters()
    assert {k.split(".")[1] for k in counted} == {"held", "load", "dropped"}
    assert all(v == 0 for k, v in counted.items() if k.endswith("dropped"))


def _kda_inputs(t, seed=0, gscale=1.0, b=2, h=3, dk=8):
    r = np.random.RandomState(seed)
    q = r.randn(b, t, h, dk).astype(np.float32)
    k = r.randn(b, t, h, dk).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(b, t, h, dk).astype(np.float32)
    g = -gscale * np.abs(r.randn(b, t, h, dk)).astype(np.float32)
    beta = (1 / (1 + np.exp(-r.randn(b, t, h)))).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("t,chunk,gscale", [
    (64, 32, 1.0),       # chunks of two sub-blocks
    (128, 64, 1.0),      # the cell's chunk: four sub-blocks
    (70, 32, 1.0),       # a tail that fills no chunk
    (37, 5, 1.0),        # a chunk that is no multiple of a sub-block
    (64, 16, 0.01),      # hardly any decay
    (128, 64, 5.0),      # e^-320 a chunk
    (128, 64, 30.0),     # e^-1920 a chunk: exp(-G) would overflow
])
def test_chunked_kda_is_the_recurrence(t, chunk, gscale):
    """The chunked form against the recurrence a position at a time:
    the program's own (`kda_recurrent`) and the reference's
    (`delta_rule`), outputs and the gradients of all five inputs.
    1e-4 of the largest entry: the chunked form sums a chunk's terms in
    another order, and at strong decay it rounds differences of sums
    of up to 64 logarithms."""
    args = _kda_inputs(t, gscale=gscale)
    got = ops_kda.kda_chunked(*args, chunk=chunk)
    want = ops_kda.kda_recurrent(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=1e-4 * float(
        jnp.max(jnp.abs(want))))
    ref = jnp.stack([ref_mod.delta_rule(*(a[r] for a in args))
                     for r in range(2)])
    np.testing.assert_allclose(got, ref, atol=1e-4 * float(
        jnp.max(jnp.abs(ref))))
    gc = jax.grad(lambda *a: jnp.sum(jnp.sin(
        ops_kda.kda_chunked(*a, chunk=chunk))), argnums=(0, 1, 2, 3, 4))(*args)
    gr = jax.grad(lambda *a: jnp.sum(jnp.sin(
        ops_kda.kda_recurrent(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(
            jnp.max(jnp.abs(b))))


def test_mla_is_attention_over_materialised_keys():
    """The layer against `naive_attention` over keys and values written
    out per head: the shared 4-wide key part repeated for every head,
    queries 12 wide against values 8 wide, no rotary."""
    m = create_layer("mla", "m")
    for k, v in (("nhead", "2"), ("kv_rank", "16"), ("qk_nope_dim", "8"),
                 ("qk_rope_dim", "4"), ("v_dim", "8"), ("init_sigma", "0.3")):
        m.set_param(k, v)
    shape = (2, 1, 24, 32)
    m.infer_shapes([shape])
    p = m.init_params(jax.random.PRNGKey(3), [shape])
    x = jnp.asarray(np.random.RandomState(1).randn(*shape), jnp.float32)
    (got,) = m.apply(p, [x], train=True)
    xs = x[:, 0]
    a = xs @ p["wkva"]
    c = a[..., :16]
    c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-5) \
        * p["kvnorm"]
    kv = (c @ p["wkvb"]).reshape(2, 24, 2, 16)
    q = (xs @ p["wq"]).reshape(2, 24, 2, 12)
    k = jnp.concatenate([kv[..., :8], jnp.broadcast_to(
        a[:, :, None, 16:], (2, 24, 2, 4))], -1)
    o = ops_attn.naive_attention(*(jnp.moveaxis(z, 2, 1) for z in (
        q, k, jnp.pad(kv[..., 8:], ((0, 0),) * 3 + ((0, 4),)))), causal=True)
    want = jnp.moveaxis(o[..., :8], 1, 2).reshape(2, 24, 16) @ p["wo"]
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-4, atol=1e-5)


def _moe(held=None, nexpert=16, top_k=4, shared=1):
    m = create_layer("moe", "e")
    for k, v in (("nexpert", str(nexpert)), ("moe_top_k", str(top_k)),
                 ("nhidden", "8"), ("moe_glu", "1"),
                 ("moe_score", "sigmoid"), ("moe_scale", "2.446"),
                 ("moe_shared", str(shared)), ("moe_aux", "0"),
                 ("init_sigma", "0.3"), ("moe_bias_sigma", "0.05")):
        m.set_param(k, v)
    if held:
        m.set_param("moe_held", held)
    m.infer_shapes([(2, 1, 12, 16)])
    return m


def test_four_shares_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide: 16 experts in 4
    shares. Each share routes over all 16, computes its own experts'
    part; the four routed parts plus the shared expert counted once are
    the uncut layer (float32: 1e-5)."""
    shape = (2, 1, 12, 16)
    whole = _moe()
    p = whole.init_params(jax.random.PRNGKey(11), [shape])
    x = jnp.asarray(np.random.RandomState(2).randn(*shape), jnp.float32)
    (want,) = whole.apply(p, [x], train=True)
    only_shared = dict(p, w1=p["w1"] * 0, w2=p["w2"] * 0, w3=p["w3"] * 0)
    (shared,) = whole.apply(only_shared, [x], train=True)
    total = shared
    held_sum = 0.0
    for first in (0, 4, 8, 12):
        share = _moe(held=f"{first},4")
        ps = dict(p, **{k: p[k][first:first + 4] for k in ("w1", "w2", "w3")})
        outs, _, stats = share.apply_with_stats(ps, [x], train=True)
        total = total + (outs[0] - shared)
        held_sum += float(stats["held"])
        assert float(stats["dropped"]) == 0
    assert held_sum == 2 * 12 * 4          # every assignment held once
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_routing_with_near_ties_follows_the_reference():
    """Scores that differ in the last digits of float32, and a selection
    bias that decides between them: program and reference choose the
    same experts (both take `top_k` of s + b in float32; of equal
    entries the lower index)."""
    text = ONE_LAYER.format(layer=LAYERS["moe"])
    trainer = build(text, {})
    ref = ref_mod.Reference(text, {})
    params = jax.device_get(trainer.state["params"])
    gate = np.array(params["e1"]["gate"])
    gate[1::2] = gate[0::2] * (1 + 1e-7)   # pairs of all but equal rows
    gate[5] = gate[4]                      # and one pair exactly equal
    params["e1"]["gate"] = gate
    lay = next(l for l in ref.conf_layers if l.type == "moe")
    x = np.random.RandomState(3).randn(40, 32).astype(np.float32)
    moe = trainer.net.layer_objs[lay.index]
    p = {k: jnp.asarray(v) for k, v in params["e1"].items()}
    got = moe.apply(p, [jnp.asarray(x).reshape(1, 1, 40, 32)], train=True)
    want = ref._moe(lay, p, jnp.asarray(x))
    np.testing.assert_allclose(got[0][0, 0], want, rtol=1e-5, atol=1e-5)
    _, chosen, _ = moe._route(p, jnp.asarray(x)[None])
    s = jax.nn.sigmoid(x @ gate.T)
    want_chosen = jax.lax.top_k(s + params["e1"]["sbias"], 2)[1]
    assert np.array_equal(np.asarray(chosen[0]), np.asarray(want_chosen))


def test_ids_over_256_under_bfloat16():
    """A bf16 step cannot hold an id over 256 as a float: ids stay
    integers from the batch to `embed`, whose output is the bf16 row of
    exactly that id. A float batch is refused."""
    over = dict(TINY, dtype="bfloat16", nvocab="20480", batch_size="1")
    trainer = build(conf_text(), over)
    tok = np.array([20479, 257, 256, 255, 4097, 0] + [1] * 34,
                   np.int32).reshape(1, 1, 40, 1)
    got = trainer.extract_feature(batch_of(tok), "x0")
    table = np.asarray(jnp.asarray(jax.device_get(
        trainer.state["params"]["embed"]["wmat"])).astype(jnp.bfloat16)
        .astype(jnp.float32))
    np.testing.assert_array_equal(got[0, 0], table[tok[0, 0, :, 0]])
    assert trainer.stage_batch(batch_of(tok)).data.dtype == jnp.int32
    with pytest.raises(TypeError, match="integers"):
        trainer.stage_batch(batch_of(tok.astype(np.float32)))
    trainer.update(batch_of(tok))           # a whole bf16 step runs
    assert np.isfinite(float(jax.device_get(
        trainer.state["params"]["lm_head"]["wmat"]).sum()))


def test_remat_changes_no_number():
    """`remat = 1` puts one checkpoint round each kda / glu_ffn layer:
    the same losses and parameters, to the last bit on the CPU."""
    tok = tokens()
    runs = []
    for remat in ("0", "1"):
        t = build(conf_text(), dict(TINY, remat=remat))
        assert t.net.remat is bool(int(remat))
        loss, _ = first_step(t, tok)
        t.update(batch_of(tok))
        runs.append((loss, jax.device_get(t.state["params"])))
    assert runs[0][0] == runs[1][0]
    for a, b in zip(jax.tree.leaves(runs[0][1]), jax.tree.leaves(runs[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_remat_checkpoints_the_kinds_that_pay(capsys):
    """Under `remat = 1` the network's list of checkpointed layers holds
    every kda and glu_ffn layer of the conf and no other (an mla or moe
    layer's second forward buys a third of the memory a millisecond:
    docs/global.md); the trainer says so once. Under `remat = 0` the
    list is empty and nothing is said."""
    t = build(conf_text(), dict(TINY, remat="1", silent="0"))
    kinds = {l.type_name for l in t.net.layer_objs}
    assert {"kda", "glu_ffn", "mla", "moe", "rms_norm", "embed",
            "lm_head"} <= kinds
    want = [f"{l.type_name}.{t.net.cfg.layers[i].name}"
            for i, l in enumerate(t.net.layer_objs)
            if l.type_name in ("kda", "glu_ffn")]
    assert len(want) == 5 and t.net.checkpointed == want
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("remat:")]
    assert said == ["remat: 5 of 33 layers checkpointed "
                    "(kda x4, glu_ffn x1)"]
    t = build(conf_text(), dict(TINY, remat="0", silent="0"))
    assert t.net.checkpointed == []
    assert "remat:" not in capsys.readouterr().out


def _step_eqns(trainer, tok):
    """(primitive name, name stack) of every equation of the train
    step's jaxpr, those of inner jaxprs (loops, checkpoints, calls)
    too."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            yield e.primitive.name, str(e.source_info.name_stack)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)

    st = trainer.stage_batch(batch_of(tok))
    return list(walk(trainer._train_step.trace(
        trainer.state, st.data, st.extras, st.labels, st.mask,
        jax.random.PRNGKey(0)).jaxpr.jaxpr))


@pytest.mark.parametrize("remat", ["0", "1"])
def test_remat_runs_the_attention_core_once(remat, monkeypatch):
    """One `mla` layer, the flash kernel in interpret mode: the step
    calls `flash_fwd` once, in the layer's forward, and the backward
    kernels once each on what that call left (q, k, v, o, lse), with
    `remat` as without. A checkpoint round the layer would call it a
    second time under `rematted_computation`."""
    from cxxnet_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_FORCE_INTERPRET", True)
    t = build(ONE_LAYER.format(layer=LAYERS["mla"]),
              {"input_shape": "1,64,1", "remat": remat})
    eqns = _step_eqns(t, tokens(seq=64))
    # the kernels sit in one jitted function a direction (their name
    # stacks start there), called once from each of the layer's scopes
    assert sorted(stack for prim, stack in eqns
                  if prim == "pallas_call") == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    assert sorted(stack for prim, stack in eqns
                  if prim == "jit" and stack.endswith("/scores")) == [
        "jvp(mla.m1)/scores", "transpose(jvp(mla.m1))/scores"]


def test_mla_core_at_192_wide_heads_keeps_a_row_of_statistics(monkeypatch):
    """The flash kernels as the `mla` layer runs them (192-wide heads,
    so half-size tiles; equal head counts, no window), in interpret
    mode against `naive_attention`, forward and the three gradients;
    the custom vjp keeps q, k, v, o and `lse` as (b, h, 1, s), the
    positions last, with no trailing dim for the chip to pad."""
    from cxxnet_tpu.ops import attention as ops_attn
    from cxxnet_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(pa, "BLOCK_Q", 32)
    monkeypatch.setattr(pa, "BLOCK_K", 32)
    r = np.random.RandomState(2)
    q, k, v = (jnp.asarray(r.randn(1, 4, 64, 192), jnp.float32)
               for _ in range(3))
    assert pa._tiles_of(q, 64) == (16, 16)
    out, res = pa._vjp_fwd(q, k, v, True, None, True, 0)
    assert [x.shape for x in res] == 4 * [(1, 4, 64, 192)] + [(1, 4, 1, 64)]
    np.testing.assert_allclose(
        out, ops_attn.naive_attention(q, k, v, causal=True),
        rtol=1e-5, atol=1e-5)
    gk = jax.grad(lambda *a: jnp.sum(jnp.cos(pa.flash_attention(
        *a, True, None, True))), (0, 1, 2))(q, k, v)
    gn = jax.grad(lambda *a: jnp.sum(jnp.cos(ops_attn.naive_attention(
        *a, causal=True))), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gn):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("remat", ["0", "1"])
def test_remat_runs_the_expert_loop_once_a_direction(remat):
    """One `moe` layer: `_dropless_fwd`'s tile loop once in the forward,
    `_dropless_bwd`'s once in the backward, with `remat` as without. A
    checkpoint round the layer would hold a third, outside both
    scopes."""
    t = build(ONE_LAYER.format(layer=LAYERS["moe"]), {"remat": remat})
    loops = sorted(stack for prim, stack in _step_eqns(t, tokens())
                   if prim == "while" and "experts" in stack)
    assert loops == ["jvp(moe.e1)/experts",
                     "transpose(jvp(moe.e1))/experts"]


def test_token_iterator_reads_a_file_and_a_seed(tmp_path):
    from cxxnet_tpu.io import create_iterator
    ids = np.arange(100, dtype="<i4") * 300
    ids.tofile(tmp_path / "ids.bin")
    it = create_iterator([("iter", "tokens"), ("batch_size", "2"),
                          ("input_shape", "1,16,1"),
                          ("path_tokens", str(tmp_path / "ids.bin")),
                          ("iter", "end")])
    it.init()
    it.before_first()
    rows = []
    while it.next():
        b = it.value()
        assert b.data.dtype == np.int32 and b.data.shape == (2, 1, 16, 1)
        rows.append(b.data.reshape(2, 16))
    assert np.array_equal(np.concatenate(rows).reshape(-1), ids[:96])
    seeded = create_iterator([("iter", "tokens"), ("batch_size", "1"),
                              ("input_shape", "1,8,1"), ("nvocab", "50"),
                              ("num_batches", "3"), ("iter", "end")])
    seeded.init()
    seeded.before_first()
    n = 0
    while seeded.next():
        assert seeded.value().data.max() < 50
        n += 1
    assert n == 3


def test_cli_trains_and_predicts_the_example_conf(tmp_path):
    """`python -m cxxnet_tpu.main examples/LongSeq/kimi_linear_5l.conf`
    through the normal path at tiny widths: three steps over the one
    seeded batch with a falling loss, then `task = pred` from the checkpoint writes one
    next-token id a row."""
    over = dict(TINY, batch_size="1", save_model="1",
                model_dir=str(tmp_path), eta="0.01", silent="0")
    # the conf states its widths layer by layer, where a `k=v` argument
    # does not reach: cut them in a copy, as the benchmark's dry run does
    pairs = [(k, v) for k, v in parse_config_string(conf_text())
             if k not in over]
    tiny = tmp_path / "tiny.conf"
    tiny.write_text("\n".join(
        f"{k} = {v}" for k, v in pairs + list(over.items())) + "\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    run = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.main", str(tiny),
         "telemetry_steps=1", f"log_file={tmp_path}/log.jsonl",
         "log_format=json"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    import json
    events = [json.loads(l) for l in open(tmp_path / "log.jsonl")]
    losses = [e["loss"] for e in events if e.get("name") == "train.step"]
    assert len(losses) == 3 and losses[-1] < losses[0], losses
    pred = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.main", str(tiny), "task=pred",
         f"model_in={tmp_path}/0003.model", f"pred={tmp_path}/pred.txt"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert pred.returncode == 0, pred.stderr[-2000:]
    out = [float(l) for l in open(tmp_path / "pred.txt")]
    assert len(out) == 1 and 0 <= out[0] < 64 and out[0] == int(out[0])


def test_importing_the_layers_does_not_import_pallas():
    """`mla` takes the flash kernel where it runs, and imports it there:
    at the top of layers/lm.py the import cost every process 1.3-2 s of
    start-up, which the CNN cells' `setup_s` (bound 10%) showed on the
    chip."""
    code = ("import sys, cxxnet_tpu.nnet.trainer; "
            "print(any('pallas' in m for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.stdout.strip() == "False", out.stdout + out.stderr[-500:]
