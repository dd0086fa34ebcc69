"""The SmallThinker layers (`gqa` and rotary in layers/lm.py, the window
and grouped-head flash kernels of ops/pallas_attention.py, the two-input
ReLU `moe` of layers/moe.py) against the plain reference
(benchmark/reference/smallthinker.py), on the CPU in float32 at widths
cut to tens, from the example conf itself
(examples/LongSeq/smallthinker_8l.conf) with its keys overridden as the
benchmark's dry run overrides them: 40 positions under a window of 16,
so a sequence is longer than two windows, and where the kernels run (in
interpret mode) their tiles are 8 x 8, so whole tiles lie left of it.

Tolerances as tests/test_kimi_linear.py: the same float32 arithmetic in
another order, so a loss agrees to 1e-5 of itself and a gradient leaf to
2e-4 of its largest entry.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import smallthinker as ref_mod
from cxxnet_tpu.layers import create_layer, lm
from cxxnet_tpu.ops import attention as ops_attn
from cxxnet_tpu.ops import pallas_attention as pa
from cxxnet_tpu.utils.config import parse_config_string
from test_kimi_linear import (ROOT, _step_eqns, adam_steps_against_reference,
                              build, first_step, program_against_reference,
                              tokens)

CONF = os.path.join(ROOT, "examples", "LongSeq", "smallthinker_8l.conf")
TINY = {
    "nhidden": "32", "nhead": "14", "nkvhead": "2", "head_dim": "8",
    "nvocab": "64", "nexpert": "16", "moe_top_k": "3", "moe_held": "0,4",
    "input_shape": "1,40,1", "dtype": "float32", "batch_size": "2",
    "dev": "cpu", "loss_block": "16", "silent": "1", "init_sigma": "0.2",
}


def conf_text() -> str:
    """The example conf, its windows cut to 16 positions (the layers
    without one keep none: an override would reach them too)."""
    with open(CONF) as f:
        return f.read().replace("window = 4096", "window = 16")


@pytest.fixture
def kernels(monkeypatch):
    """The flash kernels in interpret mode, 8 x 8 tiles (a query tile
    is a row statistic's lanes: eight to the lane here, for `_tile_ok`)."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "BLOCK_Q", 8)
    monkeypatch.setattr(pa, "BLOCK_K", 8)
    monkeypatch.setattr(pa, "_LANE", 8)


@pytest.fixture
def small_blocks(monkeypatch):
    """The reference's attention in five blocks of query rows."""
    monkeypatch.setattr(ref_mod, "ATTN_BLOCK", 8)


def _stack_against_reference():
    """Loss and every gradient leaf of the 51 conf layers, then three
    Adam steps (the parameters' change to 5e-3 of its norm, leaf by
    leaf, as test_kimi_linear.py says why)."""
    tok = tokens()
    trainer, ref, params = program_against_reference(
        conf_text(), TINY, tok, ref_mod)
    adam_steps_against_reference(trainer, ref, params, tok)
    return trainer


def test_eight_layer_stack_matches_the_reference_on_the_xla_route(
        small_blocks):
    trainer = _stack_against_reference()
    counted = trainer.fetch_counters()
    assert {k.split(".")[1] for k in counted} == {
        "tiles", "held", "load", "dropped"}
    # the XLA route masks the window and skips nothing, and says so
    assert all(v == 1.0 for k, v in counted.items() if k.endswith(".tiles"))
    assert all(v == 0 for k, v in counted.items() if k.endswith("dropped"))


def test_eight_layer_stack_matches_the_reference_through_the_kernels(
        kernels, small_blocks):
    """The same through the flash kernels: 5 x 5 tiles of 8, a window of
    16, so a band row holds three tiles and every tile left of it is
    never walked; layers 0 and 4 run the full kernels."""
    trainer = _stack_against_reference()
    counted = trainer.fetch_counters()
    # 12 band tiles over the 15 causal ones
    want = {f"l{i}_gqa.tiles": (12 / 15 if i % 4 else 1.0) for i in range(8)}
    got = {k: v for k, v in counted.items() if k.endswith(".tiles")}
    assert got == pytest.approx(want)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("h,hkv,s,window,bq,bk", [
    (7, 1, 64, 20, 8, 8),      # 7:1, whole tiles left of the window
    (14, 2, 48, 12, 8, 16),    # two groups, key tiles wider than query tiles
    (7, 1, 64, 17, 16, 8),     # query tiles wider; a window off the tiling
    (14, 2, 64, 0, 16, 8),     # grouped heads, full causal
    (7, 1, 40, 40, 8, 8),      # a window as long as the sequence
    (2, 1, 40, 100, 8, 8),     # ... and longer
    (4, 4, 64, 24, 8, 8),      # a window without groups
])
def test_window_kernels_are_naive_attention_with_the_same_mask(
        h, hkv, s, window, bq, bk, monkeypatch):
    """Forward and all three gradients, in interpret mode; dk and dv sum
    over the query heads of their group."""
    monkeypatch.setattr(pa, "BLOCK_Q", bq)
    monkeypatch.setattr(pa, "BLOCK_K", bk)
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, h, s, 8), jnp.float32)
    k = jnp.asarray(r.randn(2, hkv, s, 8), jnp.float32)
    v = jnp.asarray(r.randn(2, hkv, s, 8), jnp.float32)

    def kern(q, k, v):
        return pa.flash_attention(q, k, v, True, None, True, window)

    def naive(q, k, v):
        return ops_attn.naive_attention(q, k, v, causal=True, window=window)

    np.testing.assert_allclose(kern(q, k, v), naive(q, k, v),
                               rtol=1e-5, atol=1e-5)
    # what the backward kernels read besides q, k, v, o: a row of
    # positions a head, (b, h, 1, s)
    assert pa._vjp_fwd(q, k, v, True, None, True, window)[1][4].shape == (
        2, h, 1, s)
    np.testing.assert_allclose(
        ops_attn.blockwise_attention(q, k, v, causal=True, window=window,
                                     kv_block=8),
        naive(q, k, v), rtol=1e-5, atol=1e-5)
    gk = jax.grad(lambda *a: jnp.sum(jnp.cos(kern(*a))), (0, 1, 2))(q, k, v)
    gn = jax.grad(lambda *a: jnp.sum(jnp.cos(naive(*a))), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gn):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5,
                                   err_msg=f"d{name}")


def test_a_window_skips_tiles_and_needs_causal_attention():
    q = jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16)
    assert pa._tiles_of(q, 16384) == (1024, 1024)
    # the cell's shape: 5 tiles a band row, 70 of the 136 causal tiles
    assert pa.tile_share(q, 4096) == pytest.approx(70 / 136)
    assert pa.tile_share(q, 0) == 1.0 and pa.tile_share(q, 16384) == 1.0
    assert pa._kv_steps(16, 16, 1024, 1024, 4096) == 5
    assert pa._q_steps(16, 16, 1024, 1024, 4096) == 5
    x = jnp.zeros((1, 2, 16, 8))
    with pytest.raises(ValueError, match="causal"):
        pa.flash_attention(x, x, x, False, None, True, 4)
    with pytest.raises(ValueError, match="causal"):
        ops_attn.naive_attention(x, x, x, window=4)
    with pytest.raises(ValueError, match="key/value heads"):
        pa.flash_attention(jnp.zeros((1, 3, 16, 8)), x, x, True, None, True)


def test_rotary_is_a_complex_rotation():
    """Pair i of a head, entries i and i + d/2, is the complex number
    a + ib turned by exp(i t theta^(-i / (d/2))); the reference's own
    rotary says the same."""
    r = np.random.RandomState(1)
    x = r.randn(2, 3, 12, 8).astype(np.float32)          # b, h, T, d
    theta = 1.5e6
    z = x[..., :4] + 1j * x[..., 4:]
    ang = np.arange(12)[:, None] * theta ** (-np.arange(4) / 4.0)
    z = z * np.exp(1j * ang)
    want = np.concatenate([z.real, z.imag], axis=-1)
    np.testing.assert_allclose(lm.rotary(jnp.asarray(x), theta), want,
                               rtol=1e-5, atol=1e-5)
    ref = ref_mod.rotary(jnp.asarray(np.moveaxis(x[0], 1, 0)), theta)
    np.testing.assert_allclose(np.moveaxis(np.asarray(ref), 0, 1), want[0],
                               rtol=1e-5, atol=1e-5)
    # position 0 is not turned, and a turn keeps a pair's length
    np.testing.assert_allclose(want[:, :, 0], x[:, :, 0], atol=1e-6)
    np.testing.assert_allclose(np.abs(z), np.hypot(x[..., :4], x[..., 4:]),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# the experts
# ---------------------------------------------------------------------------
def _moe(held=None, nexpert=16, top_k=6):
    m = create_layer("moe", "e")
    for k, v in (("nexpert", str(nexpert)), ("moe_top_k", str(top_k)),
                 ("nhidden", "8"), ("moe_glu", "1"), ("moe_act", "relu"),
                 ("moe_score", "softmax"), ("moe_norm_topk", "1"),
                 ("moe_aux", "0"), ("init_sigma", "0.3")):
        m.set_param(k, v)
    if held:
        m.set_param("moe_held", held)
    return m


SHAPE = (2, 1, 12, 16)


def test_two_inputs_of_one_node_are_the_one_input_layer():
    one, two = _moe(), _moe()
    one.infer_shapes([SHAPE])
    two.infer_shapes([SHAPE, SHAPE])
    p = one.init_params(jax.random.PRNGKey(11), [SHAPE])
    p2 = two.init_params(jax.random.PRNGKey(11), [SHAPE, SHAPE])
    assert all(np.array_equal(p[k], p2[k]) for k in p) and set(p) == set(p2)
    x = jnp.asarray(np.random.RandomState(2).randn(*SHAPE), jnp.float32)
    (a,) = one.apply(p, [x], train=True)
    (b,) = two.apply(p, [x, x], train=True)
    assert np.array_equal(a, b)
    # and another node for the router is another routing
    other = jnp.asarray(np.random.RandomState(3).randn(*SHAPE), jnp.float32)
    (c,) = two.apply(p, [x, other], train=True)
    assert not np.allclose(a, c, atol=1e-3)
    with pytest.raises(ValueError, match="one length"):
        _moe().infer_shapes([SHAPE, (2, 1, 11, 16)])


def test_four_shares_add_up_to_the_uncut_reference():
    """The share test of the model-configs guide: 16 experts held as
    0-3, 4-7, 8-11, 12-15. Each share routes over all 16 on the
    router's own input and computes its own experts' part; the four
    parts are what the plain reference gives for the whole layer
    (float32: 1e-5). There is no shared expert to count once."""
    text = ("netconfig=start\n"
            "layer[0->x0] = embed:embed\n  nvocab = 64\n  nhidden = 16\n"
            "layer[x0->a] = rms_norm:n1\n"
            "layer[x0,a->f] = moe:e\n  nexpert = 16\n  moe_top_k = 6\n"
            "  nhidden = 8\n  moe_glu = 1\n  moe_act = relu\n"
            "  moe_score = softmax\n  moe_norm_topk = 1\n  moe_aux = 0\n"
            "layer[f,0->logits] = lm_head:lm_head\n  nvocab = 64\n"
            "netconfig=end\ninput_shape = 1,12,1\nbatch_size = 2\n"
            "updater = adam\n")
    lay = next(l for l in ref_mod.Reference(text, {}).conf_layers
               if l.type == "moe")
    assert ref_mod.held_of(lay) == (0, 16)
    whole = _moe()
    whole.infer_shapes([SHAPE, SHAPE])
    p = whole.init_params(jax.random.PRNGKey(11), [SHAPE, SHAPE])
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(*SHAPE), jnp.float32)
    a = jnp.asarray(r.randn(*SHAPE), jnp.float32)
    ref = ref_mod.Reference(text, {})
    want = np.stack([np.asarray(ref._moe(lay, p, x[i, 0], a[i, 0]))
                     for i in range(2)])
    total, held_sum = 0.0, 0.0
    for first in (0, 4, 8, 12):
        share = _moe(held=f"{first},4")
        share.infer_shapes([SHAPE, SHAPE])
        ps = dict(p, **{k: p[k][first:first + 4] for k in ("w1", "w2", "w3")})
        outs, _, stats = share.apply_with_stats(ps, [x, a], train=True)
        total = total + outs[0]
        held_sum += float(stats["held"])
        assert float(stats["dropped"]) == 0
    assert held_sum == 2 * 12 * 6          # every assignment held once
    np.testing.assert_allclose(total[:, 0], want, rtol=1e-5, atol=1e-5)
    # the weights of a token's six are the softmax over their own logits
    weights, chosen, _ = whole._route(p, a[:, 0])
    logits = np.asarray(a[:, 0]) @ np.asarray(p["gate"]).T
    picked = np.take_along_axis(logits, np.asarray(chosen), axis=-1)
    e = np.exp(picked - picked.max(-1, keepdims=True))
    np.testing.assert_allclose(weights, e / e.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------
def test_remat_checkpoints_the_gqa_layers_and_changes_no_number(capsys):
    tok = tokens()
    runs = []
    for remat in ("0", "1"):
        t = build(conf_text(), dict(TINY, remat=remat, silent="0"))
        said = [l for l in capsys.readouterr().out.splitlines()
                if l.startswith("remat:")]
        if remat == "1":
            assert t.net.checkpointed == [f"gqa.l{i}_gqa" for i in range(8)]
            assert said == ["remat: 8 of 51 layers checkpointed (gqa x8)"]
        else:
            assert t.net.checkpointed == [] and not said
        loss, _ = first_step(t, tok)
        runs.append((loss, jax.device_get(t.state["params"])))
    assert runs[0][0] == runs[1][0]
    for a, b in zip(jax.tree.leaves(runs[0][1]), jax.tree.leaves(runs[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_eight_layers_lower_one_full_and_one_window_set_of_kernels(
        monkeypatch):
    """Every process lowers the step before it can ask the compile cache
    for it. The kernels are called through one jitted function a
    direction, so the step's text, lowered for the TPU without a chip,
    holds a kernel body a direction for the two full layers and one for
    the six window layers (the forward twice: the layer's own and
    `remat`'s second run are two functions), not one a layer."""
    from cxxnet_tpu.io.data import DataBatch
    monkeypatch.setattr(pa, "_backend_ok", lambda: True)
    t = build(conf_text(), dict(TINY, input_shape="1,64,1", head_dim="128",
                                nhead="4", nkvhead="2", remat="1"))
    staged = t.stage_batch(DataBatch(data=np.zeros((2, 1, 64, 1), np.int32),
                                     label=np.zeros((2, 1), np.float32)))
    text = t._train_step.trace(
        t.state, staged.data, staged.extras, staged.labels, staged.mask,
        jax.random.PRNGKey(0)).lower(lowering_platforms=("tpu",)).as_text()
    bodies = {n: len(re.findall(rf'kernel_name = "{n}"', text))
              for n in ("flash_fwd", "flash_dq", "flash_dkv",
                        "flash_win_fwd", "flash_win_dq", "flash_win_dkv")}
    assert bodies == {"flash_fwd": 2, "flash_dq": 1, "flash_dkv": 1,
                      "flash_win_fwd": 2, "flash_win_dq": 1,
                      "flash_win_dkv": 1}
    # the kernels' row statistics: positions last, one row a head
    assert "2x4x1x64xf32" in text and "2x4x64x8xf32" not in text
    # and the scopes a reader of the trace finds the layers' parts by
    stacks = {stack for _, stack in _step_eqns(t, np.zeros(
        (2, 1, 64, 1), np.int32))}
    for scope in ("jvp(gqa.l1_gqa)/proj", "jvp(gqa.l1_gqa)/rope",
                  "jvp(gqa.l1_gqa)/out", "jvp(gqa.l0_gqa)/proj",
                  "jvp(gqa.l1_gqa)/scores", "jvp(gqa.l0_gqa)/scores",
                  # (inside the jitted functions the kernels' own names)
                  "flash_fwd", "flash_win_fwd", "flash_win_dkv"):
        assert scope in stacks, (scope, sorted(
            s for s in stacks if "gqa.l1" in s))
    assert "jvp(gqa.l0_gqa)/rope" not in stacks   # no positional encoding
    # the backward holds the checkpointed layer's second forward
    assert {"transpose(jvp(gqa.l1_gqa))/jvp(gqa.l1_gqa)",
            "rematted_computation/scores"} <= stacks


def test_cli_trains_and_predicts_the_example_conf(tmp_path):
    """`python -m cxxnet_tpu.main examples/LongSeq/smallthinker_8l.conf`
    through the normal path at tiny widths: three steps over the one
    seeded batch with a falling loss, then `task = pred` from the
    checkpoint writes one next-token id a row."""
    over = dict(TINY, batch_size="1", save_model="1",
                model_dir=str(tmp_path), eta="0.01", silent="0")
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
