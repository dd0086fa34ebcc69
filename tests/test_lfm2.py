"""The LFM2 stack (`gconv` and QK-normed `gqa` in layers/lm.py, the
sigmoid router of layers/moe.py with `moe_norm_eps`) against the plain
reference (benchmark/reference/lfm2.py), on the CPU in float32 at widths
cut to tens, from the example conf itself
(examples/LongSeq/lfm2_5l.conf) with its keys overridden as the
benchmark's dry run overrides them; the step's checkpoints and scopes;
the conf through the CLI. The layers one at a time, and the stack through
the flash kernels, are in tests/test_lfm2_layers.py, so that `--dist
loadfile` spreads the two.

Tolerances as tests/test_kimi_linear.py: the same float32 arithmetic in
another order, so a loss agrees to 1e-5 of itself and a gradient leaf to
2e-4 of its largest entry.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from benchmark.reference import lfm2 as ref_mod
from benchmark.reference import smallthinker as attn_mod
from cxxnet_tpu.ops import pallas_attention as pa
from cxxnet_tpu.utils.config import parse_config_string
from test_kimi_linear import (ROOT, _step_eqns, adam_steps_against_reference,
                              build, first_step, program_against_reference,
                              tokens)

CONF = os.path.join(ROOT, "examples", "LongSeq", "lfm2_5l.conf")
TINY = {
    "nhidden": "32", "nhead": "8", "nkvhead": "2", "head_dim": "8",
    "nvocab": "64", "nexpert": "16", "moe_top_k": "2", "moe_held": "0,4",
    "input_shape": "1,40,1", "dtype": "float32", "batch_size": "2",
    "dev": "cpu", "loss_block": "16", "silent": "1", "init_sigma": "0.2",
    "moe_bias_sigma": "0.05",
}


def conf_text() -> str:
    with open(CONF) as f:
        return f.read()


@pytest.fixture
def small_blocks(monkeypatch):
    """The reference's attention in five blocks of query rows, its dense
    feed-forward in five blocks of positions."""
    monkeypatch.setattr(attn_mod, "ATTN_BLOCK", 8)
    monkeypatch.setattr(ref_mod, "FFN_BLOCK", 8)


def _stack_against_reference():
    """Start, logits, loss and every gradient leaf of the 33 conf
    layers, then three Adam steps (the parameters' change to 5e-3 of its
    norm, leaf by leaf, as test_kimi_linear.py says why)."""
    tok = tokens()
    trainer, ref, params = program_against_reference(
        conf_text(), TINY, tok, ref_mod)
    adam_steps_against_reference(trainer, ref, params, tok)
    return trainer, ref


def test_five_layer_stack_matches_the_reference_on_the_xla_route(
        small_blocks):
    trainer, ref = _stack_against_reference()
    kinds = [l.type for l in ref.conf_layers]
    assert (kinds.count("gconv"), kinds.count("gqa"), kinds.count("glu_ffn"),
            kinds.count("moe")) == (4, 1, 1, 4)
    # the selection bias takes no step, the two head norms do
    assert "sbias" not in ref.hyper()["l2_moe"]
    assert {"qnorm", "knorm"} <= set(ref.hyper()["l2_gqa"])
    counted = trainer.fetch_counters()
    assert {k.split(".")[1] for k in counted} == {
        "tiles", "masked", "fused_bwd", "held", "load", "dropped"}
    assert counted["l2_gqa.tiles"] == counted["l2_gqa.masked"] == 1.0
    assert counted["l2_gqa.fused_bwd"] == 0.0
    assert all(v == 0 for k, v in counted.items() if k.endswith("dropped"))


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------
def test_remat_checkpoints_the_mixers_and_the_dense_layer(capsys):
    tok = tokens()
    runs = []
    for remat in ("0", "1"):
        t = build(conf_text(), dict(TINY, remat=remat, silent="0"))
        said = [l for l in capsys.readouterr().out.splitlines()
                if l.startswith("remat:")]
        if remat == "1":
            assert t.net.checkpointed == [
                "gconv.l1_gconv", "glu_ffn.l1_ffn", "gqa.l2_gqa",
                "gconv.l3_gconv", "gconv.l4_gconv", "gconv.l5_gconv"]
            assert said == ["remat: 6 of 33 layers checkpointed "
                            "(gconv x4, glu_ffn x1, gqa x1)"]
        else:
            assert t.net.checkpointed == [] and not said
        loss, _ = first_step(t, tok)
        runs.append((loss, jax.device_get(t.state["params"])))
    assert runs[0][0] == runs[1][0]
    for a, b in zip(jax.tree.leaves(runs[0][1]), jax.tree.leaves(runs[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_the_step_carries_the_scopes_a_reader_finds_the_layers_by(
        monkeypatch):
    """docs/OBSERVABILITY.md: `gconv.<key>` with `proj`, `gate`, `conv`,
    `out`; `gqa.<key>` with `qknorm` between `proj` and `rope`; the
    kernels' own names inside `scores`."""
    monkeypatch.setattr(pa, "_backend_ok", lambda: True)
    t = build(conf_text(), dict(TINY, input_shape="1,64,1", head_dim="64",
                                nhead="4", nkvhead="1", remat="1"))
    stacks = {stack for _, stack in _step_eqns(t, np.zeros(
        (2, 1, 64, 1), np.int32))}
    for scope in ("jvp(gconv.l1_gconv)/proj", "jvp(gconv.l1_gconv)/gate",
                  "jvp(gconv.l1_gconv)/conv", "jvp(gconv.l1_gconv)/out",
                  "jvp(gconv.l5_gconv)/conv", "jvp(gqa.l2_gqa)/proj",
                  "jvp(gqa.l2_gqa)/qknorm", "jvp(gqa.l2_gqa)/rope",
                  "jvp(gqa.l2_gqa)/scores", "jvp(gqa.l2_gqa)/out",
                  "flash_fwd", "flash_dqkv",
                  "jvp(moe.l2_moe)/route", "jvp(glu_ffn.l1_ffn)"):
        # (an einsum puts its own name under the scope it runs in)
        assert any(s.startswith(scope) for s in stacks), (scope, sorted(
            s for s in stacks if "gconv.l1" in s or "gqa" in s))
    # the backward holds a checkpointed mixer's second forward
    assert "transpose(jvp(gconv.l3_gconv))/jvp(gconv.l3_gconv)" in stacks


def test_cli_trains_and_predicts_the_example_conf(tmp_path):
    """`python -m cxxnet_tpu.main examples/LongSeq/lfm2_5l.conf` through
    the normal path at tiny widths: three steps over the one seeded
    batch with a falling loss, then `task = pred` from the checkpoint
    writes one next-token id a row."""
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
