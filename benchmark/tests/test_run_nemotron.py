"""The Nemotron-H cell: its entries by name, its configuration against
the catalog row it is cut from, its required work against the hand
count, the cell rehearsed on the CPU at its tiny overrides (`--dry-run`:
the whole conf through the trainer against the plain reference), and its
three readers on a made-up step. None of these numbers is a device
number."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import confnet, flops, run as bench, scope_map as sm
from benchmark import scope_sub
from benchmark.reference import nemotron_h

CELL = "nemotron_3_super_120b_a12b.train_seq8k"
CONFIG = "nemotron_3_super_120b_a12b"
NEW = ("mamba_ms", "ssd_ms", "mtp_ms")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the published catalog row the configuration is cut from
CATALOG_ROW = os.path.join(ROOT, "benchmark", "tests", "fixtures",
                           CONFIG + ".catalog.json")
T = 8192
DRY_RUN_LINE = "dry run on cpu: these are not device numbers: "


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def reference(overrides=None):
    cfg = config()
    with open(os.path.join(ROOT, cfg["conf"])) as f:
        return nemotron_h.Reference(f.read(), overrides or cfg["overrides"])


def test_cell_is_the_one_the_issue_names(spec):
    cell = bench.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "train_tokens"
    assert {k: cell.traffic[k] for k in (
        "seq_len", "distinct_batches", "compared_steps",
        "in_flight_steps")} == {"seq_len": 8192, "distinct_batches": 2,
                                "compared_steps": 3, "in_flight_steps": 4}
    assert cell.cfg["overrides"]["batch_size"] == "1"
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"step_mfu", "dispatch_ms.train",
                       "device_idle_pct"} <= listed
    # the older metrics' lists wait for a `benchmark` PR
    assert not {"moe_ms", "lm_head_ms", "moe_held_load",
                "setup_traces"} & listed
    w = spec.entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "train_seq8k", 1)
    c = spec.entry("configs", CONFIG)
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    assert c["reduced"] == config()["reduced"]
    for name in NEW:
        m = spec.entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_s"
        assert m["layer"] == "layers / ops"


def test_file_holds_the_published_config():
    """Every value of the catalog's `config` under the same key, the
    reduced keys apart; no width among the reduced (the shared expert's
    5,376 columns stay the published number: the chip's 672 are a share
    the deployment states); the conf runs the published widths and
    layers 0-10 of the pattern, and the MTP depth's pattern."""
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    assert row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
    cfg = config()
    assert cfg["source"] == row["source_url"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    for key, val in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != val
        else:
            assert cfg[key] == val, key
    assert not any(w in k for k in cfg["reduced"] for w in (
        "intermediate", "hidden_size", "latent", "state", "_dim", "_rank",
        "expand", "per_tok"))
    c = row["config"]
    ref = reference()
    letters = {"mamba2": "M", "moe": "E", "gqa": "*"}
    trunk = [l for l in ref.conf_layers
             if l.type in letters and not l.name.startswith("mtp")]
    mtp = [l for l in ref.conf_layers
           if l.type in letters and l.name.startswith("mtp")]
    assert "".join(letters[l.type] for l in trunk) == \
        c["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    assert "".join(letters[l.type] for l in mtp) == \
        c["mtp_hybrid_override_pattern"]
    for l in ref.conf_layers:
        if l.type not in ("embed", "lm_head", "share", "add"):
            assert l.out_shape == (T, c["hidden_size"])
            assert float(l.get("eps", "0")) == c["layer_norm_epsilon"]
        if l.type == "mamba2":
            assert int(l.get("head_dim", "0")) == c["mamba_head_dim"]
            assert int(l.get("ssm_state", "0")) == c["ssm_state_size"]
            assert int(l.get("conv_size", "0")) == c["conv_kernel"]
            assert int(l.get("ssd_chunk", "0")) == c["chunk_size"]
            assert int(l.get("nhead", "0")) == cfg["mamba_num_heads"]
            assert int(l.get("ngroups", "0")) == cfg["n_groups"]
            # the share's group of the gated norm is the published one
            assert int(l.get("nhead", "0")) * int(l.get("head_dim", "0")) \
                // int(l.get("ngroups", "0")) == c["mamba_num_heads"] * \
                c["mamba_head_dim"] // c["n_groups"]
        if l.type == "gqa":
            assert int(l.get("head_dim", "0")) == c["head_dim"]
            assert int(l.get("nhead", "0")) == cfg["num_attention_heads"]
            assert int(l.get("nkvhead", "0")) == cfg["num_key_value_heads"]
            assert float(l.get("rope_theta", "-1")) == 0    # NoPE
            assert int(l.get("window", "-1")) == 0
        if l.type == "moe":
            assert int(l.get("nexpert", "0")) == c["n_routed_experts"]
            assert nemotron_h.held_of(l) == (0, cfg["n_routed_experts"])
            assert int(l.get("moe_top_k", "0")) == c["num_experts_per_tok"]
            assert int(l.get("nhidden", "0")) == c["moe_intermediate_size"]
            assert int(l.get("moe_latent", "0")) == c["moe_latent_size"]
            assert int(l.get("moe_shared_width", "0")) * 8 == \
                c["moe_shared_expert_intermediate_size"]
            assert l.get("moe_act", "") == c["mlp_hidden_act"] == "relu2"
            assert float(l.get("moe_scale", "0")) == \
                c["routed_scaling_factor"]
    heads = [l for l in ref.conf_layers if l.type in ("lm_head", "share")]
    assert [int(h.get("target_offset", "1")) for h in heads] == [1, 2]
    assert int(heads[0].get("nvocab", "0")) == cfg["vocab_size"] == \
        c["vocab_size"] // 8
    # the parameter count the file states is the reference's own leaves
    import jax
    shapes = jax.eval_shape(ref.init, 0)
    count = sum(int(a.size) for d in shapes.values() for a in d.values())
    assert count == cfg["sizes"]["parameters"] == 607038960


def test_counts_match_the_hand_count():
    ref = reference()
    net = ref.net
    hand = config()["hand_count"]
    assert flops.forward_macs_per_image(net) == hand["forward_macs_per_image"]
    assert flops.train_flop_per_image(net) == hand["train_flop_per_image"]
    rows = flops.layer_macs(net)
    assert rows and not any(first for _, _, first in rows)
    macs = {n: m for n, m, _ in rows}
    assert macs["l0_mamba/in"] == T * 4096 * (1024 + 1280 + 16)
    assert macs["l0_mamba/ssd"] == T * (64 * 128 + 16 * 64 * 64
                                        + 2 * 16 * 64 * 128)
    assert macs["l1_moe/routed"] == T * 22 * 8 // 512 * 1024 * 2 * 2688
    assert macs["l1_moe/shared"] == T * 4096 * 2 * 672
    assert macs["l7_gqa/scores_values"] == T * (T + 1) // 2 * 2 * 4 * 128
    assert macs["mtp/eh"] == T * 8192 * 4096
    assert macs["mtp_head/logits"] == macs["lm_head/logits"] == \
        T * 4096 * 16384


def test_conf_copy_is_the_committed_conf():
    cfg = config()
    with open(os.path.join(ROOT, cfg["conf"])) as f:
        copy = confnet.parse_pairs(f.read())
    with open(os.path.join(ROOT, cfg["copied_from"])) as f:
        original = confnet.parse_pairs(f.read())
    kept, skip = [], False
    for k, v in original:
        if k in ("data", "pred"):
            skip = True
        if not skip:
            kept.append((k, v))
        if skip and k == "iter" and v == "end":
            skip = False
    assert copy == kept


def test_result_line_and_counters(capsys, monkeypatch):
    """The whole conf at the tiny overrides, through the trainer, against
    the plain reference: `correct`, every compared number, the counters
    the driver fetches after the window (the six expert layers' held /
    load / dropped, the MTP head's own loss)."""
    driver = bench.load_module(os.path.join(
        bench.HERE, "drivers", "train_tokens.py"))
    seen = {}
    window = driver.window

    def keeping(*a):
        seen["win"] = window(*a)
        return seen["win"]

    monkeypatch.setattr(driver, "window", keeping)
    args = ["--workload", CELL, "--seed", "2147483999", "--seconds", "1",
            "--dry-run", "--trace", "1"]
    assert bench.main(args) == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert list(res["compared"]) == list(bench.load_cell(CELL).limits)
    assert out.err.strip().splitlines()[-1] == "correct = True"
    # what the CPU can read: no device trace, so none of the new three
    # (the set-up metrics' lists do not name this cell yet)
    line = next(l for l in out.err.splitlines() if DRY_RUN_LINE in l)
    read = json.loads(line.split(DRY_RUN_LINE, 1)[1])
    assert set(read) <= {m["name"] for m in bench.load_cell(CELL).per_layer}
    assert not set(NEW) & set(read)
    counters = seen["win"].counters
    assert sorted(k for k in counters if k.endswith(".load")) == sorted(
        f"{k}.load" for k in ("l1_moe", "l3_moe", "l5_moe", "l8_moe",
                              "l10_moe", "mtp2_moe"))
    assert all(v == 0 for k, v in counters.items() if k.endswith("dropped"))
    assert counters["mtp_head.loss"] > 0 and "lm_head.loss" not in counters


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def reader(name):
    return bench.load_module(os.path.join(bench.HERE, "layer_metrics",
                                          name + ".py"))


def net_of(cell):
    cfg = bench.load_cell(cell).cfg
    module = cfg["reference"]["module"]
    if module == "cnn":
        return confnet.build(confnet.parse_pairs(cfg["conf_text"]),
                             cfg["overrides"])
    import importlib
    mod = importlib.import_module("benchmark.reference." + module)
    return mod.Reference(cfg["conf_text"], cfg["overrides"]).net


STEPS = 2
# device ns of one step, by (phase, type, key)
ROWS = {("fwd", "mamba2", "l0_mamba"): 10e6, ("bwd", "mamba2", "l0_mamba"): 20e6,
        ("bwd", "mamba2", "l9_mamba"): 21e6, ("update", "mamba2", "l0_mamba"): 3e6,
        ("fwd", "mtp", "mtp"): 4e6, ("bwd", "gqa", "mtp1_gqa"): 6e6,
        ("fwd", "lm_head", "mtp_head"): 9e6, ("bwd", "moe", "mtp2_moe"): 5e6,
        ("fwd", "lm_head", "lm_head"): 9e6, ("fwd", "moe", "l1_moe"): 7e6,
        ("other", "", ""): 5e6}
SUBS = {("fwd", "mamba2", "l0_mamba", "ssd"): 4e6,
        ("bwd", "mamba2", "l0_mamba", "ssd"): 7e6,
        ("bwd", "mamba2", "l9_mamba", "ssd"): 6e6,
        ("fwd", "mamba2", "l0_mamba", "conv"): 2e6,
        ("fwd", "gconv", "l0_mamba", "ssd"): 9e6}


def obs(cell, with_trace=True):
    return SimpleNamespace(
        net=net_of(cell), rows=1, device_kind="TPU v5 lite",
        device_events=[("fusion.1", 0, 1e6)] if with_trace else [],
        window=SimpleNamespace(steps=STEPS, counters={}),
        scope_table=(sm.Table(STEPS, {k: v * STEPS for k, v in ROWS.items()})
                     if with_trace else None),
        sub_table=({k: v * STEPS for k, v in SUBS.items()}
                   if with_trace else None))


def test_readers_read_the_new_cell():
    o = obs(CELL)
    assert reader("mamba_ms").read(o) == pytest.approx(51.0)
    assert reader("ssd_ms").read(o) == pytest.approx(17.0)
    # every `mtp*` key of every type, the head's second pass among them
    assert reader("mtp_ms").read(o) == pytest.approx(24.0)


def test_readers_are_silent_without_a_trace_or_a_scope():
    o = obs(CELL, with_trace=False)
    assert [reader(n).read(o) for n in NEW] == [None] * 3
    # a program whose step has no such scope (the parent, under this
    # PR's benchmark files)
    o = obs(CELL)
    o.scope_table = sm.Table(STEPS, {("fwd", "moe", "l1_moe"): 7e6})
    o.sub_table = {("fwd", "moe", "l1_moe", "experts"): 7e6}
    assert [reader(n).read(o) for n in NEW] == [None] * 3


@pytest.mark.parametrize("cell", ["alexnet.train_resident",
                                  "kimi_linear_48b_a3b.train_seq8k",
                                  "lfm2_24b_a2b.train_seq32k"])
def test_readers_are_silent_on_the_other_cells_nets(cell):
    o = obs(CELL)
    o.net = net_of(cell)
    assert [reader(n).read(o) for n in NEW] == [None] * 3


def test_sub_scopes_follow_the_layer_through_checkpoints_and_fusions():
    """The component after a layer's, past what JAX puts between
    (`checkpoint`, `jit(...)`); a fusion takes its contraction's."""
    of = scope_sub.sub_of
    assert of("jit(train_step)/jvp(mamba2.l0_mamba)/ssd/intra/exp") == (
        "fwd", "mamba2", "l0_mamba", "ssd")
    assert of("jit(train_step)/transpose(jvp(mamba2.l0_mamba))/"
              "jvp(mamba2.l0_mamba)/checkpoint/ssd/state/mul") == (
        "bwd", "mamba2", "l0_mamba", "ssd")
    assert of("jit(train_step)/jvp(mamba2.l0_mamba)/conv/jit(silu)/mul") \
        == ("fwd", "mamba2", "l0_mamba", "conv")
    assert of("jit(train_step)/jvp(rms_norm.l0_norm)/mul") == (
        "fwd", "rms_norm", "l0_norm", "")
    assert of("jit(train_step)/update/l0_mamba/sub")[0] == "update"
    text = """HloModule m

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %e = f32[4]{0} exponential(f32[4]{0} %p), metadata={op_name="jit(train_step)/jvp(mamba2.l0_mamba)/norm/exp"}
  ROOT %d = f32[4]{0} dot(f32[4]{0} %e, f32[4]{0} %p), metadata={op_name="jit(train_step)/jvp(mamba2.l0_mamba)/ssd/intra/dot_general"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %fusion.2 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/jvp(mamba2.l0_mamba)/norm/add"}
}
"""
    assert scope_sub.subs_of(text)["fusion.2"] == (
        "fwd", "mamba2", "l0_mamba", "ssd")
