"""The LFM2 configuration: its required work as `flops.py` counts it
from the reference module's net, the conf copy against the committed
example, and its file against the catalog entry it is cut from."""

import json
import os

import pytest

from benchmark import confnet, flops, kernel_work_attention
from benchmark.reference import lfm2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
T = 32768


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b.json")) as f:
        return json.load(f)


def reference(overrides=None):
    cfg = config()
    with open(os.path.join(ROOT, cfg["conf"])) as f:
        return lfm2.Reference(f.read(), overrides or cfg["overrides"])


def test_counts_match_the_hand_count():
    net = reference().net
    hand = config()["hand_count"]
    assert flops.forward_macs_per_image(net) == hand["forward_macs_per_image"]
    assert flops.train_flop_per_image(net) == hand["train_flop_per_image"]
    assert hand["train_flop_per_image"] == 6 * hand["forward_macs_per_image"]


def test_every_product_has_its_data_gradient_and_its_layer():
    ref = reference()
    rows = flops.layer_macs(ref.net)
    assert rows and not any(first for _, _, first in rows)
    kinds = [l.type for l in ref.conf_layers]
    assert [kinds.count(k) for k in ("gconv", "gqa", "glu_ffn", "moe")] == [
        4, 1, 1, 4]
    assert kinds[0] == "embed" and kinds[-1] == "lm_head"
    # one checkpoint wherever one node alone is live: two a published layer
    assert [len(g) for g in ref.groups] == [1] + [3] * 10 + [1, 1]
    names = {l.name for l in ref.conf_layers}
    assert all(name.split("/")[0] in names for name, _, _ in rows)
    macs = {n: m for n, m, _ in rows}
    assert macs["l1_gconv/in"] == T * 2048 * 6144
    assert macs["l5_gconv/out"] == T * 2048 * 2048
    assert macs["l1_ffn/gate_up_down"] == T * 2048 * 3 * 11776
    assert macs["l2_gqa/q"] == macs["l2_gqa/o"] == T * 2048 * 2048
    assert macs["l2_gqa/kv"] == T * 2048 * 2 * 512
    # every causal pair and no other, 2 products of 32 heads x 64
    assert macs["l2_gqa/scores_values"] == T * (T + 1) // 2 * 2 * 32 * 64
    assert macs["l3_moe/router"] == T * 2048 * 64
    assert macs["l3_moe/routed"] == T * 4 * 8 // 64 * 3 * 2048 * 1536
    assert macs["lm_head/logits"] == T * 2048 * 8192
    # what is new to the program is over half of the required work
    new = sum(m for n, m in macs.items() if "gconv" in n or "gqa" in n)
    assert 0.55 < new / sum(macs.values()) < 0.6
    # the kernels' work function counts the same pairs, 7 products
    assert kernel_work_attention.causal_attention_flop(1, T, 32, 64) == \
        7 * macs["l2_gqa/scores_values"]


def test_a_dry_runs_overrides_reach_the_layers_keys():
    cfg = config()
    ref = reference(dict(cfg["overrides"], **cfg["dry_run_overrides"]))
    gqa = next(l for l in ref.conf_layers if l.type == "gqa")
    assert gqa.get("nhead", "") == "8" and gqa.out_shape == (48, 32)
    assert gqa.get("qk_norm", "") == "1"
    assert lfm2.held_of(next(
        l for l in ref.conf_layers if l.type == "moe")) == (0, 4)


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


def test_file_holds_the_published_config():
    """Every value of the catalog's `config` under the same key, the
    reduced keys apart; no width among the reduced; the conf runs the
    published widths and published layers 1-5 of the pattern."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    for key, val in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != val
        else:
            assert cfg[key] == val, key
    c = row["config"]
    ref = reference()
    mixers = [l for l in ref.conf_layers if l.type in ("gconv", "gqa")]
    assert [{"gconv": "conv", "gqa": "full_attention"}[l.type]
            for l in mixers] == c["layer_types"][1:6]
    assert len(mixers) == cfg["num_hidden_layers"] == 5
    # layer 1 is one of the leading dense layers, layers 2-5 have experts
    ffns = [l for l in ref.conf_layers if l.type in ("glu_ffn", "moe")]
    assert [l.type for l in ffns] == ["glu_ffn"] + ["moe"] * 4
    assert c["num_dense_layers"] == 2
    assert int(ffns[0].get("nhidden", "0")) == c["intermediate_size"]
    for l in ref.conf_layers:
        if l.type not in ("embed", "lm_head", "add"):
            assert l.out_shape == (T, c["hidden_size"])
            assert float(l.get("eps", "0")) == c["norm_eps"]
    for g in mixers:
        if g.type == "gconv":
            assert int(g.get("conv_size", "0")) == c["conv_L_cache"]
            continue
        assert int(g.get("nhead", "0")) == c["num_attention_heads"]
        assert int(g.get("nkvhead", "0")) == c["num_key_value_heads"]
        assert int(g.get("head_dim", "0")) * c["num_attention_heads"] == \
            c["hidden_size"]
        assert float(g.get("rope_theta", "0")) == \
            c["rope_parameters"]["rope_theta"]
        assert int(g.get("window", "-1")) == 0
        assert int(g.get("qk_norm", "0")) == 1
    for m in ffns[1:]:
        assert int(m.get("nexpert", "0")) == 64       # the published count
        assert lfm2.held_of(m) == (0, cfg["num_experts"])
        assert int(m.get("moe_top_k", "0")) == c["num_experts_per_tok"]
        assert int(m.get("nhidden", "0")) == c["moe_intermediate_size"]
        assert m.get("moe_score", "") == "sigmoid"
        assert float(m.get("moe_scale", "0")) == c["routed_scaling_factor"]
        assert float(m.get("moe_norm_eps", "0")) == 1e-6
        assert int(m.get("moe_shared", "0")) == 0 and len(m.ins) == 1
    head = ref.conf_layers[-1]
    assert int(head.get("nvocab", "0")) == cfg["vocab_size"] == 65536 // 8
    assert T <= c["max_position_embeddings"]
    # the parameter count the file states is the reference's own leaves
    import jax
    shapes = jax.eval_shape(ref.init, 0)
    count = sum(int(a.size) for d in shapes.values() for a in d.values())
    assert count == cfg["sizes"]["parameters"] == 486062464
