"""The SmallThinker configuration: its required work as `flops.py`
counts it from the reference module's net, the attention kernels' work
function, the conf copy against the committed example, and its file
against the catalog entry it is cut from."""

import json
import os

import pytest

from benchmark import confnet, flops, kernel_work_attention
from benchmark.reference import smallthinker

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
T = 16384


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        return json.load(f)


def reference(overrides=None):
    cfg = config()
    with open(os.path.join(ROOT, cfg["conf"])) as f:
        return smallthinker.Reference(f.read(), overrides or cfg["overrides"])


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
    assert kinds.count("gqa") == 8 and kinds.count("moe") == 8
    assert kinds[0] == "embed" and kinds[-1] == "lm_head"
    assert [len(g) for g in ref.groups] == [1] + [6] * 8 + [1, 1]
    names = {l.name for l in ref.conf_layers}
    assert all(name.split("/")[0] in names for name, _, _ in rows)
    macs = {n: m for n, m, _ in rows}
    assert macs["l0_gqa/q"] == T * 2560 * 3584
    assert macs["l0_gqa/kv"] == T * 2560 * 2 * 512
    # a full layer: every causal pair; a window layer: 4,096 keys a
    # query once the window is full, 3,584.1 on the mean
    assert macs["l0_gqa/scores_values"] == T * (T + 1) // 2 * 2 * 28 * 128
    assert macs["l1_gqa/scores_values"] == (
        4096 * 4097 // 2 + (T - 4096) * 4096) * 2 * 28 * 128
    assert macs["l4_gqa/scores_values"] == macs["l0_gqa/scores_values"]
    assert macs["l1_gqa/scores_values"] / macs["l0_gqa/scores_values"] == \
        pytest.approx(0.4375, abs=2e-4)
    assert macs["l2_moe/router"] == T * 2560 * 64
    assert macs["l2_moe/routed"] == T * 6 * 8 // 64 * 3 * 2560 * 768
    assert macs["lm_head/logits"] == T * 2560 * 18992


@pytest.mark.parametrize("t,window,pairs", [
    (8, 0, 36), (8, 3, 1 + 2 + 3 * 6), (8, 8, 36), (8, 20, 36), (1, 4, 1)])
def test_seen_pairs(t, window, pairs):
    assert kernel_work_attention.seen_pairs(t, window) == pairs
    # against the mask itself
    seen = sum(1 for q in range(t) for k in range(t)
               if k <= q and (not window or q - k < window))
    assert seen == pairs


def test_attention_kernels_required_flop():
    """Two products forward, five backward, 2 FLOP a multiply-add, the
    pairs a query sees; the reference's scores_values row is the
    forward's share of the same count."""
    got = kernel_work_attention.causal_attention_flop(1, T, 28, 128, 4096)
    macs = {n: m for n, m, _ in flops.layer_macs(reference().net)}
    assert got == 7 * macs["l1_gqa/scores_values"]
    assert kernel_work_attention.causal_attention_flop(3, 8, 2, 4) == \
        2 * 7 * 3 * 36 * 2 * 4


def test_a_dry_runs_overrides_reach_the_layers_keys():
    cfg = config()
    ref = reference(dict(cfg["overrides"], **cfg["dry_run_overrides"]))
    gqa = next(l for l in ref.conf_layers if l.type == "gqa")
    assert gqa.get("nhead", "") == "14" and gqa.out_shape == (48, 32)
    assert gqa.get("window", "") == "16"
    assert smallthinker.held_of(next(
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
    published widths and the published layer pattern."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    for key, val in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != val
        else:
            assert cfg[key] == val, key
    c = row["config"]
    ref = reference()
    gqas = [l for l in ref.conf_layers if l.type == "gqa"]
    moes = [l for l in ref.conf_layers if l.type == "moe"]
    assert len(gqas) == len(moes) == cfg["num_hidden_layers"] == 8
    for i, g in enumerate(gqas):
        assert g.out_shape == (c["max_position_embeddings"], c["hidden_size"])
        assert int(g.get("nhead", "0")) == c["num_attention_heads"]
        assert int(g.get("nkvhead", "0")) == c["num_key_value_heads"]
        assert int(g.get("head_dim", "0")) == c["head_dim"]
        assert int(g.get("window", "-1")) == \
            c["sliding_window_size"] * c["sliding_window_layout"][i]
        assert float(g.get("rope_theta", "-1")) == \
            c["rope_theta"] * c["rope_layout"][i]
        assert float(g.get("eps", "0")) == c["rms_norm_eps"]
    for m in moes:
        assert int(m.get("nexpert", "0")) == 64       # the published count
        assert smallthinker.held_of(m) == (0, cfg["moe_num_primary_experts"])
        assert int(m.get("moe_top_k", "0")) == \
            c["moe_num_active_primary_experts"]
        assert int(m.get("nhidden", "0")) == c["moe_ffn_hidden_size"]
        assert m.get("moe_act", "") == "relu"
        assert m.get("moe_score", "") == "softmax"
        assert int(m.get("moe_norm_topk", "0")) == int(c["norm_topk_prob"])
        assert len(m.ins) == 2 and m.ins[1].endswith("_a")   # reads `a`
    head = ref.conf_layers[-1]
    assert int(head.get("nvocab", "0")) == cfg["vocab_size"] == 151936 // 8
    assert cfg["sizes"]["parameters"] == 643852800
