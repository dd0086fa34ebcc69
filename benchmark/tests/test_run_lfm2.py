"""The LFM2 cell rehearsed on the CPU at its tiny overrides
(`--dry-run`) and its three readers on a made-up step
(test_faults_lfm2.py puts the control and the faults in the program's
place). None of these numbers is a device number."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import confnet, run as bench, scope_map as sm

CELL = "lfm2_24b_a2b.train_seq32k"
ARGS = ["--workload", CELL, "--seed", "2147483999", "--seconds", "1",
        "--dry-run"]
NEW = ("gconv_ms", "gqa_d64_ms", "flash_d64_roofline")


def last_line(capsys):
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.fixture()
def driver():
    return bench.load_module(os.path.join(
        bench.HERE, "drivers", "train_tokens.py"))


def test_cell_is_the_one_the_issue_names(spec):
    cell = bench.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "train_tokens"
    assert {k: cell.traffic[k] for k in (
        "seq_len", "distinct_batches", "compared_steps",
        "in_flight_steps")} == {"seq_len": 32768, "distinct_batches": 2,
                                "compared_steps": 3, "in_flight_steps": 4}
    assert cell.cfg["overrides"]["batch_size"] == "1"
    listed = [m["name"] for m in cell.per_layer]
    assert set(NEW) <= set(listed)
    assert {"step_mfu", "dispatch_ms.train", "device_idle_pct"} <= set(listed)
    # the expert and head metrics read this cell's layers too; the
    # SmallThinker cell's attention metrics do not list it
    assert {"moe_ms", "lm_head_ms", "moe_held_load",
            "masked_tile_share"} <= set(listed)
    assert not {"gqa_full_ms", "flash_gqa_roofline"} & set(listed)
    w = spec.entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "lfm2_24b_a2b", "train_seq32k", 1)
    for name in NEW:
        m = spec.entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_s"


def test_result_line_and_counters(capsys, driver, monkeypatch, rehearsed):
    seen = {}
    window = driver.window

    def keeping(*a):
        seen["win"] = window(*a)
        return seen["win"]

    monkeypatch.setattr(driver, "window", keeping)
    assert bench.main(ARGS + ["--trace", "1"]) == 0
    res, err = last_line(capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["metrics"] == {}
    limits = bench.load_cell(CELL).limits
    assert list(res["compared"]) == list(limits)
    assert "compiles_in_window" in limits and "loss3" in limits
    assert err.strip().splitlines()[-1] == "correct = True"
    # what the CPU can read: set-up's records and the counters (the XLA
    # route masks every tile it runs: 1)
    got = rehearsed(err, CELL)
    assert got["masked_tile_share"] == 1.0 and got["moe_held_load"] >= 1.0
    # the counters the driver fetches after the window: the attention
    # layer's tiles and the four expert layers' held / load / dropped
    counters = seen["win"].counters
    assert counters["l2_gqa.tiles"] == 1.0
    assert sorted(k for k in counters if k.endswith(".load")) == [
        f"l{i}_moe.load" for i in (2, 3, 4, 5)]
    assert all(counters[f"l{i}_moe.dropped"] == 0 for i in (2, 3, 4, 5))


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
    mod = importlib.import_module("benchmark.reference." + module)
    return mod.Reference(cfg["conf_text"], cfg["overrides"]).net


STEPS = 2
# device ns of one step, by (phase, type, key)
ROWS = {("fwd", "gconv", "l1_gconv"): 10e6, ("bwd", "gconv", "l1_gconv"): 30e6,
        ("fwd", "gconv", "l3_gconv"): 11e6, ("bwd", "gconv", "l5_gconv"): 29e6,
        ("fwd", "gqa", "l2_gqa"): 120e6, ("bwd", "gqa", "l2_gqa"): 330e6,
        ("update", "gqa", "l2_gqa"): 9e6, ("update", "gconv", "l1_gconv"): 2e6,
        ("fwd", "moe", "l2_moe"): 7e6, ("other", "", ""): 5e6}
KERNEL_MS = {"jvp_flash_fwd_.3": 110.0, "flash_fwd.9": 111.0,
             "flash_dq.2": 90.0, "flash_dkv": 109.0}


def obs(cell, with_trace=True):
    table = sm.Table(STEPS, {k: v * STEPS for k, v in ROWS.items()})
    events = [(n, 1e9 * i + j, ms * 1e6) for i in range(STEPS)
              for j, (n, ms) in enumerate(KERNEL_MS.items())]
    events += [("fusion.77", 5e8, 4e6), ("while.3", 6e8, 9e6)]
    return SimpleNamespace(
        net=net_of(cell), rows=1, device_kind="TPU v5 lite",
        device_events=events if with_trace else [],
        window=SimpleNamespace(steps=STEPS, counters={"l2_moe.load": 1.1}),
        scope_table=table if with_trace else None)


def test_readers_read_the_new_cell():
    o = obs(CELL)
    assert reader("gconv_ms").read(o) == pytest.approx(80.0)
    assert reader("gqa_d64_ms").read(o) == pytest.approx(450.0)
    # the three kernels' events, a step: 420 ms; required: 7 products x
    # 2 FLOP x 32 heads x 64 x the causal pairs of 32,768 positions
    t = 32768
    want = 100.0 * (14 * (t * (t + 1) // 2) * 32 * 64 / 197e12) / 0.420
    got = reader("flash_d64_roofline").read(o)
    assert got == pytest.approx(want) and 0 < got < 100


def test_readers_are_silent_without_a_trace_or_a_scope():
    o = obs(CELL, with_trace=False)
    assert [reader(n).read(o) for n in NEW] == [None] * 3
    # a program whose step has no `gconv` or `gqa` scope and no such
    # kernel (the parent, under this PR's benchmark files)
    o = obs(CELL)
    o.scope_table = sm.Table(STEPS, {("fwd", "moe", "l2_moe"): 7e6})
    o.device_events = [("fusion.77", 5e8, 4e6)]
    assert [reader(n).read(o) for n in NEW] == [None] * 3


@pytest.mark.parametrize("cell", ["alexnet.train_resident",
                                  "kimi_linear_48b_a3b.train_seq8k",
                                  "smallthinker_21b_a3b.train_seq16k"])
def test_readers_are_silent_on_the_other_cells_nets(cell):
    """No `gconv` layer and no 64-wide `gqa` layer there; the Kimi step
    runs `flash_fwd` under its `mla` layer and the SmallThinker step
    under 128-wide `gqa` layers: the roofline's work is the 64-wide
    layers', so it stays silent on both."""
    o = obs(CELL)
    o.net = net_of(cell)
    assert [reader(n).read(o) for n in NEW] == [None] * 3


def test_masked_tile_share_reads_the_window_counters():
    """The forward kernel masks 32 of the 528 live tiles of the one
    `gqa` layer at 32,768 positions (the diagonal's); silent without the
    counter and on a net without `gqa` layers."""
    share = reader("masked_tile_share")
    o = obs(CELL)
    o.window.counters["l2_gqa.masked"] = 32 / 528
    assert share.read(o) == pytest.approx(0.0606, abs=5e-5)
    o.window.counters = {"l2_moe.load": 1.1}
    assert share.read(o) is None
    o.window = SimpleNamespace(steps=STEPS)
    assert share.read(o) is None
    o = obs(CELL)
    o.window.counters["l2_gqa.masked"] = 0.5
    o.net = net_of("kimi_linear_48b_a3b.train_seq8k")
    assert share.read(o) is None
