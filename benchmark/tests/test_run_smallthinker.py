"""The SmallThinker cell rehearsed on the CPU at its tiny overrides
(`--dry-run`), `correct` false with the control or a broken mechanism in
the program's place, and its four readers on a made-up step. None of
these numbers is a device number."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import confnet, run as bench, scope_map as sm
from benchmark.reference import smallthinker

CELL = "smallthinker_21b_a3b.train_seq16k"
ARGS = ["--workload", CELL, "--seed", "2147483999", "--seconds", "1",
        "--dry-run"]
NEW = ("gqa_full_ms", "gqa_window_ms", "flash_gqa_roofline",
       "window_tile_share")


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
        "in_flight_steps")} == {"seq_len": 16384, "distinct_batches": 2,
                                "compared_steps": 3, "in_flight_steps": 4}
    listed = [m["name"] for m in cell.per_layer]
    assert set(NEW) <= set(listed)
    assert {"step_mfu", "dispatch_ms.train", "device_idle_pct"} <= set(listed)
    # the expert and head metrics read this cell's layers too; the Kimi
    # cell's attention metric does not list it
    assert {"moe_ms", "lm_head_ms", "moe_held_load",
            "masked_tile_share"} <= set(listed)
    assert "mla_ms" not in listed
    w = spec.entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "smallthinker_21b_a3b", "train_seq16k", 1)
    for name in NEW:
        m = spec.entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_s"


def test_result_line_and_counters(capsys, rehearsed):
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
    # the counters' readers found the layers' `tiles` and `masked` (the
    # CPU's XLA route masks and skips nothing: 1) and the experts' load
    got = rehearsed(err, CELL)
    assert got["window_tile_share"] == 1.0
    assert got["masked_tile_share"] == 1.0 and got["moe_held_load"] >= 1.0


@pytest.mark.parametrize("variant", ["float8_e4m3fn", "no_window",
                                     "rope_everywhere", "router_after",
                                     "silu_experts", "no_routed"])
def test_a_broken_reference_in_the_programs_place_is_not_correct(
        capsys, driver, monkeypatch, variant):
    """What the limits were set against on the chip, at the dry run's
    size: each reads over a limit."""
    make = driver.make_reference
    prepare = driver.prepare

    def swapped(cfg, traffic, seed, overrides, reference):
        prep = prepare(cfg, traffic, seed, overrides, reference)
        prep.readings = driver.reference_readings(
            make(cfg, overrides, variant), cfg, traffic, seed, prep.batches)
        return prep

    monkeypatch.setattr(driver, "prepare", swapped)
    assert bench.main(ARGS) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def reader(name):
    return bench.load_module(os.path.join(bench.HERE, "layer_metrics",
                                          name + ".py"))


def net_of(cell):
    cfg = bench.load_cell(cell).cfg
    if cfg["reference"]["module"] == "smallthinker":
        return smallthinker.Reference(cfg["conf_text"], cfg["overrides"]).net
    return confnet.build(confnet.parse_pairs(cfg["conf_text"]),
                         cfg["overrides"])


STEPS = 2
# device ns of one step, by (phase, type, key)
ROWS = {("fwd", "gqa", "l0_gqa"): 50e6, ("bwd", "gqa", "l0_gqa"): 150e6,
        ("fwd", "gqa", "l4_gqa"): 52e6, ("bwd", "gqa", "l4_gqa"): 148e6,
        ("fwd", "gqa", "l1_gqa"): 30e6, ("bwd", "gqa", "l1_gqa"): 90e6,
        ("bwd", "gqa", "l7_gqa"): 80e6, ("update", "gqa", "l1_gqa"): 9e6,
        ("fwd", "moe", "l1_moe"): 7e6, ("other", "", ""): 5e6}
KERNEL_MS = {"jvp_flash_fwd_.3": 30.0, "flash_fwd.9": 31.0,
             "flash_dq.2": 40.0, "flash_dkv": 70.0,
             "flash_win_fwd.8": 15.0, "flash_win_dq.1": 20.0,
             "jvp_flash_win_dkv_": 36.0}


def obs(cell, with_trace=True):
    table = sm.Table(STEPS, {k: v * STEPS for k, v in ROWS.items()})
    events = [(n, 1e9 * i + j, ms * 1e6) for i in range(STEPS)
              for j, (n, ms) in enumerate(KERNEL_MS.items())]
    events += [("fusion.77", 5e8, 4e6), ("while.3", 6e8, 9e6)]
    counters = {f"l{i}_gqa.tiles": (70 / 136 if i % 4 else 1.0)
                for i in range(8)}
    counters["l1_moe.load"] = 1.2
    return SimpleNamespace(
        net=net_of(cell), rows=1, device_kind="TPU v5 lite",
        device_events=events if with_trace else [],
        window=SimpleNamespace(steps=STEPS, counters=counters),
        scope_table=table if with_trace else None)


def test_readers_read_the_new_cell():
    o = obs(CELL)
    assert reader("gqa_full_ms").read(o) == pytest.approx(400.0)
    assert reader("gqa_window_ms").read(o) == pytest.approx(200.0)
    assert reader("window_tile_share").read(o) == pytest.approx(70 / 136)
    # the six kernels' events, a step: 242 ms; required: 7 products x 2
    # FLOP x 28 heads x 128 x the pairs seen, two full and six window
    # layers
    t, w = 16384, 4096
    pairs = 2 * (t * (t + 1) // 2) + 6 * (w * (w + 1) // 2 + (t - w) * w)
    want = 100.0 * (14 * pairs * 28 * 128 / 197e12) / 0.242
    got = reader("flash_gqa_roofline").read(o)
    assert got == pytest.approx(want) and 0 < got < 100


def test_readers_are_silent_without_a_trace_or_a_counter():
    o = obs(CELL, with_trace=False)
    o.window.counters = {}
    assert [reader(n).read(o) for n in NEW] == [None] * 4
    # a program whose step has no `gqa` scope and no such kernel (the
    # parent, under this PR's benchmark files)
    o = obs(CELL)
    o.scope_table = sm.Table(STEPS, {("fwd", "moe", "l1_moe"): 7e6})
    o.device_events = [("fusion.77", 5e8, 4e6)]
    o.window.counters = {"l1_moe.load": 1.2}
    assert [reader(n).read(o) for n in NEW] == [None] * 4


@pytest.mark.parametrize("cell", ["alexnet.train_resident",
                                  "kimi_linear_48b_a3b.train_seq8k"])
def test_readers_are_silent_on_a_net_without_gqa_layers(cell):
    if cell.startswith("kimi"):
        from benchmark.reference import kimi_linear
        cfg = bench.load_cell(cell).cfg
        net = kimi_linear.Reference(cfg["conf_text"], cfg["overrides"]).net
    else:
        net = net_of(cell)
    o = obs(CELL)
    o.net = net
    # (the Kimi step runs `flash_fwd` under its `mla` layer: the
    # roofline's work is the `gqa` layers', so it stays silent there)
    assert [reader(n).read(o) for n in NEW] == [None] * 4


def test_masked_tile_share_reads_the_window_counters():
    """The mean over all eight `gqa` layers: a full layer masks 16 of
    its 136 live tiles, a window layer 28 of 70; silent without the
    counters."""
    share = reader("masked_tile_share")
    o = obs(CELL)
    o.window.counters.update({f"l{i}_gqa.masked": (28 / 70 if i % 4 else
                                                   16 / 136)
                              for i in range(8)})
    assert share.read(o) == pytest.approx(
        (2 * 16 / 136 + 6 * 28 / 70) / 8)
    assert share.read(o) == pytest.approx(0.3294, abs=5e-5)
    o.window.counters = {"l1_moe.load": 1.2, "l1_gqa.tiles": 0.5}
    assert share.read(o) is None
