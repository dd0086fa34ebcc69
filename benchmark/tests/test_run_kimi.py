"""The Kimi-Linear cell rehearsed on the CPU at its tiny overrides
(`--dry-run`): the result line, `correct` on a sound run, and `correct`
false with the control or a broken mechanism in the program's place.
None of these numbers is a device number."""

import json
import os

import pytest

from benchmark import run as bench

CELL = "kimi_linear_48b_a3b.train_seq8k"
ARGS = ["--workload", CELL, "--seed", "2147483999", "--seconds", "1",
        "--dry-run"]


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
        "in_flight_steps")} == {"seq_len": 8192, "distinct_batches": 2,
                                "compared_steps": 3, "in_flight_steps": 4}
    listed = {m["name"] for m in cell.per_layer}
    assert {"kda_ms", "mla_ms", "moe_ms", "lm_head_ms", "moe_held_load",
            "kda_kernel_ms", "step_mfu", "dispatch_ms.train",
            "device_idle_pct"} <= listed
    # no `gqa` layer here: the tile counters' metrics do not list it
    assert not {"masked_tile_share", "window_tile_share"} & listed
    w = spec.entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "kimi_linear_48b_a3b", "train_seq8k", 1)
    for name in ("kda_ms", "mla_ms", "kda_kernel_ms"):
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
    assert {"loss3", "grad1", "grad1_mla_wq", "grad1_med", "dparam",
            "dparam_med", "compiles_in_window"} == set(limits)
    # the number over the `mla` layer's query projection names its leaf
    assert "compared ok   grad1_mla_wq" in err and \
        "(worst leaf l4_mla.wq)" in err
    assert err.strip().splitlines()[-1] == "correct = True"
    # the counter's reader found the experts' load (a CPU run prints it
    # beside its other non-device numbers, and reports no metric)
    assert rehearsed(err, CELL)["moe_held_load"] >= 1.0


@pytest.mark.parametrize("variant", ["float8_e4m3fn", "no_decay",
                                     "no_routed", "rotary_on"])
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
    over = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    if variant == "rotary_on":
        # a rotary embedding moves the query projection's gradient
        assert "grad1_mla_wq" in over


def test_traffic_must_agree_with_the_confs_positions(driver):
    cell = bench.load_cell(CELL)
    ref = driver.make_reference(cell.cfg, cell.cfg["overrides"])
    with pytest.raises(SystemExit, match="seq_len"):
        driver.prepare(cell.cfg, dict(cell.traffic, seq_len=4096), 1,
                       dict(cell.cfg["overrides"]), ref)


def test_the_group_is_the_latent_attentions_query_projection(driver):
    cell = bench.load_cell(CELL)
    ref = driver.make_reference(cell.cfg, cell.cfg["overrides"])
    assert bench.leaf_groups(cell, ref.net) == {
        "grad1_mla_wq": ("grad1", ["l4_mla.wq"])}


def test_half_of_each_row_repeated_is_not_correct(capsys, driver, monkeypatch):
    """The program fed rows whose second half of positions repeats the
    first; the reference is handed the batches the seed draws."""
    from benchmark.tools.read_limits_tokens import half_rows
    make = driver.make_batches
    monkeypatch.setattr(driver, "make_batches",
                        lambda *a, **k: half_rows(make(*a, **k)))
    reference = driver.reference_readings

    def whole(ref, cfg, traffic, seed, batches):
        rows, seq = batches[0][0].shape
        vocab = next(l for l in ref.net.layers
                     if l.type == "lm_head").out_shape[-1]
        return reference(ref, cfg, traffic, seed,
                         make(seed, len(batches), rows, seq, vocab))

    monkeypatch.setattr(driver, "reference_readings", whole)
    assert bench.main(ARGS) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
