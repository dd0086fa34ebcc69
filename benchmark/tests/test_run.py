"""A whole run, rehearsed on the CPU at the configuration's tiny
overrides (`--dry-run`): the result line, `correct` on a sound run, and
`correct` false with the timed path broken underneath or the control in
the program's place. None of these numbers is a device number."""

import json
import os

import numpy as np
import pytest

from benchmark import run as bench
from benchmark.tools import read_limits

CELLS = ["alexnet.train_resident", "resnet18.train_resident"]


def args_for(cell):
    return ["--workload", cell, "--seed", "2147483999", "--seconds", "1",
            "--dry-run"]


def last_line(capsys):
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.fixture()
def driver():
    return bench.load_module(os.path.join(
        bench.HERE, "drivers", "train_resident.py"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_the_one_the_issue_names(spec, cell):
    w = spec.entry("workloads", cell)
    assert (w["config"], w["traffic"], w["chips"]) == (
        cell.split(".")[0], "train_resident", 1)
    loaded = bench.load_cell(cell)
    assert loaded.traffic["driver"] == "train_resident"
    listed = {m["name"] for m in loaded.per_layer}
    assert {"fwd_ms", "bwd_ms", "update_ms", "conv_bwd_ms", "scoped_pct",
            "step_mfu", "dispatch_ms.train", "device_idle_pct"} <= listed
    # the token cells' metrics do not list it
    assert not {"moe_ms", "masked_tile_share", "gqa_d64_ms"} & listed


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_has_the_contracts_keys(capsys, cell, rehearsed):
    assert bench.main(args_for(cell) + ["--trace", "1"]) == 0
    res, err = last_line(capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["metrics"] == {}          # a CPU run reports no metric
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes", "busy_s", "window_s"}
    limits = bench.load_cell(cell).limits
    assert list(res["compared"]) == list(limits)
    assert {"loss3", "grad1", "dparam", "compiles_in_window"} <= set(limits)
    tail = err.strip().splitlines()[-len(limits) - 1:]
    assert tail[-1] == "correct = True"
    assert all(l.startswith("compared ok") for l in tail[:-1])
    rehearsed(err, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, driver, monkeypatch, cell):
    build = driver.build_trainer

    def broken(*args):
        trainer = build(*args)
        step = trainer._train_step

        def unchanged(state, *rest):
            import jax
            keep = jax.tree.map(lambda a: a.copy(), state)
            _, loss = step(state, *rest)
            return keep, loss

        trainer._train_step = unchanged
        return trainer

    monkeypatch.setattr(driver, "build_trainer", broken)
    assert bench.main(args_for(cell)) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
    assert res["compared"]["dparam"]["value"] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(
        capsys, driver, monkeypatch, cell):
    make = driver.make_batches
    monkeypatch.setattr(
        driver, "make_batches",
        lambda *a, **k: read_limits.half_batch(make(*a, **k)))
    # the reference is handed the batches the run was given, not the
    # halved ones: it draws its own
    reference = driver.reference_readings

    def whole(ref, cfg, traffic, seed, batches):
        rows, shape = batches[0][0].shape[0], batches[0][0].shape[1:]
        return reference(ref, cfg, traffic, seed,
                         make(seed, len(batches), rows, shape, 1000))

    monkeypatch.setattr(driver, "reference_readings", whole)
    assert bench.main(args_for(cell)) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False


@pytest.mark.parametrize("name", CELLS)
def test_control_in_a_lower_precision_is_not_correct(driver, name):
    """The reference computed in float8 e4m3, put in the program's
    place, fails one of the cell's limits."""
    cell = bench.load_cell(name)
    overrides = dict(cell.cfg["overrides"], **cell.cfg["dry_run_overrides"])
    ref = driver.make_reference(cell.cfg, overrides)
    control = driver.make_reference(cell.cfg, overrides, "float8_e4m3fn")
    rows = int(overrides["batch_size"])
    batches = driver.make_batches(7, 2, rows, ref.net.input_shape, 1000)
    want = driver.reference_readings(ref, cell.cfg, cell.traffic, 7, batches)
    got = driver.reference_readings(control, cell.cfg, cell.traffic, 7,
                                    batches)
    numbers, _ = driver.compare(got, want)
    assert any(numbers[k] > limit for k, limit in cell.limits.items()
               if k in numbers)
    same, _ = driver.compare(want, want)
    assert all(v == 0 for v in same.values())


def test_a_group_number_is_the_worst_gap_among_its_leaves(driver):
    """Each gap taken as for all leaves (against the leaf's norm or the
    median leaf's), the worst among the group's alone."""
    ref = {"loss": [2.0], "grad1": dict.fromkeys(
        ["l1_mla.wq", "l1_mla.wo", "l2_kda.wq", "l3_moe.w1"], 1.0)}
    ref["dparam"] = dict(ref["grad1"])
    prog = dict(ref, grad1={"l1_mla.wq": 1.01, "l1_mla.wo": 1.3,
                            "l2_kda.wq": 1.2, "l3_moe.w1": 1.5})
    nums, where = driver.compare(prog, ref, {"g": ("grad1", ["l1_mla.wq"])})
    assert nums["g"] == pytest.approx(0.01) and where["g"] == "l1_mla.wq"
    assert nums["grad1"] == pytest.approx(0.5) and where["grad1"] == "l3_moe.w1"
    assert driver.compare(prog, ref)[0] == {
        k: v for k, v in nums.items() if k != "g"}
    with pytest.raises(ValueError, match="no leaf"):
        driver.compare(prog, ref, {"g": ("grad1", ["l9_mla.wq"])})
