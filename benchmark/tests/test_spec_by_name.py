"""Room for the next cell: a configuration, a cell and a per-layer metric
appended at the end of BENCHMARK.json, as a later change appends them,
with the files they name. Every check that reads BENCHMARK.json (each
of `test_contract.py` and each `test_cell_is_*`) still passes against
that copy: the checks find an entry by its name, and none counts a list
or reads an entry by its place in it."""

import inspect
import json
import os
import shutil

import pytest

from benchmark import run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
MODULES = ("test_contract", "test_run", "test_run_kimi",
           "test_run_smallthinker", "test_run_lfm2")
CONFIG = "made_up_model"
CELL = CONFIG + ".train_seq32k"
METRIC = "made_up_ms"
LFM2 = "lfm2_24b_a2b.train_seq32k"


def module(name):
    return bench.load_module(os.path.join(HERE, name + ".py"))


def checks():
    """(module, check, its parameters) for every check that reads
    BENCHMARK.json, one a parameter set of a parametrized check."""
    out = []
    for mod_name in MODULES:
        for name, fn in vars(module(mod_name)).items():
            if not (name.startswith("test_cell_is_") or (
                    mod_name == "test_contract" and name.startswith("test_")
                    and callable(fn))):
                continue
            sets = [{}]
            for mark in getattr(fn, "pytestmark", []):
                if mark.name == "parametrize":
                    argnames, values = mark.args[0], mark.args[1]
                    sets = [dict(s, **{argnames: v}) for s in sets
                            for v in values]
            out += [(mod_name, name, s) for s in sets]
    return out


CHECKS = checks()


def _write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """A checkout of the benchmark with one configuration (LFM2's file
    under another name), one cell of it and one per-layer metric
    appended, each with the files a later change would add."""
    root = str(tmp_path_factory.mktemp("appended"))
    src = os.path.join(bench.ROOT, "benchmark")
    dst = os.path.join(root, "benchmark")
    shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    os.symlink(os.path.join(bench.ROOT, "examples"),
               os.path.join(root, "examples"))
    with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)

    with open(os.path.join(src, "configs", "lfm2_24b_a2b.json")) as f:
        cfg = json.load(f)
    cfg["name"] = CONFIG
    _write_json(os.path.join(dst, "configs", CONFIG + ".json"), cfg)
    spec["configs"].append({
        "name": CONFIG, "source": "https://huggingface.co/org/made-up",
        "file": f"benchmark/configs/{CONFIG}.json",
        "reduced": cfg["reduced"], "why": "a configuration appended"})
    spec["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "train_seq32k",
        "chips": 1, "why": "a cell appended after the accepted five"})
    shutil.copy(os.path.join(src, "limits", LFM2 + ".json"),
                os.path.join(dst, "limits", CELL + ".json"))
    spec["per_layer"].append({
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "layers / ops",
        "moves": "train_img_s", "workloads": [LFM2, CELL]})
    with open(os.path.join(dst, "layer_metrics", METRIC + ".py"), "w") as f:
        f.write('"""A metric appended: reads nothing."""\n\n\n'
                "def read(obs):\n    return None\n")
    _write_json(os.path.join(root, "BENCHMARK.json"), spec)
    return root


@pytest.mark.parametrize(
    "mod_name,check,params", CHECKS,
    ids=[f"{m}.{c}" + "".join(f"[{v}]" for v in p.values())
         for m, c, p in CHECKS])
def test_a_check_passes_with_a_sixth_cell_appended(
        appended, spec, monkeypatch, mod_name, check, params):
    monkeypatch.setattr(bench, "ROOT", appended)
    monkeypatch.setattr(bench, "HERE", os.path.join(appended, "benchmark"))
    read = spec()
    assert read["workloads"][-1]["name"] == CELL
    assert read["per_layer"][-1]["name"] == METRIC
    fn = getattr(module(mod_name), check)
    wants = inspect.signature(fn).parameters
    given = dict(params, spec=spec)
    assert set(wants) <= set(given), f"{check} asks for {set(wants)}"
    fn(**{k: given[k] for k in wants})


def test_every_check_that_reads_the_spec_is_among_them():
    """The cells' own checks, one a module that has a cell, and the
    contract's whole file."""
    found = {(m, c) for m, c, _ in CHECKS}
    for mod_name in MODULES[1:]:
        assert (mod_name, "test_cell_is_the_one_the_issue_names") in found
    assert {c for m, c in found if m == "test_contract"} >= {
        "test_names_units_and_sources", "test_every_name_finds_its_files",
        "test_conf_copy_is_the_committed_conf"}
