"""BENCHMARK.json against the files it names."""

import json
import os
import re

import pytest

from benchmark import confnet

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_names_units_and_sources(spec):
    s = spec()
    metrics = s["end_to_end"] + s["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in s["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in s["end_to_end"]]
    for entry in s["configs"] + s["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200
    for w in s["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


def test_every_name_finds_its_files(spec):
    s = spec()
    root = spec.root
    e2e = {m["name"] for m in s["end_to_end"]}
    cells = {w["name"] for w in s["workloads"]}
    configs = {c["name"]: c for c in s["configs"]}
    for w in s["workloads"]:
        assert w["config"] in configs
        bench = os.path.join(root, "benchmark")
        traffic = os.path.join(bench, "traffic", w["traffic"] + ".json")
        with open(traffic) as f:
            driver = json.load(f)["driver"]
        assert os.path.exists(os.path.join(bench, "drivers", driver + ".py"))
        with open(os.path.join(bench, "limits", w["name"] + ".json")) as f:
            assert json.load(f)["limits"]
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert os.path.exists(os.path.join(
            root, "benchmark", "layer_metrics", m["name"] + ".py"))
    for c in s["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            root, "benchmark", "reference", cfg["reference"]["module"] + ".py"))


def test_limit_groups_name_compared_numbers(spec):
    """A number over a group of leaves (a limits file's `groups`) has a
    limit in that file, and names its reading, a layer type and one
    parameter of it."""
    for w in spec()["workloads"]:
        with open(os.path.join(spec.root, "benchmark", "limits",
                               w["name"] + ".json")) as f:
            limits = json.load(f)
        for name, g in limits.get("groups", {}).items():
            assert name in limits["limits"]
            assert set(g) == {"of", "layer_type", "param"}
            assert g["of"] in ("grad1", "dparam")


@pytest.mark.parametrize("name", ["alexnet", "resnet18"])
def test_conf_copy_is_the_committed_conf(spec, name):
    """The cell runs the example conf as committed: the copy under
    benchmark/ holds the same pairs in the same order, the data and
    eval iterator blocks apart."""
    root = spec.root
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, cfg["conf"])) as f:
        copy = confnet.parse_pairs(f.read())
    with open(os.path.join(root, cfg["copied_from"])) as f:
        original = confnet.parse_pairs(f.read())
    kept, skip = [], False
    for k, v in original:
        if k in ("data", "eval"):
            skip = True
        if not skip:
            kept.append((k, v))
        if skip and k == "iter" and v == "end":
            skip = False
    assert copy == kept
    # what the cell changes is in the configuration's file, not the conf
    assert "pool_grad" not in dict(copy)
    assert cfg["layer_overrides"] == {"max_pooling": {"pool_grad": "winner"}}
    assert any("pool_grad" in line for line in cfg["assumed"])


def test_layer_pairs_go_under_every_layer_of_the_type():
    text = """
    netconfig=start
    layer[0->1] = max_pooling   # stem
      kernel_size = 3
    layer[1->2] = avg_pooling
      kernel_size = 2
    layer[+1] = max_pooling:p3
    netconfig=end
    input_shape = 1,9,9
    """
    net = confnet.build(confnet.parse_pairs(confnet.with_layer_pairs(
        text, {"max_pooling": {"pool_grad": "winner"}})))
    assert [l.get("pool_grad", "ties") for l in net.layers] == [
        "winner", "ties", "winner"]
    assert net.layers[0].kernel() == 3
