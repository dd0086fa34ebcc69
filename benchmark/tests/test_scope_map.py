"""From the compiled step's text to scopes, on a recorded text, and the
join onto device events, on a made-up list.

`fixtures/step_small.hlo.txt` is a conv / relu / max-pool / loss step
with an SGD-momentum update, compiled for one v5e chip: `fusion.25`
holds the conv's weight gradient with the update of its weight and
momentum (and the relu's backward), `fusion.14` is elementwise over
three layers and both directions, `convert_reduce_fusion.1` has no name
of its own, `select_and_scatter.9` is no fusion, and the `copy-start`s
carry no name."""

import os
from types import SimpleNamespace

import pytest

from benchmark import run, scope_map as sm

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("fwd_ms", "bwd_ms", "update_ms", "pool_bwd_ms", "conv_bwd_ms",
           "batch_norm_ms", "scoped_pct")


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(HERE, "fixtures", "step_small.hlo.txt")) as f:
        return f.read()


def test_scopes_of_the_recorded_step(text):
    scopes = sm.scopes_of(text)
    # a fusion with a convolution is that convolution's, whatever else
    # it holds; the updater's instructions inside it make it mixed
    assert scopes["fusion.25"] == ("bwd", "conv", "conv1")
    assert scopes["broadcast_maximum_fusion"] == ("fwd", "conv", "conv1")
    # any other fusion is what its root makes (XLA gives a fusion its
    # root's name): the pool's output, though five of its instructions
    # are the loss's and one the pool's
    assert scopes["fusion.14"] == ("fwd", "max_pooling", "layer_2")
    # one without a name of its own goes to what most of its scoped
    # instructions name
    assert scopes["convert_reduce_fusion.1"] == ("fwd", "relu", "layer_1")
    assert scopes["select_and_scatter.9"] == ("bwd", "max_pooling",
                                              "layer_2")
    assert scopes["copy-start"] == sm.OTHER
    # instructions inside fused computations are in the map too, and the
    # updater's scope, which holds the key alone, gets the layer's type
    assert ("update", "conv", "conv1") in scopes.values()
    # (`fusion.19.clone.1` is nested inside `fusion.25`: never an event)
    assert sm.mixed_fusions(text) == {"fusion.14", "fusion.25",
                                      "fusion.19.clone.1"}


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp(conv.conv1)/conv_general_dilated",
     ("fwd", "conv", "conv1")),
    ("jit(train_step)/transpose(jvp(max_pooling.layer_2))/pad",
     ("bwd", "max_pooling", "layer_2")),
    ("jit(train_step)/jvp(lrn.layer_3)/lrn_fwd/pallas_call",
     ("fwd", "lrn", "layer_3")),
    ("jit(train_step)/update/conv1/sub", ("update", "conv", "conv1")),
    ("jit(train_step)/update/cond/branch_1_fun/conv1/sub",
     ("update", "conv", "conv1")),
    ("jit(train_step)/update/broadcast_in_dim", ("update", "", "")),
    # what XLA merged keeps both names: the first with a scope counts
    ("jit(train_step)/jvp()/mul;jit(train_step)/transpose(jvp(fullc.fc6"
     "))/dot_general", ("bwd", "fullc", "fc6")),
    # an inference program has no jvp around its layers
    ("jit(infer)/batch_norm.bn1/rsqrt", ("fwd", "batch_norm", "bn1")),
    ("jit(train_step)/jvp()/convert_element_type", sm.OTHER),
    ("jit(train_step)/jit(_where)/select_n", sm.OTHER),
    ("", sm.OTHER),
])
def test_scope_of_an_op_name(op_name, want):
    assert sm.scope_of(op_name, {"conv1": "conv"}) == want


EVENTS = [("fusion.25", 0.0, 60.0), ("broadcast_maximum_fusion", 60.0, 30.0),
          ("fusion.14", 90.0, 10.0), ("select_and_scatter.9", 100.0, 40.0),
          ("copy-start", 140.0, 5.0), ("fusion.777", 150.0, 5.0),
          ("fusion.25", 200.0, 60.0), ("broadcast_maximum_fusion", 260.0, 30.0),
          ("fusion.14", 290.0, 10.0), ("select_and_scatter.9", 300.0, 40.0)]


def made_up_obs(events=EVENTS, steps=2):
    layers = [SimpleNamespace(type=t) for t in
              ("conv", "relu", "max_pooling", "softmax")]
    return SimpleNamespace(device_events=list(events),
                           net=SimpleNamespace(layers=layers),
                           window=SimpleNamespace(steps=steps))


def read_all(obs):
    return {name: run.load_module(os.path.join(
        run.HERE, "layer_metrics", name + ".py")).read(obs)
            for name in READERS}


def test_time_by_scope_on_a_made_up_window(text, monkeypatch):
    calls = []
    monkeypatch.setattr(sm, "step_text",
                        lambda obs: calls.append(1) or text)
    obs = made_up_obs()
    table = sm.by_scope(obs)
    assert table.ns[("bwd", "conv", "conv1")] == 120.0
    assert table.ns[("bwd", "max_pooling", "layer_2")] == 80.0
    assert table.ns[("fwd", "max_pooling", "layer_2")] == 20.0
    assert table.ns[sm.OTHER] == 10.0
    assert (table.total_ns, table.unknown_ns, table.mixed_ns) == (
        290.0, 5.0, 140.0)
    got = read_all(obs)
    assert calls == [1]                    # one compile a run, kept on obs
    assert got["fwd_ms"] == pytest.approx(80.0 / 2 / 1e6)
    assert got["bwd_ms"] == pytest.approx(200.0 / 2 / 1e6)
    assert got["update_ms"] == 0.0         # all of it rode in fusion.25
    assert got["pool_bwd_ms"] == pytest.approx(80.0 / 2 / 1e6)
    assert got["conv_bwd_ms"] == pytest.approx(120.0 / 2 / 1e6)
    assert got["batch_norm_ms"] is None    # no such layer in this net
    # busy is the union of the intervals: 145 + 5 + 140 ns
    assert got["scoped_pct"] == pytest.approx(100.0 * 280.0 / 290.0)
    assert "mixed_pct = 48.276" in sm.render(table)


def test_nothing_to_read_is_none_not_an_error(text, monkeypatch):
    # a CPU rehearsal: no device events, and nothing is compiled
    monkeypatch.setattr(sm, "step_text", lambda obs: 1 / 0)
    assert set(read_all(made_up_obs(events=[])).values()) == {None}
    # a failed second compile costs the new metrics, not the run
    assert set(read_all(made_up_obs()).values()) == {None}


def test_a_program_without_step_hlo_reads_nothing(monkeypatch):
    """The parent of the PR that brought the scopes: the driver runs
    these readers over it too."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    monkeypatch.delattr(NetTrainer, "step_hlo")
    assert set(read_all(made_up_obs()).values()) == {None}
