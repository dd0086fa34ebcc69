"""`lrn_ms` on a made-up step: whatever stands under an `lrn.<key>`
scope counts, the kernels of `route.pallas` and a layout copy beside
them alike; a conv named after itself does not, and a net without an
`lrn` layer reads nothing."""

import os
from types import SimpleNamespace

import pytest

from benchmark import run, scope_map as sm

TEXT = """HloModule jit_train_step

ENTRY %main.1 (p.1: bf16[8,16,4]) -> bf16[8,16,4] {
  %p.1 = bf16[8,16,4]{2,1,0} parameter(0)
  %lrn_fwd.2 = bf16[4,16,8]{2,1,0} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(lrn.layer_3)/route.pallas/lrn_fwd/pallas_call"}
  %copy.7 = bf16[4,16,8]{0,1,2} copy(%lrn_fwd.2), metadata={op_name="jit(train_step)/jvp(lrn.layer_3)/route.pallas/transpose"}
  %convolution.4 = bf16[8,16,4]{2,1,0} convolution(%copy.7, %copy.7), dim_labels=bf0_oi0->bf0, metadata={op_name="jit(train_step)/transpose(jvp(conv.conv2))/conv_general_dilated"}
  %lrn_bwd.3 = bf16[4,16,8]{2,1,0} custom-call(%copy.7, %convolution.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(lrn.layer_3))/route.pallas/lrn_bwd/pallas_call"}
  %reduce-window.5 = bf16[8,16,4]{2,1,0} reduce-window(%lrn_bwd.3), metadata={op_name="jit(train_step)/jvp(lrn.layer_7)/route.xla/reduce_window_sum"}
  ROOT %multiply.6 = bf16[8,16,4]{2,1,0} multiply(%reduce-window.5, %p.1), metadata={op_name="jit(train_step)/transpose(jvp(lrn.layer_7))/route.xla/mul"}
}
"""

EVENTS = [("lrn_fwd.2", 0.0, 30.0), ("copy.7", 30.0, 8.0),
          ("convolution.4", 40.0, 100.0), ("lrn_bwd.3", 140.0, 50.0),
          ("reduce-window.5", 190.0, 6.0), ("multiply.6", 196.0, 4.0)] * 2


def reader():
    return run.load_module(os.path.join(run.HERE, "layer_metrics",
                                        "lrn_ms.py"))


def obs(types, events=EVENTS):
    return SimpleNamespace(
        device_events=list(events), window=SimpleNamespace(steps=2),
        net=SimpleNamespace(layers=[SimpleNamespace(type=t)
                                    for t in types]))


def test_the_route_names_a_scope_of_no_layer():
    scopes = sm.scopes_of(TEXT)
    assert scopes["lrn_fwd.2"] == ("fwd", "lrn", "layer_3")
    assert scopes["copy.7"] == ("fwd", "lrn", "layer_3")
    assert scopes["lrn_bwd.3"] == ("bwd", "lrn", "layer_3")
    assert scopes["multiply.6"] == ("bwd", "lrn", "layer_7")
    assert scopes["convolution.4"] == ("bwd", "conv", "conv2")


def test_lrn_ms_counts_kernels_copies_and_the_xla_route(monkeypatch):
    monkeypatch.setattr(sm, "step_text", lambda o: TEXT)
    got = reader().read(obs(["conv", "lrn", "conv", "lrn"]))
    assert got == pytest.approx(2 * (30 + 8 + 50 + 6 + 4) / 2 / 1e6)


def test_lrn_ms_is_silent_without_the_layer_or_the_trace(monkeypatch):
    monkeypatch.setattr(sm, "step_text", lambda o: TEXT)
    assert reader().read(obs(["conv", "batch_norm"])) is None
    assert reader().read(obs(["conv", "lrn"], events=[])) is None
