"""benchmark/flops.py against the hand counts in the configuration files."""

import json
import os

import pytest

from benchmark import confnet, flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("name", ["alexnet", "resnet18"])
def test_counts_match_the_hand_count(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, cfg["conf"])) as f:
        net = confnet.build(confnet.parse_pairs(f.read()), cfg["overrides"])
    hand = cfg["hand_count"]
    assert flops.forward_macs_per_image(net) == hand["forward_macs_per_image"]
    assert flops.train_flop_per_image(net) == hand["train_flop_per_image"]


def test_input_layer_has_no_data_gradient():
    net = confnet.build(confnet.parse_pairs("""
        netconfig=start
        layer[0->1] = conv:c1
          kernel_size = 3
          nchannel = 4
        layer[1->2] = flatten
        layer[2->3] = fullc:f1
          nhidden = 10
        layer[+0] = softmax
        netconfig=end
        input_shape = 2,5,5
    """))
    c1 = 4 * 3 * 3 * 2 * 3 * 3
    f1 = 36 * 10
    assert flops.layer_macs(net) == [("c1", c1, True), ("f1", f1, False)]
    assert flops.train_flop_per_image(net) == 2 * (2 * c1 + 3 * f1)
