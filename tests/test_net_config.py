"""Tests for NetConfig DAG parsing and the functional Network."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.nnet.net_config import NetConfig
from cxxnet_tpu.nnet.network import Network, param_key
from cxxnet_tpu.utils.config import parse_config_string


def build(text):
    cfg = NetConfig()
    cfg.configure(parse_config_string(text))
    return cfg


MLP = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 100
  init_sigma = 0.01
layer[+1:sg1] = sigmoid:se1
layer[sg1->fc2] = fullc:fc2
  nhidden = 10
layer[+0] = softmax
netconfig=end
input_shape = 1,1,784
random_type = gaussian
"""


def test_mlp_structure():
    cfg = build(MLP)
    assert cfg.num_layers == 4
    assert cfg.node_names == ["in", "fc1", "sg1", "fc2"]
    l0, l1, l2, l3 = cfg.layers
    assert (l0.type_name, l0.nindex_in, l0.nindex_out) == ("fullc", [0], [1])
    assert (l1.type_name, l1.nindex_in, l1.nindex_out) == ("sigmoid", [1], [2])
    assert (l2.type_name, l2.nindex_in, l2.nindex_out) == ("fullc", [2], [3])
    assert (l3.type_name, l3.nindex_in, l3.nindex_out) == ("softmax", [3], [3])
    assert cfg.layer_name_map == {"fc1": 0, "se1": 1, "fc2": 2}
    # per-layer vs default config scoping
    assert ("nhidden", "100") in cfg.layercfg[0]
    assert ("nhidden", "10") in cfg.layercfg[2]
    assert ("random_type", "gaussian") in cfg.defcfg
    assert ("input_shape", "1,1,784") in cfg.defcfg


def test_numeric_node_names():
    cfg = build("""
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  nchannel = 8
layer[1->2] = relu
layer[2->2] = dropout
netconfig=end
input_shape = 3,8,8
""")
    assert cfg.node_names == ["in", "1", "2"]
    assert cfg.layers[2].nindex_in == cfg.layers[2].nindex_out == [2]


def test_undefined_input_node_raises():
    with pytest.raises(ValueError):
        build("""
netconfig=start
layer[bogus->x] = relu
netconfig=end
""")


def test_multi_input_and_split():
    cfg = build("""
netconfig=start
layer[0->a,b] = split
layer[a->c] = relu
layer[b->d] = sigmoid
layer[c,d->e] = ch_concat
netconfig=end
input_shape = 4,6,6
""")
    assert cfg.layers[0].nindex_out == [1, 2]
    assert cfg.layers[3].nindex_in == [3, 4]
    net = Network(cfg, batch_size=2)
    assert net.node_shapes[5] == (2, 8, 6, 6)


def test_shared_layer():
    cfg = build("""
netconfig=start
layer[0->a] = fullc:shared_fc
  nhidden = 16
layer[a->b] = relu
layer[b->c] = flatten
layer[c->d] = share[shared_fc]
netconfig=end
input_shape = 1,1,16
""")
    assert cfg.layers[3].is_shared
    assert cfg.layers[3].primary_layer_index == 0
    net = Network(cfg, batch_size=2)
    params = net.init_params(jax.random.PRNGKey(0))
    assert list(params) == ["shared_fc"]  # one param set for both conns
    # forward runs and produces the right shapes
    x = jnp.ones((2, 1, 1, 16))
    values, _ = net.forward(params, {0: x}, train=False)
    assert values[4].shape == (2, 1, 1, 16)


def test_shared_layer_params_rejected():
    with pytest.raises(ValueError):
        build("""
netconfig=start
layer[0->a] = fullc:f1
  nhidden = 4
layer[a->b] = share[f1]
  nhidden = 8
netconfig=end
""")


def test_a_named_shared_head_sets_its_own_target():
    """`share[lm_head]:mtp_head` may set the keys its primary's class
    lists (`target_offset`, `loss_weight`): it runs a copy over the
    primary's one set of weights, named by its own key, and counts its
    own loss under that key; any other key stays refused."""
    text = """
netconfig=start
layer[0->x0] = embed:embed
  nvocab = 16
  nhidden = 8
layer[x0,0->logits] = lm_head:lm_head
  nvocab = 16
layer[x0,0->second] = share[lm_head]:mtp_head
  target_offset = 2
  loss_weight = 0.5
netconfig=end
input_shape = 1,6,1
"""
    cfg = build(text)
    assert cfg.layers[2].is_shared and cfg.layers[2].name == "mtp_head"
    net = Network(cfg, batch_size=2)
    head, second = net.layer_objs[1], net.layer_objs[2]
    assert (head.offset, head.weight) == (1, 1.0)
    assert (second.offset, second.weight) == (2, 0.5)
    params = net.init_params(jax.random.PRNGKey(0))
    assert sorted(params) == ["embed", "lm_head"]
    counters = {}
    ids = jnp.asarray(np.arange(12).reshape(2, 1, 6, 1) % 16, jnp.int32)
    _, loss = net.forward(params, {0: ids}, train=True, counters=counters)
    assert list(counters) == ["mtp_head.loss"]
    with pytest.raises(ValueError, match="shared layer"):
        build(text.replace("loss_weight = 0.5", "nvocab = 8"))


def test_label_vec_slicing():
    cfg = build("""
label_vec[0,1) = label
label_vec[1,4) = extra
netconfig=start
layer[+1] = fullc
  nhidden = 4
netconfig=end
input_shape = 1,1,8
""")
    # explicit label_vec lines append ranges; the default (0,1) stays at 0
    assert cfg.label_name_map == {"label": 1, "extra": 2}
    assert cfg.label_range == [(0, 1), (0, 1), (1, 4)]


def test_layer_plus0_self_loop_and_anon_nodes():
    cfg = build("""
netconfig=start
layer[+1] = fullc
  nhidden = 4
layer[+0] = dropout
layer[+1] = fullc
  nhidden = 2
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
""")
    assert cfg.num_nodes == 3
    assert cfg.layers[1].nindex_in == cfg.layers[1].nindex_out == [1]


def test_structure_roundtrip():
    cfg = build(MLP)
    d = cfg.to_dict()
    cfg2 = NetConfig.from_dict(d)
    assert cfg2.num_layers == cfg.num_layers
    for a, b in zip(cfg.layers, cfg2.layers):
        assert a.structure_equals(b)
    # re-configuring a loaded net with the same config succeeds...
    cfg2.configure(parse_config_string(MLP))
    # ...and with a mismatched one fails
    cfg3 = NetConfig.from_dict(d)
    with pytest.raises(ValueError):
        cfg3.configure(parse_config_string(MLP.replace("sigmoid", "tanh")))


def test_mnist_conv_net_shapes():
    cfg = build("""
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  pad = 1
  stride = 2
  nchannel = 32
layer[1->2] = max_pooling
  kernel_size = 3
  stride = 2
layer[2->3] = flatten
layer[3->3] = dropout
  threshold = 0.5
layer[3->4] = fullc:fc1
  nhidden = 100
layer[4->5] = sigmoid
layer[5->6] = fullc:fc2
  nhidden = 10
layer[6->6] = softmax
netconfig=end
input_shape = 1,28,28
""")
    net = Network(cfg, batch_size=100)
    # conv: (28+2-3)//2+1 = 14; pool: min(14-3+1,13)//2+1 = 7
    assert net.node_shapes[1] == (100, 32, 14, 14)
    assert net.node_shapes[2] == (100, 32, 7, 7)
    assert net.node_shapes[3] == (100, 1, 1, 32 * 49)
    assert net.node_shapes[6] == (100, 1, 1, 10)


def test_forward_loss_and_grad():
    cfg = build(MLP)
    net = Network(cfg, batch_size=4)
    params = net.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(4, 1, 1, 784)
                    .astype(np.float32))
    labels = {"label": jnp.asarray([[1.0], [2.0], [3.0], [4.0]])}

    def loss_fn(p):
        _, loss = net.forward(p, {0: x}, train=True,
                              rng=jax.random.PRNGKey(1), labels=labels)
        return loss

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    # CE of uniform-ish init ~ log(10) per example * 4 examples
    assert 0.5 * 4 * np.log(10) < float(loss) < 2 * 4 * np.log(10)
    g = grads["fc1"]["wmat"]
    assert g.shape == (100, 784)
    assert float(jnp.abs(g).sum()) > 0


def test_forward_mask_zeroes_padding_loss():
    cfg = build(MLP)
    net = Network(cfg, batch_size=4)
    params = net.init_params(jax.random.PRNGKey(0))
    x = jnp.ones((4, 1, 1, 784))
    labels = {"label": jnp.zeros((4, 1))}
    _, loss_full = net.forward(params, {0: x}, train=True,
                               rng=jax.random.PRNGKey(1), labels=labels)
    _, loss_half = net.forward(params, {0: x}, train=True,
                               rng=jax.random.PRNGKey(1), labels=labels,
                               mask=jnp.array([1.0, 1.0, 0.0, 0.0]))
    assert abs(float(loss_half) - float(loss_full) / 2) < 1e-4


def test_param_key_naming():
    cfg = build(MLP)
    assert param_key(cfg, 0) == "fc1"
    assert param_key(cfg, 1) == "se1"
    assert param_key(cfg, 3) == "layer_3"


def test_anonymous_nodes_unique_after_retarget():
    """Two layer[+1] declarations whose top is the same node (after an
    explicit re-target) must allocate DISTINCT anonymous output nodes -
    the reference allocates positionally (regression: name-keyed
    anonymous nodes aliased)."""
    cfg = NetConfig()
    cfg.configure(parse_config_string("""
netconfig=start
layer[0->b] = fullc:f1
  nhidden = 4
layer[+1] = relu
layer[!node-of-layer-1->b2] = fullc:f2
  nhidden = 4
layer[b2->b] = fullc:f3
  nhidden = 4
layer[+1] = sigmoid
netconfig=end
input_shape = 1,1,4
batch_size = 2
"""))
    relu_out = cfg.layers[1].nindex_out[0]
    sig_out = cfg.layers[4].nindex_out[0]
    assert relu_out != sig_out, (relu_out, sig_out)
