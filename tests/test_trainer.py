"""Trainer end-to-end tests on synthetic data (CPU, 8 virtual devices)."""

import io

import numpy as np
import pytest

import jax

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config_string

MLP_CFG = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1:ac1] = tanh
layer[ac1->fc2] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.5
momentum = 0.9
wd = 0.0
metric = error
eval_train = 1
"""


def make_trainer(extra="", cfg=MLP_CFG, silent=True):
    t = NetTrainer()
    for k, v in parse_config_string(cfg + extra):
        t.set_param(k, v)
    if silent:
        t.set_param("silent", "1")
    t.init_model()
    return t


def synth_batches(n_batches=20, batch_size=16, seed=0):
    """Linearly separable 2-class data."""
    rng = np.random.RandomState(seed)
    w = rng.randn(8)
    batches = []
    for _ in range(n_batches):
        x = rng.randn(batch_size, 8).astype(np.float32)
        y = (x @ w > 0).astype(np.float32)
        batches.append(DataBatch(
            data=x.reshape(batch_size, 1, 1, 8),
            label=y.reshape(batch_size, 1)))
    return batches


class ListIter:
    def __init__(self, batches):
        self.batches = batches
        self.i = -1

    def before_first(self):
        self.i = -1

    def next(self):
        self.i += 1
        return self.i < len(self.batches)

    def value(self):
        return self.batches[self.i]


def test_training_converges():
    t = make_trainer()
    batches = synth_batches(30)
    for r in range(8):
        t.start_round(r)
        for b in batches:
            t.update(b)
        t.clear_train_metric()
    # eval error on held-out batches from the same distribution
    out = t.evaluate(ListIter(synth_batches(5, seed=0)), "test")
    err = float(out.split(":")[-1])
    assert err < 0.15, out
    assert out.startswith("\ttest-error:")


def test_update_all_runs_evals():
    """update_all's eval_iters/eval_names must actually evaluate (they
    were silently ignored until round 5) and return the reference-
    format metric string; without eval iters it returns ''."""
    t = make_trainer()
    batches = synth_batches(4)
    assert t.update_all(ListIter(batches)) == ""
    out = t.update_all(ListIter(batches),
                       eval_iters=[ListIter(synth_batches(2, seed=1)),
                                   ListIter(synth_batches(2, seed=2))],
                       eval_names=["test"])
    assert "\ttest-error:" in out
    assert "\teval2-error:" in out  # default name for unnamed iters


def test_epoch_counter_and_update_period():
    t = make_trainer(extra="update_period = 2\n")
    batches = synth_batches(4)
    p0 = np.asarray(t.state["params"]["fc1"]["wmat"]).copy()
    t.update(batches[0])
    assert t.epoch == 0  # no update yet
    p1 = np.asarray(t.state["params"]["fc1"]["wmat"])
    np.testing.assert_allclose(p0, p1)  # params unchanged before period
    t.update(batches[1])
    assert t.epoch == 1
    p2 = np.asarray(t.state["params"]["fc1"]["wmat"])
    assert np.abs(p2 - p0).max() > 0


def test_update_period_equals_two_small_steps():
    """grad accumulation over 2 half-batches == reference scaling."""
    t1 = make_trainer()
    t2 = make_trainer(extra="update_period = 2\n")
    # same params start
    b = synth_batches(2)
    t2.update(b[0])
    t2.update(b[1])
    assert t2.epoch == 1


def test_short_batch_padding_and_metrics():
    t = make_trainer()
    x = np.ones((10, 1, 1, 8), dtype=np.float32)
    y = np.zeros((10, 1), dtype=np.float32)
    short = DataBatch(data=x, label=y, num_batch_padd=0)
    # batch smaller than batch_size: padded internally
    t.update(short)
    out = t.evaluate(ListIter([short]), "t")
    assert np.isfinite(float(out.split(":")[-1]))


def test_num_batch_padd_trimming():
    t = make_trainer()
    x = np.random.RandomState(0).randn(16, 1, 1, 8).astype(np.float32)
    y = np.zeros((16, 1), dtype=np.float32)
    batch = DataBatch(data=x, label=y, num_batch_padd=6)
    p = t.predict(batch)
    assert p.shape == (10,)  # padding rows trimmed


def test_predict_and_extract():
    t = make_trainer()
    b = synth_batches(1)[0]
    pred = t.predict(b)
    assert pred.shape == (16,)
    assert set(np.unique(pred)) <= {0.0, 1.0}
    dist = t.predict_dist(b)
    assert dist.shape == (16, 2)
    np.testing.assert_allclose(dist.sum(axis=1), 1.0, rtol=1e-5)
    feat = t.extract_feature(b, "ac1")
    assert feat.shape == (16, 1, 1, 32)
    feat2 = t.extract_feature(b, "top[-1]")
    assert feat2.shape == (16, 1, 1, 2)
    feat3 = t.extract_feature(b, "top[-2]")
    assert feat3.shape == (16, 1, 1, 32)


def test_checkpoint_roundtrip():
    t = make_trainer()
    for b in synth_batches(3):
        t.update(b)
    buf = io.BytesIO()
    t.save_model(buf)

    t2 = make_trainer()
    buf.seek(0)
    t2.load_model(buf)
    assert t2.epoch == t.epoch
    np.testing.assert_allclose(
        np.asarray(t2.state["params"]["fc1"]["wmat"]),
        np.asarray(t.state["params"]["fc1"]["wmat"]))
    # both predict identically
    b = synth_batches(1, seed=7)[0]
    np.testing.assert_allclose(t.predict_dist(b), t2.predict_dist(b),
                               rtol=1e-5)


def test_checkpoint_with_optimizer_state():
    t = make_trainer(extra="save_optimizer = 1\n")
    for b in synth_batches(3):
        t.update(b)
    buf = io.BytesIO()
    t.save_model(buf)
    buf.seek(0)
    t2 = make_trainer(extra="save_optimizer = 1\n")
    t2.load_model(buf)
    np.testing.assert_allclose(
        np.asarray(t2.state["ustate"]["fc1"]["wmat"]["m"]),
        np.asarray(t.state["ustate"]["fc1"]["wmat"]["m"]))


def test_finetune_copy_model_from():
    t = make_trainer()
    for b in synth_batches(3):
        t.update(b)
    buf = io.BytesIO()
    t.save_model(buf)

    # new net with same fc1 but different fc2 width: fc1 copied, fc2 not
    cfg2 = MLP_CFG.replace("nhidden = 2", "nhidden = 4")
    t2 = make_trainer(cfg=cfg2)
    buf.seek(0)
    t2.copy_model_from(buf)
    np.testing.assert_allclose(
        np.asarray(t2.state["params"]["fc1"]["wmat"]),
        np.asarray(t.state["params"]["fc1"]["wmat"]))
    assert np.asarray(t2.state["params"]["fc2"]["wmat"]).shape == (4, 32)


def test_get_set_weight():
    t = make_trainer()
    w, shape = t.get_weight("fc1", "wmat")
    assert w.shape == (32, 8) and shape == (32, 8)
    new = np.zeros_like(w)
    t.set_weight(new, "fc1", "wmat")
    w2, _ = t.get_weight("fc1", "wmat")
    np.testing.assert_allclose(w2, 0.0)
    b = synth_batches(1)[0]
    dist = t.predict_dist(b)
    assert np.isfinite(dist).all()


def test_data_parallel_multi_device_matches_single():
    """dp over 8 virtual devices == single device (same jit program)."""
    assert len(jax.devices()) == 8
    t1 = make_trainer()  # single default device
    t8 = make_trainer(extra="dev = tpu:0-7\n")
    assert t8.mesh.devices.size == 8
    batches = synth_batches(5)
    for b in batches:
        t1.update(b)
        t8.update(b)
    np.testing.assert_allclose(
        np.asarray(t1.state["params"]["fc1"]["wmat"]),
        np.asarray(t8.state["params"]["fc1"]["wmat"]), rtol=2e-4, atol=1e-5)


def test_bfloat16_host_cast_input_path():
    """dtype=bfloat16 stages bf16 inputs from the host (half the H2D
    bytes); training, eval and predict all run through it."""
    import ml_dtypes
    t = make_trainer(extra="dtype = bfloat16\n")
    assert t._host_input(np.ones((2, 1), np.float32)).dtype \
        == ml_dtypes.bfloat16
    b = synth_batches(1)[0]
    t.update(b)
    out = t.evaluate(ListIter([b]), "e")
    assert np.isfinite(float(out.split(":")[-1]))
    assert t.predict(b).shape == (16,)


def test_stage_dtype_f32_matches_host_cast():
    """stage_dtype=float32 stages f32 and lets the jitted step cast to
    bf16 on device (fused) - the identical round-to-nearest-even, so
    the training trajectory matches the host-cast path exactly."""
    import ml_dtypes
    t1 = make_trainer(extra="dtype = bfloat16\n")
    t2 = make_trainer(extra="dtype = bfloat16\nstage_dtype = float32\n")
    assert t2._host_input(np.ones((2, 1), np.float32)).dtype == np.float32
    assert t1._host_input(np.ones((2, 1), np.float32)).dtype \
        == ml_dtypes.bfloat16
    for b in synth_batches(4):
        t1.update(b)
        t2.update(b)
    np.testing.assert_allclose(
        np.asarray(t1.state["params"]["fc1"]["wmat"]),
        np.asarray(t2.state["params"]["fc1"]["wmat"]),
        rtol=1e-6, atol=1e-7)
    with pytest.raises(ValueError, match="stage_dtype"):
        make_trainer(extra="stage_dtype = int8\n")
    # bf16 staging under f32 compute can never take effect: reject the
    # silent no-op instead of hiding a misconfiguration
    with pytest.raises(ValueError, match="requires dtype=bfloat16"):
        make_trainer(extra="stage_dtype = bfloat16\n")


def test_remat_matches_plain():
    """remat=1 (jax.checkpoint over the forward) changes memory, not
    math: training trajectories are identical."""
    t1 = make_trainer()
    t2 = make_trainer(extra="remat = 1\n")
    for b in synth_batches(4):
        t1.update(b)
        t2.update(b)
    np.testing.assert_allclose(
        np.asarray(t1.state["params"]["fc1"]["wmat"]),
        np.asarray(t2.state["params"]["fc1"]["wmat"]),
        rtol=1e-5, atol=1e-6)


def test_shard_optimizer_zero1_matches_replicated():
    """ZeRO-1 optimizer-state sharding (update_on_server analog,
    nnet_ps_server.cpp:20-170): same math, state sharded over 'data'."""
    t_rep = make_trainer(extra="dev = tpu:0-7\n")
    t_z1 = make_trainer(extra="dev = tpu:0-7\nshard_optimizer = 1\n")
    st = t_z1.state["ustate"]["fc1"]["wmat"]["m"]
    assert not st.sharding.is_fully_replicated, st.sharding
    assert "data" in t_z1._ustate_shard["fc1"]["wmat"].spec
    for b in synth_batches(5):
        t_rep.update(b)
        t_z1.update(b)
    np.testing.assert_allclose(
        np.asarray(t_rep.state["params"]["fc1"]["wmat"]),
        np.asarray(t_z1.state["params"]["fc1"]["wmat"]),
        rtol=2e-4, atol=1e-5)
    # momentum state agrees too (after gathering the shards)
    np.testing.assert_allclose(
        np.asarray(t_rep.state["ustate"]["fc1"]["wmat"]["m"]),
        np.asarray(t_z1.state["ustate"]["fc1"]["wmat"]["m"]),
        rtol=2e-4, atol=1e-5)


def test_shard_optimizer_checkpoint_roundtrip():
    t = make_trainer(
        extra="dev = tpu:0-7\nshard_optimizer = 1\nsave_optimizer = 1\n")
    for b in synth_batches(3):
        t.update(b)
    buf = io.BytesIO()
    t.save_model(buf)
    buf.seek(0)
    t2 = make_trainer(extra="save_optimizer = 1\n")
    t2.load_model(buf)
    np.testing.assert_allclose(
        np.asarray(t2.state["ustate"]["fc1"]["wmat"]["m"]),
        np.asarray(t.state["ustate"]["fc1"]["wmat"]["m"]), rtol=1e-6)


def test_device_pruning_for_odd_batch():
    # batch 16 with 5 devices requested -> pruned to 4
    t = make_trainer(extra="dev = tpu:0-4\n")
    assert t.mesh.devices.size == 4


def test_on_device_eval_metric_matches_host():
    """evaluate()'s device-accumulated metrics == the host MetricSet
    path on the same batches (incl. a short batch + num_batch_padd)."""
    from cxxnet_tpu.utils.metric import MetricSet
    t = make_trainer()
    for b in synth_batches(3):
        t.update(b)
    batches = synth_batches(3, seed=5)
    short = DataBatch(data=batches[0].data[:10],
                      label=batches[0].label[:10], num_batch_padd=2)
    evset = [batches[1], short]
    out = t.evaluate(ListIter(evset), "ev")
    dev_err = float(out.split(":")[-1])
    host = MetricSet()
    host.add_metric("error", "label")
    for b in evset:
        nvalid = b.batch_size - b.num_batch_padd
        host.add_eval([t.predict_dist(b)[:nvalid]],
                      {"label": b.label[:nvalid]})
    assert abs(dev_err - host._metrics[0].get()) < 1e-6, out
    assert out.startswith("\tev-error:")


def test_on_device_train_metric_matches_host():
    """The jitted (sum,count) accumulation == the host MetricSet on the
    same forward outputs (update_period=2 so the first update leaves the
    params untouched and predict_dist reproduces the training forward)."""
    from cxxnet_tpu.utils.metric import MetricSet
    t = make_trainer(extra="update_period = 2\n")
    b = synth_batches(1)[0]
    t.update(b)
    out = t.eval_train_metric()
    dev_err = float(out.split(":")[-1])
    host = MetricSet()
    host.add_metric("error", "label")
    host.add_eval([t.predict_dist(b)], {"label": b.label})
    assert abs(dev_err - host._metrics[0].get()) < 1e-6
    assert out.startswith("\ttrain-error:")
    # accumulator was reset by the readback
    assert float(np.asarray(t.state["tmetric"]).sum()) == 0.0


def test_train_metric_ignores_padded_rows():
    t = make_trainer(extra="update_period = 4\n")
    x = np.random.RandomState(3).randn(10, 1, 1, 8).astype(np.float32)
    y = np.ones((10, 1), np.float32)
    t.update(DataBatch(data=x, label=y))  # padded 10 -> 16
    vals = np.asarray(t.state["tmetric"])
    assert vals.shape == (1, 3)  # (sum, kahan comp, count)
    assert vals[0, 2] == 10.0  # count == valid rows only


def test_multi_target_metrics():
    cfg = """
label_vec[0,1) = label
label_vec[1,3) = extra
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 8
layer[+1:act] = relu
layer[act->out1] = fullc:o1
  nhidden = 2
layer[+0] = softmax
layer[act->out2] = fullc:o2
  nhidden = 2
layer[+0] = l2_loss
  target = extra
netconfig=end
input_shape = 1,1,4
batch_size = 8
eta = 0.01
metric[label,out1] = error
metric[extra,out2] = rmse
"""
    t = make_trainer(cfg=cfg)
    x = np.random.RandomState(0).randn(8, 1, 1, 4).astype(np.float32)
    label = np.zeros((8, 3), dtype=np.float32)
    t.update(DataBatch(data=x, label=label))
    out = t.evaluate(ListIter([DataBatch(data=x, label=label)]), "e")
    assert "e-error:" in out and "e-rmse[extra]:" in out


def test_active_step_binding_end_to_end():
    """The trainer binds the traced update counter into every training
    forward (epoch*update_period + count), verified observably: a probe
    layer emits x*(step+1), and with rmse train metrics + zero labels
    the per-update rmse sequence must be 1, 2, 3, ... across an
    update_period boundary."""
    import jax.numpy as jnp
    from cxxnet_tpu.layers.base import (Layer, get_active_step,
                                        register_layer)

    class StepProbeLayer(Layer):
        type_name = "_step_probe"

        def infer_shapes(self, in_shapes):
            return [in_shapes[0]]

        def apply(self, params, inputs, *, train, rng=None):
            step = get_active_step()
            f = (step.astype(jnp.float32) + 1.0
                 if step is not None else jnp.float32(1000.0))
            return [inputs[0] * f]

    register_layer(StepProbeLayer)
    cfg = """
netconfig=start
layer[0->1] = _step_probe
layer[1->1] = l2_loss
netconfig=end
input_shape = 1,1,1
eta = 0.0
update_period = 2
batch_size = 4
silent = 1
metric = rmse
"""
    t = NetTrainer()
    for k, v in parse_config_string(cfg):
        t.set_param(k, v)
    t.init_model()
    ones = np.ones((4, 1, 1, 1), np.float32)
    zeros = np.zeros((4, 1), np.float32)
    seen = []
    for _ in range(3):
        t.update(DataBatch(data=ones, label=zeros))
        out = t.eval_train_metric()
        seen.append(float(out.split("rmse:")[1]))
    # probe output = step+1; the rmse metric keeps the reference's
    # no-sqrt quirk (squared error), so per-update values are
    # (step+1)^2 = 1, 4, 9 for steps 0, 1, 2 - spanning the
    # update_period=2 epoch boundary
    np.testing.assert_allclose(seen, [1.0, 4.0, 9.0], rtol=1e-5)


def test_extra_data_nodes_feed_through():
    """extra_data_num nets train and predict end to end: the trainer
    feeds DataBatch.extra_data into input nodes in_1.. (the attachtxt
    pipeline's consumer side - data.h:96-139)."""
    cfg = """
extra_data_num = 1
extra_data_shape[0] = 1,1,4
netconfig=start
layer[in,in_1->2] = concat
layer[2->3] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[3->4] = relu
layer[4->5] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[5->5] = softmax
netconfig=end
input_shape = 1,1,4
batch_size = 8
eta = 0.2
momentum = 0.9
metric = error
silent = 1
"""
    t = NetTrainer()
    for k, v in parse_config_string(cfg):
        t.set_param(k, v)
    t.init_model()
    rng = np.random.RandomState(6)
    # the label depends ONLY on the extra-data input: training can only
    # succeed if in_1 is actually fed
    for _ in range(30):
        x = rng.randn(8, 1, 1, 4).astype(np.float32)
        e = rng.randn(8, 1, 1, 4).astype(np.float32)
        y = (e.reshape(8, 4).sum(1) > 0).astype(np.float32)
        t.update(DataBatch(data=x, label=y.reshape(8, 1),
                           extra_data=[e]))
    x = rng.randn(8, 1, 1, 4).astype(np.float32)
    e = rng.randn(8, 1, 1, 4).astype(np.float32)
    y = (e.reshape(8, 4).sum(1) > 0).astype(np.float32)
    pred = t.predict(DataBatch(data=x, label=y.reshape(8, 1),
                               extra_data=[e]))
    assert (pred == y).mean() >= 0.75, (pred, y)
    # missing extras must fail loudly, not silently feed garbage
    with pytest.raises(ValueError, match="extra_data_num"):
        t.update(DataBatch(data=x, label=y.reshape(8, 1)))


def test_round_batch_wrap_rows_are_trained():
    """round_batch wrap-fill rows are REAL instances consumed early
    from the next epoch; training must include them (the reference
    trims num_batch_padd only at eval - nnet_impl-inl.hpp:239)."""
    t = make_trainer()
    x = np.random.RandomState(1).randn(16, 1, 1, 8).astype(np.float32)
    y = np.zeros((16, 1), np.float32)
    wrapped = DataBatch(data=x, label=y, num_batch_padd=6)
    p0 = np.asarray(t.state["params"]["fc1"]["wmat"]).copy()
    t.update(wrapped)
    # train metric counted ALL 16 rows (not 10)
    vals = np.asarray(t.state["tmetric"])
    assert vals[0, 2] == 16.0, vals
    # but eval still trims the wrap rows
    out = t.evaluate(ListIter([wrapped]), "e")
    assert np.isfinite(float(out.split(":")[-1]))
    assert np.abs(np.asarray(t.state["params"]["fc1"]["wmat"])
                  - p0).max() > 0


def test_checkpoint_slash_in_layer_name_and_corruption():
    """'/' in a layer name round-trips (separator recorded in the
    header) and corrupt/truncated files fail with clear ValueErrors."""
    cfg2 = MLP_CFG.replace("fullc:fc1", "fullc:stage1/fc")
    cfg2 = cfg2.replace("layer[+1:fc1]", "layer[+1:s1]")
    t = make_trainer(cfg=cfg2)
    for b in synth_batches(2):
        t.update(b)
    buf = io.BytesIO()
    t.save_model(buf)
    t2 = make_trainer(cfg=cfg2)
    buf.seek(0)
    t2.load_model(buf)
    np.testing.assert_allclose(
        np.asarray(t2.state["params"]["stage1/fc"]["wmat"]),
        np.asarray(t.state["params"]["stage1/fc"]["wmat"]))
    # corruption diagnostics
    from cxxnet_tpu.nnet import checkpoint as ckpt
    raw = bytearray(buf.getvalue())
    with pytest.raises(ValueError, match="truncated"):
        ckpt.load_model(io.BytesIO(bytes(raw[:len(raw) // 2])))
    bad = bytearray(raw)
    bad[8:16] = (1 << 60).to_bytes(8, "little")
    with pytest.raises(ValueError, match="header length"):
        ckpt.load_model(io.BytesIO(bytes(bad)))


def test_fast_bf16_cast_bitwise_matches_ml_dtypes():
    """The torch fast path of the host bf16 staging cast must be
    bitwise round-to-nearest-even identical to ml_dtypes (it sits on
    the e2e critical path; a semantic drift would silently change
    every staged batch)."""
    import ml_dtypes
    from cxxnet_tpu.nnet.trainer import _bf16_cast
    rng = np.random.RandomState(0)
    x = np.concatenate([
        rng.randn(1000).astype(np.float32) * 1e3,
        np.array([0.0, -0.0, 1e-40, np.inf, -np.inf], np.float32),
    ])
    a = _bf16_cast(x).view(np.uint16)
    b = x.astype(ml_dtypes.bfloat16).view(np.uint16)
    np.testing.assert_array_equal(a, b)


def test_staged_batch_trajectory_identical():
    """update(stage_batch(b)) must be bit-identical to update(b): the
    staging runs the exact per-step pipeline once, so a device-resident
    dataset (the membuffer analog, StagedBatch) changes throughput,
    never the training trajectory."""
    batches = synth_batches(6)
    t1 = make_trainer()
    t2 = make_trainer()
    for b in batches:
        t1.update(b)
    staged = [t2.stage_batch(b) for b in batches]
    for s in staged:
        t2.update(s)
    p1 = jax.tree_util.tree_leaves(t1.state["params"])
    p2 = jax.tree_util.tree_leaves(t2.state["params"])
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_staged_batch_counts_padded_rows_once():
    """A short batch staged with wrap rows keeps the same distinct-
    instance accounting (n_examples) the streamed path reports."""
    t = make_trainer()
    b = synth_batches(1, batch_size=16)[0]
    short = DataBatch(data=b.data[:12], label=b.label[:12],
                      num_batch_padd=2)
    s = t.stage_batch(short)
    assert s.n_examples == 10
    t.update(s)  # padded staged batch trains without error
