"""Int8 post-training quantization (docs/GRAPH_PASSES.md
"Quantization"): the quantize_int8 graph pass + ops/int8.py kernels -
scale math vs a numpy reference, calibration determinism across the
single/multi-batch paths, the `layer_quant` pin (config, plan and
schema), checkpoint/resume invariance, the Server's
uncalibrated-serves-float leg, and the tuning-cache `layer_quant`
plan key."""

import hashlib
import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet import tuning
from cxxnet_tpu.nnet.passes import find_quant_sites
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.ops import int8 as int8_ops
from cxxnet_tpu.utils.config import ConfigError, parse_config_string

BN_MLP_CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:bn1] = batch_norm:bn1
layer[+1:sg1] = tanh
layer[sg1->fc2] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,36
batch_size = 8
dev = cpu
eta = 0.1
silent = 1
seed = 11
"""

_QUANT_PASSES = "graph_passes = fold_conv_bn,dead_layer_elim," \
                "quantize_int8\n"


def _build(conf, extra=""):
    tr = NetTrainer()
    for k, v in parse_config_string(conf + extra):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _batch(i, b=8, shape=(1, 1, 36), nclass=3):
    r = np.random.RandomState(700 + i)
    return DataBatch(
        data=r.rand(b, *shape).astype(np.float32),
        label=r.randint(0, nclass, size=(b, 1)).astype(np.float32))


# ---------------------------------------------------------------------------
# ops/int8.py scale math vs a numpy reference
# ---------------------------------------------------------------------------
def test_per_channel_scale_matches_numpy_reference():
    r = np.random.RandomState(3)
    w = (r.randn(5, 7) * np.asarray(
        [0.1, 1.0, 10.0, 0.0, 2.5])[:, None]).astype(np.float32)
    s = int8_ops.per_channel_scale(w)
    ref = np.abs(w).max(axis=1) / 127.0
    # the all-zero channel gets the floored (representable) scale
    ref[3] = 1e-8 / 127.0
    assert s.shape == (5,) and s.dtype == np.float32
    assert np.allclose(s, ref, rtol=1e-6, atol=0)


def test_quantize_weight_round_clip_and_dequant_roundtrip():
    r = np.random.RandomState(4)
    w = r.randn(6, 9).astype(np.float32)
    s = int8_ops.per_channel_scale(w)
    q = np.asarray(int8_ops.quantize_weight(w, s))
    assert q.dtype == np.int8
    ref = np.clip(np.round(w / s[:, None]), -127, 127)
    assert (q == ref.astype(np.int8)).all()
    # symmetric scheme: the per-channel absmax hits +-127 exactly
    assert np.abs(q).max(axis=1).tolist() == [127] * 6
    # dequantized weight is within half a quantization step
    assert np.abs(q * s[:, None] - w).max() <= (s.max() / 2) + 1e-7


def test_int8_matmul_dequant_close_to_float_matmul():
    r = np.random.RandomState(5)
    x = r.randn(4, 32).astype(np.float32)
    w = r.randn(10, 32).astype(np.float32)
    ascale = np.abs(x).max() / 127.0
    wscale = int8_ops.per_channel_scale(w)
    acc = int8_ops.int8_matmul(
        int8_ops.quantize_act(x, ascale),
        int8_ops.quantize_weight(w, wscale))
    assert np.asarray(acc).dtype == np.int32
    out = np.asarray(int8_ops.dequantize(acc, ascale, wscale))
    ref = x @ w.T
    # int8 quantization error budget: ~1% of the output scale
    assert np.abs(out - ref).max() <= 0.02 * np.abs(ref).max() + 0.05


def test_pallas_kernel_matches_lax_fallback_interpret():
    """The Pallas MXU kernel (interpret-mode hook, the pallas_lrn
    idiom) is bit-identical to the lax preferred-element-type
    fallback on a tile-clean shape."""
    r = np.random.RandomState(6)
    xq = r.randint(-127, 128, (32, 128)).astype(np.int8)
    wq = r.randint(-127, 128, (128, 128)).astype(np.int8)
    lax_out = np.asarray(int8_ops.int8_matmul(xq, wq))
    assert int8_ops._pallas_blocks(32, 128, 128) is not None
    old = int8_ops._FORCE_INTERPRET
    int8_ops._FORCE_INTERPRET = True
    try:
        # the test platform is an 8-device virtual CPU mesh
        # (conftest), but the route gate asks the mesh the step runs
        # over: a direct call or a one-device mesh takes the kernel,
        # a multi-device mesh refuses (pallas_call has no GSPMD
        # partitioning rule; the lax path partitions)
        import jax
        from jax.sharding import Mesh
        from cxxnet_tpu.parallel.mesh import active_mesh
        assert jax.device_count() == 8
        assert int8_ops.use_pallas_int8(32, 128, 128)
        with active_mesh(Mesh(np.asarray(jax.devices()[:1]),
                              ("data",))):
            assert int8_ops.use_pallas_int8(32, 128, 128)
        with active_mesh(Mesh(np.asarray(jax.devices()), ("data",))):
            assert not int8_ops.use_pallas_int8(32, 128, 128)
        pl_out = np.asarray(int8_ops._matmul_pallas(xq, wq))
    finally:
        int8_ops._FORCE_INTERPRET = old
    assert pl_out.dtype == np.int32
    assert (pl_out == lax_out).all()


# ---------------------------------------------------------------------------
# calibration: determinism across the N=1 / N>1 batch paths
# ---------------------------------------------------------------------------
def test_quant_calibration_absmax_matches_numpy_and_is_deterministic():
    on1 = _build(BN_MLP_CONF, _QUANT_PASSES)
    on2 = _build(BN_MLP_CONF, _QUANT_PASSES)
    b = _batch(90)
    assert on1.calibrate_graph_passes(b)
    # a one-element sequence rides the pinned single-batch path
    assert on2.calibrate_graph_passes([b])
    assert on1._quant_stats.keys() == {"fc1", "fc2"}
    assert on1._quant_stats == on2._quant_stats
    # fc1's tapped input IS the data node: exact numpy reference
    assert on1._quant_stats["fc1"] == pytest.approx(
        float(np.abs(b.data).max()), rel=1e-6)


def test_quant_multi_batch_calibration_pools_by_max():
    on = _build(BN_MLP_CONF, _QUANT_PASSES)
    batches = [_batch(91), _batch(92), _batch(93)]
    assert on.calibrate_graph_passes(batches)
    single = []
    for b in batches:
        t = _build(BN_MLP_CONF, _QUANT_PASSES)
        t.calibrate_graph_passes(b)
        single.append(t._quant_stats)
    for key in ("fc1", "fc2"):
        assert on._quant_stats[key] == pytest.approx(
            max(s[key] for s in single), rel=1e-5)


def test_quant_multi_batch_masks_padding_rows():
    """round_batch=0 zero-pads the tail batch; padding rows at depth
    carry bias/activation garbage that must not widen the frozen
    activation range."""
    on = _build(BN_MLP_CONF, _QUANT_PASSES)
    full = _batch(94)
    short = _batch(95)
    # poison the padding rows with a huge activation
    data = np.concatenate([short.data[:5],
                           np.full_like(short.data[:3], 1e6)])
    padded = DataBatch(data=data, label=short.label.copy(),
                       num_batch_padd=3)
    assert on.calibrate_graph_passes([full, padded])
    real_absmax = max(float(np.abs(full.data).max()),
                      float(np.abs(short.data[:5]).max()))
    assert on._quant_stats["fc1"] == pytest.approx(real_absmax,
                                                   rel=1e-5)


def test_single_batch_calibration_masks_padding_rows():
    """The N=1 path (_calibrate_staged) must mask padding rows out
    of the activation absmax exactly like the N>1 path - a
    round_batch=0 tail batch's zero-fill garbage at depth must not
    widen the frozen range (regression: the mask was discarded)."""
    on = _build(BN_MLP_CONF, _QUANT_PASSES)
    short = _batch(95)
    data = np.concatenate([short.data[:5],
                           np.full_like(short.data[:3], 1e6)])
    padded = DataBatch(data=data, label=short.label.copy(),
                       num_batch_padd=3)
    assert on.calibrate_graph_passes(padded)
    assert on._quant_stats["fc1"] == pytest.approx(
        float(np.abs(short.data[:5]).max()), rel=1e-5)


def test_set_weight_invalidates_quant_stats():
    on = _build(BN_MLP_CONF, _QUANT_PASSES)
    on.calibrate_graph_passes(_batch(96))
    assert not on.passes_need_calibration()
    w = np.asarray(on.get_weight("fc2", "wmat")[0])
    on.set_weight(w * 2.0, "fc2", "wmat")
    # frozen scales went stale: the epoch-bump eviction recalibrates
    assert on._quant_stats is None
    assert on.passes_need_calibration()


# ---------------------------------------------------------------------------
# end-to-end: parity + int8 engagement on the traced program
# ---------------------------------------------------------------------------
def _dot_dtypes(tr, b=8):
    node = tr.net_cfg.num_nodes - 1
    g, ge = tr.stage_infer_rows(np.zeros((b, 1, 1, 36), np.float32))
    eqns = tr._infer_fn(node).trace(
        tr.state["params"], g, ge).jaxpr.jaxpr.eqns
    return [(str(e.invars[0].aval.dtype), str(e.outvars[0].aval.dtype))
            for e in eqns if e.primitive.name == "dot_general"
            if e.invars[0].aval.shape
            and e.invars[0].aval.shape[0] == b]


def test_quantized_predict_agrees_with_fold_and_trace_is_int8():
    """Int8-only error isolation: compare against the FOLDED float
    trainer calibrated on the same batch (vs the unfolded baseline
    the comparison would also price the fold's frozen-vs-per-batch
    BN statistics - the GRAPH_PASSES.md fold semantics note)."""
    fold = _build(BN_MLP_CONF,
                  "graph_passes = fold_conv_bn,dead_layer_elim\n")
    on = _build(BN_MLP_CONF, _QUANT_PASSES)
    for i in range(4):
        fold.update(_batch(i))
        on.update(_batch(i))
    cb = _batch(79)
    fold.calibrate_graph_passes(cb)
    on.calibrate_graph_passes(cb)
    agree, total = 0, 0
    for i in range(4):
        b = _batch(80 + i)
        po, pn = fold.predict_dist(b), on.predict_dist(b)
        assert np.abs(po - pn).max() <= 0.02  # int8 error budget
        agree += int((po.argmax(1) == pn.argmax(1)).sum())
        total += po.shape[0]
    assert agree / total >= 0.9
    # every data-path matmul of the quantized trace is int8 -> int32;
    # the float trace keeps f32 dots (vacuity guard)
    q_dots = _dot_dtypes(on, b=8)
    assert q_dots and all(d == ("int8", "int32") for d in q_dots)
    f_dots = _dot_dtypes(fold, b=8)
    assert f_dots and all(d[0] == "float32" for d in f_dots)


def test_quantized_weights_stay_live_functions_of_params():
    on = _build(BN_MLP_CONF, _QUANT_PASSES)
    on.calibrate_graph_passes(_batch(97))
    b = _batch(98)
    p1 = on.predict_dist(b)
    # zero fc2's weight THROUGH the live params (no set_weight, no
    # eviction): the in-jit quantize stage must see the new weight
    import jax.numpy as jnp
    on.state["params"]["fc2"]["wmat"] = jnp.zeros_like(
        on.state["params"]["fc2"]["wmat"])
    p2 = on.predict_dist(b)
    assert not np.allclose(p1, p2)
    # zero logits -> uniform softmax rows
    assert np.allclose(p2, 1.0 / 3.0, atol=1e-6)


# ---------------------------------------------------------------------------
# the layer_quant pin
# ---------------------------------------------------------------------------
def test_layer_quant_float_pin_excludes_site():
    conf = BN_MLP_CONF.replace(
        "  nhidden = 16",
        "  nhidden = 16\n  layer_quant = float")
    tr = _build(conf, _QUANT_PASSES)
    idx = [tr.net_cfg.layers[i].name
           for i in find_quant_sites(tr.net_cfg)]
    assert idx == ["fc2"]
    # the pinned layer's dot stays float while fc2 quantizes
    tr.calibrate_graph_passes(_batch(99))
    dts = _dot_dtypes(tr)
    assert ("float32", "float32") in dts
    assert ("int8", "int32") in dts


def test_layer_quant_rejects_bad_value():
    with pytest.raises(ValueError, match="layer_quant"):
        _build(BN_MLP_CONF.replace(
            "  nhidden = 16",
            "  nhidden = 16\n  layer_quant = int4"))


# ---------------------------------------------------------------------------
# checkpoint bytes + two-way resume across the quant flag flip
# ---------------------------------------------------------------------------
def test_checkpoint_bytes_identical_quant_on_off():
    off = _build(BN_MLP_CONF)
    on = _build(BN_MLP_CONF, _QUANT_PASSES)
    for i in range(4):
        off.update(_batch(i))
        on.update(_batch(i))
    on.predict(_batch(81))  # calibrate + build the quantized graph
    bo, bq = io.BytesIO(), io.BytesIO()
    off.save_model(bo)
    on.save_model(bq)
    assert bo.getvalue() == bq.getvalue()


def test_resume_across_quant_flag_both_directions(tmp_path):
    """`continue = 1` resumes across quantize_int8 on<->off in both
    directions: the pass never touches the training graph or the
    checkpoint format (the fold-pass resume matrix, quant edition)."""
    from cxxnet_tpu.tools.pass_smoke import CONF
    from cxxnet_tpu.tools.telemetry_smoke import write_synth_mnist
    d = str(tmp_path)
    write_synth_mnist(d, 192, 0, "train")
    write_synth_mnist(d, 96, 1, "test")
    with open(os.path.join(d, "t.conf"), "w") as f:
        f.write(CONF.format(d=d))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    passes_arg = ("graph_passes=fold_conv_bn,dead_layer_elim,"
                  "quantize_int8")

    def run(mdir, *overrides):
        r = subprocess.run(
            [sys.executable, "-m", "cxxnet_tpu.main",
             os.path.join(d, "t.conf"), f"model_dir={mdir}",
             *overrides],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]

    def sha(mdir, n):
        with open(os.path.join(mdir, f"{n:04d}.model"), "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()

    ma, mb = os.path.join(d, "ma"), os.path.join(d, "mb")
    run(ma)
    run(mb, passes_arg)
    assert sha(ma, 2) == sha(mb, 2)
    # resume ACROSS the flag flip, both directions
    run(ma, "continue=1", "num_round=3", "max_round=1", passes_arg)
    run(mb, "continue=1", "num_round=3", "max_round=1")
    assert sha(ma, 3) == sha(mb, 3)


# ---------------------------------------------------------------------------
# serving: uncalibrated warns and serves float
# ---------------------------------------------------------------------------
def test_server_uncalibrated_warns_and_serves_float(capsys):
    from cxxnet_tpu.serve import Server
    off = _build(BN_MLP_CONF)
    on = _build(BN_MLP_CONF, "graph_passes = quantize_int8\n")
    assert on.passes_need_calibration()
    srv = Server(on, max_batch=8, max_wait_ms=1.0, replicas=1)
    assert "have no calibration stats" in capsys.readouterr().err
    srv.warmup()
    srv.start()
    b = _batch(56, b=8)
    try:
        rows = srv.submit(b.data).result(timeout=60)
    finally:
        srv.stop()
    # float serving: matches the passes-off trainer exactly (the
    # un-rewritten graph is the same program)
    expect = off.infer_rows(*off.stage_infer_rows(b.data))
    assert np.allclose(rows, np.asarray(expect).reshape(8, -1),
                       rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# tuning cache: the layer_quant plan key
# ---------------------------------------------------------------------------
def test_cache_layer_quant_roundtrip_and_garbage_rejected(tmp_path):
    p = str(tmp_path / "tc.json")
    tuning.save_entry(p, "cpu", {},
                      layers={"fc1": {"layer_quant": "float"},
                              "fc2": {"layer_quant": "int8"}})
    assert tuning.tuned_layer_plan(p, "cpu") == {
        "fc1": {"layer_quant": "float"},
        "fc2": {"layer_quant": "int8"}}
    with open(p) as f:
        assert json.load(f)["version"] == 2
    # the typo'd knob is untunable at save AND rejected at load
    with pytest.raises(ValueError, match="untunable per-layer"):
        tuning.save_entry(str(tmp_path / "x.json"), "cpu", {},
                          layers={"fc1": {"layer_qunat": "int8"}})
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"version": 2, "platforms": {
            "cpu": {"layers": {"fc1": {"layer_qunat": "int8"}}}}}, f)
    with pytest.raises(ConfigError):
        tuning.load_cache(bad)


def test_trainer_applies_layer_quant_plan_and_explicit_wins(tmp_path):
    p = str(tmp_path / "tc.json")
    tuning.save_entry(p, "cpu", {},
                      layers={"fc1": {"layer_quant": "float"},
                              "bn1": {"layer_quant": "float"}})
    tr = _build(BN_MLP_CONF, f"tuning_cache = {p}\n" + _QUANT_PASSES)
    idx = tr.net_cfg.layer_name_map["fc1"]
    assert ("layer_quant", "float") in tr.net_cfg.layercfg[idx]
    # the plan stamp drives the pattern exclusion
    assert [tr.net_cfg.layers[i].name
            for i in find_quant_sites(tr.net_cfg)] == ["fc2"]
    # layer_quant on a non-conv/fullc layer is inapplicable: skipped
    bidx = tr.net_cfg.layer_name_map["bn1"]
    assert not any(k == "layer_quant"
                   for k, _ in tr.net_cfg.layercfg[bidx])
    # explicit per-layer key beats the plan
    conf2 = BN_MLP_CONF.replace(
        "  nhidden = 16",
        "  nhidden = 16\n  layer_quant = int8")
    tr2 = _build(conf2, f"tuning_cache = {p}\n" + _QUANT_PASSES)
    idx2 = tr2.net_cfg.layer_name_map["fc1"]
    vals = [v for k, v in tr2.net_cfg.layercfg[idx2]
            if k == "layer_quant"]
    assert vals == ["int8"]


# ---------------------------------------------------------------------------
# config schema: keys registered, the layer_qunat typo pinned
# ---------------------------------------------------------------------------
def test_schema_registers_quant_keys_and_pins_layer_qunat():
    from cxxnet_tpu.analysis import schema
    reg = schema.build_registry()
    for key in ("layer_quant", "pass_quantize_int8",
                "pass_elim_reshape", "pass_calibration_batches"):
        assert reg.recognizes(key), key
    # the serve_max_batchh treatment, quant edition
    assert reg.suggest("layer_qunat") == "layer_quant"
    with pytest.raises(ConfigError, match="layer_quant"):
        schema.validate_pairs([("layer_qunat", "int8")],
                              source="x.conf")


def test_pass_toggle_quantize_int8_via_prefix():
    tr = NetTrainer()
    tr.set_param("pass_quantize_int8", "1")
    assert tr._pass_toggles["quantize_int8"] == 1
    tr.set_param("pass_elim_reshape", "0")
    assert tr._pass_toggles["elim_reshape"] == 0
