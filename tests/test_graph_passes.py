"""Graph-level optimizing passes over the NetConfig DAG
(cxxnet_tpu/nnet/passes.py, docs/GRAPH_PASSES.md): pattern engine,
the four shipped passes, the pass-aware inference path, checkpoint
compatibility, and the tuning cache."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet import passes, tuning
from cxxnet_tpu.nnet.passes import PassPipeline, find_fold_sites
from cxxnet_tpu.nnet.trainer import NetTrainer
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
batch_size = 32
dev = cpu
eta = 0.3
metric = error
silent = 1
seed = 7
"""

BN_CONV_CONF = """
netconfig=start
layer[+1:c1] = conv:c1
  nchannel = 8
  kernel_size = 4
  stride = 2
layer[+1:b1] = batch_norm:b1
layer[+1:r1] = relu
layer[+1:c2] = conv:c2
  nchannel = 8
  kernel_size = 3
  pad = 1
layer[+1:fl] = flatten
layer[+1:fc] = fullc:fc
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 3,16,16
batch_size = 8
dev = cpu
eta = 0.1
silent = 1
seed = 5
"""


def _build(conf, extra=""):
    tr = NetTrainer()
    for k, v in parse_config_string(conf + extra):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _mlp_batch(i, b=32, width=36, nclass=3):
    r = np.random.RandomState(100 + i)
    return DataBatch(
        data=r.rand(b, 1, 1, width).astype(np.float32),
        label=r.randint(0, nclass, size=(b, 1)).astype(np.float32))


def _conv_batch(i, b=8):
    r = np.random.RandomState(200 + i)
    return DataBatch(
        data=r.rand(b, 3, 16, 16).astype(np.float32),
        label=r.randint(0, 3, size=(b, 1)).astype(np.float32))


@pytest.fixture(scope="module")
def mlp_pair():
    """(passes-off, fold+dle-on) BN-MLP trainers trained identically
    for a few steps - infer-stage passes must not touch training, so
    their weights are the same arrays."""
    off = _build(BN_MLP_CONF)
    on = _build(BN_MLP_CONF,
                "graph_passes = fold_conv_bn,dead_layer_elim\n")
    for i in range(5):
        off.update(_mlp_batch(i))
        on.update(_mlp_batch(i))
    return off, on


# ---------------------------------------------------------------------------
# pipeline construction + did-you-mean
# ---------------------------------------------------------------------------
def test_pipeline_from_config_names_and_order():
    pl = PassPipeline.from_config("fold_conv_bn,space_to_depth")
    assert pl.names() == ["space_to_depth", "fold_conv_bn"]
    assert [p.name for p in pl.infer_passes] == ["fold_conv_bn"]
    assert PassPipeline.from_config("").names() == []
    assert set(PassPipeline.from_config("all").names()) == set(
        passes.PASS_REGISTRY)


def test_pipeline_pass_name_did_you_mean():
    with pytest.raises(ValueError, match=r"did you mean "
                       r"'fold_conv_bn'"):
        PassPipeline.from_config("fold_conv_bnn")
    with pytest.raises(ValueError, match="unknown graph pass"):
        PassPipeline.from_config("totally_bogus")


def test_pipeline_toggles_layer_over_list():
    pl = PassPipeline.from_config("fold_conv_bn",
                                  {"fold_conv_bn": 0,
                                   "dead_layer_elim": 1})
    assert pl.names() == ["dead_layer_elim"]
    with pytest.raises(ValueError, match="did you mean"):
        PassPipeline.from_config("", {"fold_conv_bnn": 1})


def test_trainer_rejects_typo_pass_name():
    tr = NetTrainer()
    for k, v in parse_config_string(BN_MLP_CONF):
        tr.set_param(k, v)
    tr.set_param("graph_passes", "dead_layer_elimm")
    with pytest.raises(ValueError, match="dead_layer_elim"):
        tr.init_model()


def test_schema_registers_pass_and_tuning_keys():
    from cxxnet_tpu.analysis import schema
    reg = schema.build_registry()
    for key in ("graph_passes", "tuning_cache", "layer_dtype",
                "pass_fold_conv_bn", "pass_dead_layer_elim",
                "pass_autocast", "pass_space_to_depth"):
        assert reg.recognizes(key), key
    assert reg.suggest("graph_passess") == "graph_passes"
    with pytest.raises(ConfigError, match="graph_passes"):
        schema.validate_pairs([("graph_passess", "all")],
                              source="x.conf")


# ---------------------------------------------------------------------------
# pattern engine
# ---------------------------------------------------------------------------
def test_find_fold_sites_mlp_and_conv():
    off = _build(BN_MLP_CONF)
    assert find_fold_sites(off.net_cfg) == [(0, 1)]
    conv = _build(BN_CONV_CONF)
    assert find_fold_sites(conv.net_cfg) == [(0, 1)]


def test_fold_site_requires_single_consumer():
    conf = BN_MLP_CONF.replace(
        "layer[+1:bn1] = batch_norm:bn1",
        "layer[fc1->spl1,spl2] = split\n"
        "layer[spl1->bn1] = batch_norm:bn1")
    # fc1's output feeds a split, not the bn directly: no site
    tr = _build(conf.replace("layer[sg1->fc2]", "layer[sg1->fc2]"))
    assert find_fold_sites(tr.net_cfg) == []


def test_fold_site_excludes_shared_weights():
    conf = """
netconfig=start
layer[0->a] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[0->b] = share[fc1]
layer[a->c] = batch_norm:bn1
layer[a,b->d] = concat
layer[+1] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,12
batch_size = 8
dev = cpu
eta = 0.1
silent = 1
"""
    tr = _build(conf)
    # fc1 is a share primary AND node a has two consumers: no site
    assert find_fold_sites(tr.net_cfg) == []


# ---------------------------------------------------------------------------
# fold_conv_bn
# ---------------------------------------------------------------------------
def test_fold_parity_on_calibration_batch(mlp_pair):
    off, on = mlp_pair
    b = _mlp_batch(50)
    po = off.predict_dist(b)
    pn = on.predict_dist(b)  # calibrates on this batch
    assert np.allclose(po, pn, rtol=1e-5, atol=1e-6)
    assert (po.argmax(1) == pn.argmax(1)).all()
    assert on._fold_stats is not None
    assert "bn1" in on._fold_stats


def test_folded_jaxpr_has_no_moment_pipeline(mlp_pair):
    off, on = mlp_pair
    on.predict(_mlp_batch(50))  # ensure calibrated
    node = on.net_cfg.num_nodes - 1
    data = np.zeros((32, 1, 1, 36), np.float32)
    g, ge = on.stage_infer_rows(data)
    folded = str(on._infer_fn(node)
                 .trace(on.state["params"], g, ge).jaxpr)
    g2, ge2 = off.stage_infer_rows(data)
    unfolded = str(off._infer_fn(node)
                   .trace(off.state["params"], g2, ge2).jaxpr)
    assert "rsqrt" not in folded
    assert "rsqrt" in unfolded


def test_fold_conv_parity():
    off = _build(BN_CONV_CONF)
    on = _build(BN_CONV_CONF, "graph_passes = fold_conv_bn\n")
    for i in range(3):
        off.update(_conv_batch(i))
        on.update(_conv_batch(i))
    b = _conv_batch(60)
    po, pn = off.predict_dist(b), on.predict_dist(b)
    assert np.allclose(po, pn, rtol=1e-4, atol=1e-6)
    assert (po.argmax(1) == pn.argmax(1)).all()
    # the folded graph lost its batch_norm layer
    node = on.net_cfg.num_nodes - 1
    _net2, _pfn, gm = on._build_infer_graph(node)
    assert "batch_norm" not in [li.type_name for li in gm.cfg.layers]


def test_fold_parity_self_loop_bn():
    """`layer[+0] = batch_norm` (classic cxxnet style) overwrites its
    own node: calibration must tap the BN INPUT before the overwrite,
    not read the post-normalization value after the forward - a
    wrong tap folds silently wrong weights (the stats would come out
    as ~(beta, 1/slope), not the conv-output moments)."""
    conf = BN_MLP_CONF.replace(
        "layer[+1:bn1] = batch_norm:bn1",
        "layer[+0] = batch_norm:bn1")
    off = _build(conf)
    on = _build(conf, "graph_passes = fold_conv_bn\n")
    for i in range(5):
        off.update(_mlp_batch(i))
        on.update(_mlp_batch(i))
    b = _mlp_batch(53)
    po = off.predict_dist(b)
    pn = on.predict_dist(b)  # calibrates on this batch
    assert find_fold_sites(on.net_cfg) == [(0, 1)]
    assert np.allclose(po, pn, rtol=1e-5, atol=1e-6)
    assert (po.argmax(1) == pn.argmax(1)).all()


def test_pass_toggle_prefix_covers_future_passes():
    """The pass_<name> toggle handler is prefix-form: any registered
    pass gets a toggle without a trainer edit, and the schema
    registry recognizes the prefix."""
    from cxxnet_tpu.analysis import schema
    assert schema.build_registry().recognizes("pass_anything_here")
    tr = NetTrainer()
    for k, v in parse_config_string(BN_MLP_CONF):
        tr.set_param(k, v)
    tr.set_param("pass_fold_conv_bnn", "1")  # typo'd toggle
    with pytest.raises(ValueError, match="fold_conv_bn"):
        tr.init_model()


def test_folded_weights_are_live(mlp_pair):
    """The fold bakes only the calibration STATS into the executable;
    W'/b' are in-jit functions of the params ARGUMENT - calling the
    compiled folded executable with a params tree whose fc2 weights
    are zeroed must flatten the logits, no rebuild involved."""
    _off, on = mlp_pair
    b = _mlp_batch(50)
    on.predict_dist(b)
    node = on.net_cfg.num_nodes - 1
    fn = on._infer_fn(node)  # the compiled folded executable
    g, ge = on.stage_infer_rows(b.data)
    import jax.numpy as jnp
    params = {lk: dict(d) for lk, d in on.state["params"].items()}
    params["fc2"] = {"wmat": jnp.zeros_like(params["fc2"]["wmat"]),
                     "bias": jnp.zeros_like(params["fc2"]["bias"])}
    flat = np.asarray(fn(params, g, ge)).reshape(32, -1)
    assert np.allclose(flat, 1.0 / flat.shape[1], atol=1e-6)


def test_set_weight_invalidates_fold_stats():
    """The visitor weight API changes activations like a model load
    does: frozen fold statistics must retire (and the folded path
    re-agree with an unfolded trainer after recalibration)."""
    on = _build(BN_MLP_CONF, "graph_passes = fold_conv_bn\n")
    b = _mlp_batch(0)
    on.predict(b)  # calibrate
    epoch = on._fold_epoch
    w, _ = on.get_weight("fc1", "wmat")
    on.set_weight(w * 2.0, "fc1", "wmat")
    assert on._fold_stats is None
    assert on._fold_epoch == epoch + 1
    assert on.passes_need_calibration()
    pn = on.predict_dist(b)  # recalibrates on the new activations
    off = _build(BN_MLP_CONF)  # same seed -> same init
    off.set_weight(w * 2.0, "fc1", "wmat")
    po = off.predict_dist(b)
    assert np.allclose(po, pn, rtol=1e-5, atol=1e-6)
    assert (po.argmax(1) == pn.argmax(1)).all()


def test_fold_stats_reset_on_param_reload(mlp_pair):
    _off, on = mlp_pair
    on.predict(_mlp_batch(50))
    assert on._fold_stats is not None
    import io
    buf = io.BytesIO()
    on.save_model(buf)
    # copy_model_from re-inits state: frozen stats must drop so the
    # next inference recalibrates against the new activations
    buf.seek(0)
    on.copy_model_from(buf)
    assert on._fold_stats is None
    assert on.passes_need_calibration()


# ---------------------------------------------------------------------------
# dead_layer_elim
# ---------------------------------------------------------------------------
def test_dle_extract_parity_and_prune(mlp_pair):
    off, on = mlp_pair
    b = _mlp_batch(51)
    fo = off.extract_feature(b, "fc1")
    fn = on.extract_feature(b, "fc1")
    assert np.array_equal(fo, fn)
    nid = on.net.node_index("fc1")
    _net2, _pfn, gm = on._build_infer_graph(nid)
    assert [li.type_name for li in gm.cfg.layers] == ["fullc"]
    data = np.zeros((32, 1, 1, 36), np.float32)
    g, ge = on.stage_infer_rows(data)
    tr = on._infer_fn(nid).trace(on.state["params"], g, ge)
    dots = sum(1 for e in tr.jaxpr.jaxpr.eqns
               if e.primitive.name == "dot_general")
    assert dots == 1  # the pruned fc2 matmul is not even traced


def test_dle_promotes_share_with_dead_primary():
    conf = """
netconfig=start
layer[0->a] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[0->b] = share[fc1]
layer[a->c] = tanh
layer[c->d] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,12
batch_size = 8
dev = cpu
eta = 0.1
silent = 1
seed = 9
"""
    off = _build(conf)
    on = _build(conf, "graph_passes = dead_layer_elim\n")
    b = DataBatch(
        data=np.random.RandomState(0).rand(8, 1, 1, 12)
        .astype(np.float32),
        label=np.zeros((8, 1), np.float32))
    fo = off.extract_feature(b, "b")
    fn = on.extract_feature(b, "b")
    assert np.array_equal(fo, fn)
    nid = on.net.node_index("b")
    _net2, _pfn, gm = on._build_infer_graph(nid)
    # only the promoted share survives, fed by fc1's live weights
    assert [li.type_name for li in gm.cfg.layers] == ["fullc"]
    assert not gm.cfg.layers[0].is_shared
    assert list(gm.param_map().values()) == ["fc1"]


def test_dle_keeps_raw_conv_output_unfolded(mlp_pair):
    """Extracting the PRE-BN node must return the raw fullc output:
    DLE prunes the bn (not an ancestor), the fold must not rewire
    the requested node away."""
    off, on = mlp_pair
    b = _mlp_batch(52)
    assert np.array_equal(off.extract_feature(b, "fc1"),
                          on.extract_feature(b, "fc1"))


# ---------------------------------------------------------------------------
# autocast + space_to_depth
# ---------------------------------------------------------------------------
def test_autocast_plan_policy_and_override():
    import jax.numpy as jnp
    on = _build(BN_CONV_CONF,
                "graph_passes = autocast\ndtype = bfloat16\n")
    plan = on.net.dtype_plan
    types = [li.type_name for li in on.net_cfg.layers]
    assert plan[types.index("batch_norm")] == jnp.float32
    assert plan[len(types) - 1] == jnp.float32  # softmax head
    assert plan[types.index("conv")] == jnp.bfloat16
    # layer_dtype pins a layer against the policy
    pinned = _build(
        BN_CONV_CONF.replace("  nchannel = 8\n  kernel_size = 4",
                             "  nchannel = 8\n  layer_dtype = float32"
                             "\n  kernel_size = 4"),
        "graph_passes = autocast\ndtype = bfloat16\n")
    assert pinned.net.dtype_plan[0] == jnp.float32
    on.update(_conv_batch(0))
    out = on.predict_dist(_conv_batch(1))
    assert np.isfinite(out).all()


def test_autocast_noop_under_f32():
    on = _build(BN_CONV_CONF, "graph_passes = autocast\n")
    assert on.net.dtype_plan is None


def test_layer_dtype_rejects_bad_value():
    with pytest.raises(ValueError, match="layer_dtype"):
        _build(BN_CONV_CONF.replace(
            "  kernel_size = 4", "  layer_dtype = float16\n"
            "  kernel_size = 4"))


def test_s2d_pass_stamps_and_matches_auto():
    off = _build(BN_CONV_CONF)
    on = _build(BN_CONV_CONF, "graph_passes = space_to_depth\n")
    # input conv (3ch, stride 2, k4) -> stamped on; mid conv -> off
    assert ("space_to_depth", "1") in on.net_cfg.layercfg[0]
    c2 = [li.type_name for li in on.net_cfg.layers].index("conv", 1)
    assert ("space_to_depth", "0") in on.net_cfg.layercfg[c2]
    assert on.net.layer_objs[0].s2d is True
    # the stamp encodes the SAME decision the in-op auto heuristic
    # takes: predictions are bitwise identical
    for i in range(2):
        off.update(_conv_batch(i))
        on.update(_conv_batch(i))
    b = _conv_batch(70)
    assert np.array_equal(off.predict_dist(b), on.predict_dist(b))


def test_s2d_explicit_flag_wins():
    on = _build(BN_CONV_CONF.replace(
        "  kernel_size = 4", "  space_to_depth = 0\n"
        "  kernel_size = 4"), "graph_passes = space_to_depth\n")
    # the pass must not stamp over an explicit per-layer setting
    assert ("space_to_depth", "1") not in on.net_cfg.layercfg[0]
    assert on.net.layer_objs[0].s2d is False


def test_s2d_auto_single_definition():
    from cxxnet_tpu.ops.conv import _S2D_MAX_IN_CH, s2d_auto
    assert s2d_auto(3, 4, 11, 11) is True
    assert s2d_auto(3, 1, 3, 3) is False       # stride 1
    assert s2d_auto(8, 2, 3, 3) is False       # too many channels
    assert s2d_auto(3, 4, 3, 3) is False       # kernel < stride
    assert s2d_auto(3, 2, 3, 3, num_group=3) is False
    assert _S2D_MAX_IN_CH == 4


# ---------------------------------------------------------------------------
# round-trips + checkpoint compatibility
# ---------------------------------------------------------------------------
def test_transformed_cfg_roundtrips_to_dict(mlp_pair):
    from cxxnet_tpu.nnet.net_config import NetConfig
    _off, on = mlp_pair
    on.predict(_mlp_batch(50))
    for node in (on.net_cfg.num_nodes - 1,
                 on.net.node_index("fc1")):
        _n2, _pf, gm = on._build_infer_graph(node)
        back = NetConfig.from_dict(gm.cfg.to_dict())
        assert back.node_names == gm.cfg.node_names
        assert len(back.layers) == len(gm.cfg.layers)
        for a, b in zip(back.layers, gm.cfg.layers):
            assert a.structure_equals(b)


def test_netconfig_clone_is_deep(mlp_pair):
    off, _on = mlp_pair
    c = off.net_cfg.clone()
    c.layers.pop()
    c.layercfg[0].append(("x", "y"))
    assert len(off.net_cfg.layers) == len(c.layers) + 1
    assert ("x", "y") not in off.net_cfg.layercfg[0]


def test_checkpoint_bytes_and_resume_across_passes(tmp_path):
    """Folding never rewrites saved weights: training with the
    infer-stage passes on produces byte-identical checkpoints, and
    `continue = 1` resumes across graph_passes on<->off - BOTH
    directions in one matrix (the off-trained dir resumes with
    passes on, the on-trained dir resumes with passes off) -
    continuing the identical trajectory."""
    direction = "off_then_on"
    from cxxnet_tpu.tools.telemetry_smoke import write_synth_mnist
    from cxxnet_tpu.tools.pass_smoke import CONF
    d = str(tmp_path)
    write_synth_mnist(d, 192, 0, "train")
    write_synth_mnist(d, 96, 1, "test")
    with open(os.path.join(d, "t.conf"), "w") as f:
        f.write(CONF.format(d=d))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    passes_arg = "graph_passes=fold_conv_bn,dead_layer_elim"
    first = [] if direction == "off_then_on" else [passes_arg]
    second = [passes_arg] if direction == "off_then_on" else []

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
    run(ma, *first)
    run(mb, *second)
    # infer-stage passes leave the training byte-trajectory alone
    assert sha(ma, 2) == sha(mb, 2)
    # resume ACROSS the flag flip, both directions covered by the
    # parametrization; the continued round is identical either way
    run(ma, "continue=1", "num_round=3", "max_round=1", *second)
    run(mb, "continue=1", "num_round=3", "max_round=1", *first)
    assert sha(ma, 3) == sha(mb, 3)


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------
def test_server_calibrated_serves_folded(mlp_pair):
    from cxxnet_tpu.serve import Server
    _off, on = mlp_pair
    b = _mlp_batch(55)
    expect = on.predict_dist(b)  # calibrates + folds
    srv = Server(on, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv.warmup()
    srv.start()
    try:
        rows = srv.submit(b.data[:8]).result(timeout=60)
    finally:
        srv.stop()
    # folded inference is batch-composition-independent, so the
    # bucket-padded serve rows match the batch-at-a-time predict
    assert np.allclose(rows, expect[:8], rtol=1e-5, atol=1e-6)


def test_server_uncalibrated_warns_and_serves_unfolded(capsys):
    from cxxnet_tpu.serve import Server
    off = _build(BN_MLP_CONF)
    on = _build(BN_MLP_CONF, "graph_passes = fold_conv_bn\n")
    assert on.passes_need_calibration()
    srv = Server(on, max_batch=8, max_wait_ms=1.0, replicas=1)
    assert ("have no calibration stats"
            in capsys.readouterr().err)
    srv.warmup()
    srv.start()
    b = _mlp_batch(56, b=8)
    try:
        rows = srv.submit(b.data).result(timeout=60)
    finally:
        srv.stop()
    # unfolded serving: matches the passes-off trainer on the same
    # 8-row program shape (stats stay per-batch, batch == bucket)
    expect = off.infer_rows(*off.stage_infer_rows(b.data))
    assert np.allclose(rows, np.asarray(expect).reshape(8, -1),
                       rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------
def test_tuning_cache_roundtrip_and_validation(tmp_path):
    p = str(tmp_path / "tc.json")
    tuning.save_entry(p, "cpu", {"steps_per_dispatch": 4,
                                 "prefetch_stage": 2},
                      {"best_ips": 10.0}, "host")
    assert tuning.tuned_knobs(p, "cpu") == {
        "steps_per_dispatch": "4", "prefetch_stage": "2"}
    assert tuning.tuned_knobs(p, "tpu") == {}
    with pytest.raises(ValueError, match="untunable"):
        tuning.save_entry(p, "cpu", {"bogus_knob": 1})
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("not json")
    with pytest.raises(ConfigError, match="not JSON"):
        tuning.tuned_knobs(bad, "cpu")
    with open(bad, "w") as f:
        json.dump({"platforms": {"cpu": {"knobs": {"nope": 1}}}}, f)
    with pytest.raises(ConfigError, match="unknown knob"):
        tuning.tuned_knobs(bad, "cpu")


def test_save_entry_never_clobbers_unreadable_cache(tmp_path):
    """Merging into an EXISTING cache that fails validation must
    raise, not silently replace the file (which would destroy every
    other platform's tuned entries)."""
    p = str(tmp_path / "tc.json")
    tuning.save_entry(p, "tpu", {"steps_per_dispatch": 8})
    with open(p, "w") as f:
        f.write("not json at all")
    with pytest.raises(ConfigError):
        tuning.save_entry(p, "cpu", {"steps_per_dispatch": 2})
    with open(p) as f:
        assert f.read() == "not json at all"  # untouched


def test_int_knob_shared_apply_rule():
    knobs = {"steps_per_dispatch": "4", "prefetch_stage": "4.0"}
    assert tuning.int_knob(knobs, "steps_per_dispatch", set(), 1) == 4
    # explicit key wins
    assert tuning.int_knob(knobs, "steps_per_dispatch",
                           {"steps_per_dispatch"}, 1) is None
    # malformed skips, never raises
    assert tuning.int_knob(knobs, "prefetch_stage", set(), 0) is None
    # below-minimum skips
    assert tuning.int_knob({"serve_max_batch": "-1"},
                           "serve_max_batch", set(), 0) is None


def test_recalibration_evicts_stale_infer_executables():
    """Each recalibration bumps the fold epoch; the previous epoch's
    transformed graphs and compiled executables must be evicted or a
    reload/predict loop leaks one executable per reload."""
    on = _build(BN_MLP_CONF, "graph_passes = fold_conv_bn\n")
    b = _mlp_batch(0)
    on.predict(b)
    assert len(on._infer_graph_cache) == 1
    n_jits = len(on._infer_jits)
    import io
    buf = io.BytesIO()
    on.save_model(buf)
    for _ in range(3):
        buf.seek(0)
        on.copy_model_from(buf)   # drops stats -> next predict
        on.predict(b)             # recalibrates (epoch++)
    assert len(on._infer_graph_cache) == 1
    assert len(on._infer_jits) == n_jits
    assert all(k[1] == on._fold_epoch for k in on._infer_graph_cache)


def test_param_reload_retires_stale_folded_executables():
    """After a params reload (_init_state), the serving-path
    _infer_fn must NOT hand back the folded executable frozen with
    the OLD model's calibration statistics: the epoch bumps and the
    stale executables are evicted, so an uncalibrated infer builds
    the (safe) unfolded graph."""
    on = _build(BN_MLP_CONF, "graph_passes = fold_conv_bn\n")
    b = _mlp_batch(0)
    on.predict(b)  # calibrate + fold
    node = on.net_cfg.num_nodes - 1
    folded_fn = on._infer_fn(node)
    epoch = on._fold_epoch
    import io
    buf = io.BytesIO()
    on.save_model(buf)
    buf.seek(0)
    on.copy_model_from(buf)
    assert on._fold_epoch == epoch + 1
    assert on.passes_need_calibration()
    # the serving path now builds a FRESH (unfolded) executable
    # instead of re-dispatching the stale-stats folded one
    fresh_fn = on._infer_fn(node)
    assert fresh_fn is not folded_fn
    g, ge = on.stage_infer_rows(b.data)
    out = np.asarray(on.infer_rows(g, ge))
    # unfolded graph: matches a passes-off trainer with the same
    # weights on the same program shape
    off = _build(BN_MLP_CONF)
    buf.seek(0)
    off.copy_model_from(buf)
    g2, ge2 = off.stage_infer_rows(b.data)
    expect = np.asarray(off.infer_rows(g2, ge2))
    assert np.allclose(out, expect, rtol=1e-5, atol=1e-6)


def test_load_cache_type_errors_are_config_errors(tmp_path):
    for payload in (["cpu"], {"platforms": ["cpu"]},
                    {"platforms": {"cpu": "bogus"}},
                    {"platforms": {"cpu": {"knobs": ["x"]}}}):
        p = str(tmp_path / "bad.json")
        with open(p, "w") as f:
            json.dump(payload, f)
        with pytest.raises(ConfigError):
            tuning.load_cache(p)


def test_tuning_cache_trainer_defaults_and_explicit_win(tmp_path):
    p = str(tmp_path / "tc.json")
    tuning.save_entry(p, "cpu", {"steps_per_dispatch": 4,
                                 "serve_max_batch": 16})
    tr = _build(BN_MLP_CONF, f"tuning_cache = {p}\n")
    assert tr.steps_per_dispatch == 4
    assert tr.serve_max_batch == 16
    tr2 = _build(BN_MLP_CONF,
                 f"steps_per_dispatch = 2\ntuning_cache = {p}\n")
    assert tr2.steps_per_dispatch == 2  # explicit key wins
    assert tr2.serve_max_batch == 16


def test_tuning_cache_task_level_knobs(tmp_path):
    from cxxnet_tpu.main import LearnTask
    p = str(tmp_path / "tc.json")
    tuning.save_entry(p, "cpu", {"prefetch_stage": 2,
                                 "steps_per_dispatch": 4})
    task = LearnTask()
    task.set_param("tuning_cache", p)
    task._apply_tuning_cache()
    assert task.prefetch_stage == 2
    assert task.steps_per_dispatch == 4
    task2 = LearnTask()
    task2.set_param("prefetch_stage", "0")
    task2.set_param("tuning_cache", p)
    task2._apply_tuning_cache()
    assert task2.prefetch_stage == 0  # explicit key wins
    assert task2.steps_per_dispatch == 4


def test_tuned_trainer_trains_fused(tmp_path):
    """A tuned steps_per_dispatch default really drives the fused
    path: the update loop consumes chunks bitwise-identically to the
    explicit-key run."""
    p = str(tmp_path / "tc.json")
    tuning.save_entry(p, "cpu", {"steps_per_dispatch": 2})
    tr = _build(BN_MLP_CONF, f"tuning_cache = {p}\n")
    tr.update_chunk([_mlp_batch(0), _mlp_batch(1)])
    assert tr._step_counter == 2
