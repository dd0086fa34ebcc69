"""Graph-pass smoke: folding/pruning must change no product answer.

    python -m cxxnet_tpu.tools.pass_smoke [--out DIR] [--keep]

Trains a tiny fullc+batch_norm MLP once through the real CLI, then
proves the infer-stage graph passes (docs/GRAPH_PASSES.md) at the
product surface:

- **fold parity**: `task = pred` with `graph_passes =
  fold_conv_bn,dead_layer_elim` vs passes off, at `batch_size = 96`
  so the whole pred set is ONE batch - the fold's calibration batch
  IS the inference batch, making the fold a pure contraction-order
  rewrite: identical argmax on every row (line-identical prediction
  files) and tight-allclose `task = pred_raw` logits;
- **fold engagement**: the fold leg's event stream carries the
  `graph_passes calibrate` event, and an in-process trace shows the
  folded infer jaxpr contains ZERO rsqrt (the BN moment pipeline is
  gone) while the unfolded one contains it - the parity checks
  cannot pass vacuously with the passes silently off;
- **dead-layer elimination**: `task = extract` of the EARLY node
  fc1 produces byte-identical features with passes on vs off, and
  the pruned extract executable traces a strictly smaller program
  (fewer jaxpr equations, fewer matmuls). Finding recorded here:
  jax's jit already dead-code-eliminates the LOWERED module (the
  compiled HLO of an early-node infer matches with or without the
  dead tail), so the pass's artifact-level win is the traced
  program + trace/lowering latency; the smoke asserts the traced
  sizes and reports the lowered bytes.

PR-11 legs (docs/GRAPH_PASSES.md "Pass catalog"):

- **activation-fusion parity**: a second trained MLP whose head is
  fullc -> bias -> relu, `task = pred` with
  `graph_passes = dead_layer_elim,fuse_activation` vs passes off -
  identical argmax on every row + tight-allclose raw logits (the
  bias absorption is a pure add-reassociation);
- **1x1-merge parity**: an in-process child (same CPU environment)
  trains a conv -> 1x1-conv -> relu net and compares fused
  (`merge_conv_1x1,fuse_activation`) vs unfolded predict_dist rows,
  plus the one-conv-fewer traced-program claim;
- **per-layer-plan autotune**: tools/autotune.py on a tiny budget
  writes a schema-v2 cache (the plan JSON stays in --out as a CI
  artifact), then the SAME pred task replays it twice via
  `tuning_cache =` - identical output files (plans are
  deterministic pickups, not per-run noise).

PR-12 leg (docs/GRAPH_PASSES.md "Quantization"):

- **int8 quant leg**: the SAME trained fullc+bn MLP, `task = pred`
  with `graph_passes = fold_conv_bn,dead_layer_elim,quantize_int8`
  vs passes off - argmax agreement >= 95/96 rows (int8 is an
  approximation, so the pinned threshold prices its accuracy cost
  instead of demanding identity), a calibrate event carrying
  `quant_sites` on the quant leg's stream, and an in-process
  int8-engagement proof at the traced-jaxpr level (the
  GRAPH_PASSES.md key finding - wins are measured on the traced
  program): every data-path matmul of the quantized infer trace is
  int8 x int8 -> int32 with ZERO f32 data-path dots, while the float
  trace keeps f32 dots (vacuity guard). The verdict is written to
  `quant_report.json`, uploaded with the CI artifacts.

Folded and unfolded are different program shapes, and XLA:CPU
compiles a contraction per shape (~1 ULP between them): the legs
above compare argmax labels and raw rows by a tolerance; the one
byte-equality (the dle extract of fc1) is of a node both programs
compute at the same shape. Exit 0 iff all checks pass.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

from cxxnet_tpu.tools.telemetry_smoke import write_synth_mnist

CONF = """
data = train
iter = mnist
    path_img = "{d}/train-img.gz"
    path_label = "{d}/train-lbl.gz"
    shuffle = 1
iter = end
pred = {d}/out.txt
iter = mnist
    path_img = "{d}/test-img.gz"
    path_label = "{d}/test-lbl.gz"
iter = end

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
save_model = 1
num_round = 2
max_round = 2
eta = 0.3
metric = error
silent = 1
"""

_PASSES = "graph_passes=fold_conv_bn,dead_layer_elim"

# int8 quant leg: the fold pipeline + quantize_int8 on top
_QUANT_PASSES = "graph_passes=fold_conv_bn,dead_layer_elim," \
                "quantize_int8"
# pinned argmax-agreement floor: 95 of the 96 pred rows. int8 is an
# approximation - the threshold prices its accuracy cost instead of
# demanding identity (docs/GRAPH_PASSES.md "Quantization")
_QUANT_AGREE_MIN = 95

# activation-fusion leg: same data blocks, fullc -> bias -> relu head
CONF_ACT = CONF.replace(
    "layer[+1:bn1] = batch_norm:bn1\nlayer[+1:sg1] = tanh",
    "layer[+0] = bias:bs1\n  init_bias = 0.05\nlayer[+1:sg1] = relu")

_ACT_PASSES = "graph_passes=dead_layer_elim,fuse_activation"

# 1x1-merge leg (in-process child): conv -> 1x1 conv -> relu head
_MERGE_CONF = """
netconfig=start
layer[+1:c1] = conv:c1
  nchannel = 4
  kernel_size = 3
  pad = 1
layer[+1:c2] = conv:c2
  nchannel = 6
  kernel_size = 1
layer[+1:r1] = relu
layer[+1:fl] = flatten
layer[+1:fc] = fullc:fc
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 16
dev = cpu
eta = 0.1
silent = 1
seed = 5
"""


def _cpu_env() -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _run_cli(out_dir: str, *overrides: str,
             conf: str = "pass_smoke.conf"
             ) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.main",
         os.path.join(out_dir, conf), *overrides],
        env=_cpu_env(), capture_output=True, text=True, timeout=540)


def _run_merge_leg() -> dict:
    """Spawn the --merge-leg child and parse its JSON verdict."""
    r = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.tools.pass_smoke",
         "--merge-leg"],
        env=_cpu_env(), capture_output=True, text=True, timeout=540)
    for line in r.stdout.splitlines():
        if line.startswith("MERGELEG="):
            return json.loads(line[len("MERGELEG="):])
    return {"error": f"rc={r.returncode}: {r.stderr[-300:]}"}


def _lines(path):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return f.read().splitlines()


def _floats(lines):
    return np.asarray([[float(t) for t in ln.split()]
                       for ln in lines], np.float64)


def _program_sizes() -> dict:
    """In-process introspection: traced-jaxpr sizes of the extract
    and final-node infer executables with passes on vs off (fresh
    weights - program SIZE is weight-independent)."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    net_conf = CONF.split("netconfig=start")[1].split("netconfig=end")[0]
    base = ("netconfig=start" + net_conf + "netconfig=end\n"
            "input_shape = 1,1,36\nbatch_size = 32\ndev = cpu\n"
            "eta = 0.3\nsilent = 1\nseed = 3\n")

    def build(extra=""):
        tr = NetTrainer()
        for k, v in parse_config_string(base + extra):
            tr.set_param(k, v)
        tr.init_model()
        return tr

    def sizes(tr, node):
        data = np.zeros((32, 1, 1, 36), np.float32)
        gdata, gextras = tr.stage_infer_rows(data)
        fn = tr._infer_fn(node)
        traced = fn.trace(tr.state["params"], gdata, gextras)
        eqns = traced.jaxpr.jaxpr.eqns
        return {
            "eqns": len(eqns),
            "dots": sum(1 for e in eqns
                        if e.primitive.name == "dot_general"),
            "rsqrt": str(traced.jaxpr).count("rsqrt"),
            "lowered_bytes": len(fn.lower(
                tr.state["params"], gdata, gextras).as_text()),
        }

    off, on = build(), build(_PASSES.replace("=", " = ", 1))
    early = off.net.node_index("fc1")
    final = off.net_cfg.num_nodes - 1
    # fold the final-node executable: calibrate on a fixed batch
    from cxxnet_tpu.io.data import DataBatch
    rng = np.random.RandomState(5)
    on.calibrate_graph_passes(DataBatch(
        data=rng.rand(32, 1, 1, 36).astype(np.float32),
        label=rng.randint(0, 3, (32, 1)).astype(np.float32)))
    return {
        "extract_off": sizes(off, early),
        "extract_on": sizes(on, early),
        "final_off": sizes(off, final),
        "final_on": sizes(on, final),
    }


def _quant_engagement() -> dict:
    """In-process int8-engagement proof at the traced-jaxpr level
    (the GRAPH_PASSES.md key finding - wins are measured on the
    traced program, and the parity check alone could pass vacuously
    with quantize_int8 silently off): data-path dot dtypes of the
    quantized vs float infer executables, classified by the audit's
    own `_data_path_dots` (one definition)."""
    from cxxnet_tpu.analysis.jaxpr_audit import _data_path_dots
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    net_conf = CONF.split("netconfig=start")[1].split("netconfig=end")[0]
    base = ("netconfig=start" + net_conf + "netconfig=end\n"
            "input_shape = 1,1,36\nbatch_size = 32\ndev = cpu\n"
            "eta = 0.3\nsilent = 1\nseed = 3\n")

    def build(extra=""):
        tr = NetTrainer()
        for k, v in parse_config_string(base + extra):
            tr.set_param(k, v)
        tr.init_model()
        return tr

    off = build()
    on = build(_QUANT_PASSES.replace("=", " = ", 1))
    rng = np.random.RandomState(9)
    on.calibrate_graph_passes(DataBatch(
        data=rng.rand(32, 1, 1, 36).astype(np.float32),
        label=rng.randint(0, 3, (32, 1)).astype(np.float32)))
    node = off.net_cfg.num_nodes - 1

    def dots(tr):
        g, ge = tr.stage_infer_rows(np.zeros((32, 1, 1, 36),
                                             np.float32))
        return _data_path_dots(tr._infer_fn(node),
                               (tr.state["params"], g, ge), 32)

    i8_on, fp_on = dots(on)
    i8_off, fp_off = dots(off)
    return {"int8_dots_quant": i8_on, "float_dots_quant": fp_on,
            "int8_dots_float": i8_off, "float_dots_float": fp_off}


def merge_leg() -> dict:
    """--merge-leg child: train the conv -> 1x1-conv net a few steps,
    compare predict_dist fused (merge_conv_1x1 + fuse_activation) vs
    passes off, and count the traced data-path convs."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    def build(extra=""):
        tr = NetTrainer()
        for k, v in parse_config_string(_MERGE_CONF + extra):
            tr.set_param(k, v)
        tr.init_model()
        return tr

    def batch(i):
        r = np.random.RandomState(300 + i)
        return DataBatch(
            data=r.rand(16, 3, 8, 8).astype(np.float32),
            label=r.randint(0, 3, (16, 1)).astype(np.float32))

    off = build()
    on = build("graph_passes = dead_layer_elim,merge_conv_1x1,"
               "fuse_activation\n")
    for i in range(3):
        off.update(batch(i))
        on.update(batch(i))
    b = batch(90)
    po, pn = off.predict_dist(b), on.predict_dist(b)

    def convs(tr):
        node = tr.net_cfg.num_nodes - 1
        g, ge = tr.stage_infer_rows(np.zeros((16, 3, 8, 8),
                                             np.float32))
        eqns = tr._infer_fn(node).trace(
            tr.state["params"], g, ge).jaxpr.jaxpr.eqns
        return sum(1 for e in eqns
                   if e.primitive.name == "conv_general_dilated")

    return {
        "max_diff": float(np.abs(po - pn).max()),
        "allclose": bool(np.allclose(po, pn, rtol=5e-4, atol=1e-6)),
        "argmax_equal": bool((po.argmax(1) == pn.argmax(1)).all()),
        "convs_off": convs(off),
        "convs_on": convs(on),
    }


def run_smoke(out_dir: str) -> int:
    from cxxnet_tpu.telemetry.sink import read_jsonl
    write_synth_mnist(out_dir, 192, 0, "train")
    # 96 test instances + batch_size=96 on the inference legs = the
    # whole pred set is ONE batch (the fold calibration batch)
    write_synth_mnist(out_dir, 96, 1, "test")
    with open(os.path.join(out_dir, "pass_smoke.conf"), "w") as f:
        f.write(CONF.format(d=out_dir))
    mdir = os.path.join(out_dir, "models")
    model = os.path.join(mdir, "0002.model")
    p_off = os.path.join(out_dir, "pred_off.txt")
    p_on = os.path.join(out_dir, "pred_fold.txt")
    r_off = os.path.join(out_dir, "raw_off.txt")
    r_on = os.path.join(out_dir, "raw_fold.txt")
    x_off = os.path.join(out_dir, "extract_off.txt")
    x_on = os.path.join(out_dir, "extract_on.txt")
    log = os.path.join(out_dir, "pass_events.jsonl")

    train = _run_cli(out_dir, f"model_dir={mdir}")
    common = (f"model_in={model}", "batch_size=96")
    legs = {
        "pred_off": _run_cli(out_dir, "task=pred", *common,
                             f"pred={p_off}"),
        "pred_on": _run_cli(out_dir, "task=pred", *common,
                            f"pred={p_on}", _PASSES,
                            f"log_file={log}"),
        "raw_off": _run_cli(out_dir, "task=pred_raw", *common,
                            f"pred={r_off}"),
        "raw_on": _run_cli(out_dir, "task=pred_raw", *common,
                           f"pred={r_on}", _PASSES),
        "x_off": _run_cli(out_dir, "task=extract", *common,
                          "extract_node_name=fc1", f"pred={x_off}"),
        "x_on": _run_cli(out_dir, "task=extract", *common,
                         "extract_node_name=fc1", f"pred={x_on}",
                         _PASSES),
    }
    po, pn = _lines(p_off), _lines(p_on)
    ro, rn = _lines(r_off), _lines(r_on)
    xo, xn = _lines(x_off), _lines(x_on)
    raw_diff = float("nan")
    raw_close = False
    if ro and rn and len(ro) == len(rn):
        a, b = _floats(ro), _floats(rn)
        raw_diff = float(np.abs(a - b).max())
        # ~ULP contraction change through a %g-printed file: the
        # SERVING.md "Numerics fine print" tolerance class
        raw_close = bool(np.allclose(a, b, rtol=5e-4, atol=1e-6))
    events = ([e for e in read_jsonl(log)
               if e.get("kind") == "graph_passes"]
              if os.path.exists(log) else [])
    calibrated = any(e.get("op") == "calibrate" for e in events)
    sizes = _program_sizes()
    ex_off, ex_on = sizes["extract_off"], sizes["extract_on"]
    fin_off, fin_on = sizes["final_off"], sizes["final_on"]

    # --- activation-fusion parity leg (CLI, second trained MLP) ----
    with open(os.path.join(out_dir, "pass_smoke_act.conf"), "w") as f:
        f.write(CONF_ACT.format(d=out_dir))
    mdir_a = os.path.join(out_dir, "models_act")
    model_a = os.path.join(mdir_a, "0002.model")
    a_off, a_on = (os.path.join(out_dir, n)
                   for n in ("act_off.txt", "act_on.txt"))
    ar_off, ar_on = (os.path.join(out_dir, n)
                     for n in ("act_raw_off.txt", "act_raw_on.txt"))
    train_a = _run_cli(out_dir, f"model_dir={mdir_a}",
                       conf="pass_smoke_act.conf")
    common_a = (f"model_in={model_a}", "batch_size=96")
    act_legs = {
        "a_off": _run_cli(out_dir, "task=pred", *common_a,
                          f"pred={a_off}",
                          conf="pass_smoke_act.conf"),
        "a_on": _run_cli(out_dir, "task=pred", *common_a,
                         f"pred={a_on}", _ACT_PASSES,
                         conf="pass_smoke_act.conf"),
        "ar_off": _run_cli(out_dir, "task=pred_raw", *common_a,
                           f"pred={ar_off}",
                           conf="pass_smoke_act.conf"),
        "ar_on": _run_cli(out_dir, "task=pred_raw", *common_a,
                          f"pred={ar_on}", _ACT_PASSES,
                          conf="pass_smoke_act.conf"),
    }
    ao, an = _lines(a_off), _lines(a_on)
    aro, arn = _lines(ar_off), _lines(ar_on)
    act_diff, act_close = float("nan"), False
    if aro and arn and len(aro) == len(arn):
        fa, fb = _floats(aro), _floats(arn)
        act_diff = float(np.abs(fa - fb).max())
        act_close = bool(np.allclose(fa, fb, rtol=5e-4, atol=1e-6))

    # --- 1x1-merge parity leg (in-process child) -------------------
    merge = _run_merge_leg()

    # --- int8 quant leg: quantized pred vs float, same trained MLP -
    q_pred = os.path.join(out_dir, "pred_quant.txt")
    q_log = os.path.join(out_dir, "quant_events.jsonl")
    quant_leg = _run_cli(out_dir, "task=pred", *common,
                         f"pred={q_pred}", _QUANT_PASSES,
                         f"log_file={q_log}")
    qn = _lines(q_pred)
    q_agree = (sum(a == b for a, b in zip(po, qn))
               if po and qn and len(po) == len(qn) else 0)
    q_events = ([e for e in read_jsonl(q_log)
                 if e.get("kind") == "graph_passes"]
                if os.path.exists(q_log) else [])
    q_calibrated = any(e.get("op") == "calibrate"
                       and e.get("quant_sites") for e in q_events)
    quant = _quant_engagement()

    # --- per-layer-plan autotune leg: tiny grid, cache written then
    # replayed - the plan JSON stays in out_dir as the CI artifact
    plan_json = os.path.join(out_dir, "tuning_plan.json")
    at = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.tools.autotune",
         "--out", plan_json, "--budget-secs", "5", "--serve", "1",
         "--per-layer", "1"],
        env=_cpu_env(), capture_output=True, text=True,
        timeout=540)
    plan_blob = {}
    if os.path.exists(plan_json):
        with open(plan_json) as f:
            plan_blob = json.load(f)
    t1, t2 = (os.path.join(out_dir, n)
              for n in ("tuned_pred_1.txt", "tuned_pred_2.txt"))
    tuned_legs = [
        _run_cli(out_dir, "task=pred", *common, f"pred={t1}",
                 f"tuning_cache={plan_json}"),
        _run_cli(out_dir, "task=pred", *common, f"pred={t2}",
                 f"tuning_cache={plan_json}"),
    ]
    to1, to2 = _lines(t1), _lines(t2)

    checks = [
        ("train run completed",
         train.returncode == 0 and os.path.exists(model)),
        ("all inference legs completed",
         all(r.returncode == 0 for r in legs.values())),
        ("fold parity: identical argmax predictions (96 lines)",
         po is not None and po == pn and len(po) == 96),
        ("fold parity: tight-allclose pred_raw logits "
         f"(max diff {raw_diff:.2e})", raw_close),
        ("fold engaged: calibrate event on the fold leg's stream",
         calibrated),
        ("fold engaged: folded infer jaxpr has no rsqrt "
         f"({fin_on['rsqrt']} vs unfolded {fin_off['rsqrt']})",
         fin_on["rsqrt"] == 0 and fin_off["rsqrt"] > 0),
        ("fold: strictly smaller traced program "
         f"({fin_on['eqns']} vs {fin_off['eqns']} eqns)",
         fin_on["eqns"] < fin_off["eqns"]),
        ("dle: byte-identical extract of early node fc1",
         xo is not None and xo == xn and len(xo) == 96),
        ("dle: extract traces a strictly smaller program "
         f"({ex_on['eqns']} vs {ex_off['eqns']} eqns, "
         f"{ex_on['dots']} vs {ex_off['dots']} matmuls)",
         ex_on["eqns"] < ex_off["eqns"]
         and ex_on["dots"] < ex_off["dots"]),
        ("dle: lowered module no larger "
         f"({ex_on['lowered_bytes']} vs {ex_off['lowered_bytes']} B;"
         " equal = jax's own DCE, the documented finding)",
         ex_on["lowered_bytes"] <= ex_off["lowered_bytes"]),
        ("act-fusion legs completed",
         train_a.returncode == 0
         and all(r.returncode == 0 for r in act_legs.values())),
        ("act-fusion parity: identical argmax predictions (96 lines)",
         ao is not None and ao == an and len(ao) == 96),
        ("act-fusion parity: tight-allclose pred_raw logits "
         f"(max diff {act_diff:.2e})", act_close),
        ("1x1-merge parity: allclose rows + identical argmax "
         f"(max diff {merge.get('max_diff', float('nan')):.2e})",
         merge.get("allclose", False)
         and merge.get("argmax_equal", False)),
        ("1x1-merge: exactly one conv fewer in the traced program "
         f"({merge.get('convs_on')} vs {merge.get('convs_off')})",
         merge.get("convs_off", 0) >= 2
         and merge.get("convs_on") == merge.get("convs_off", 0) - 1),
        ("int8 leg completed", quant_leg.returncode == 0),
        (f"int8 argmax agreement >= {_QUANT_AGREE_MIN}/96 "
         f"(got {q_agree}/96)",
         qn is not None and len(qn) == 96
         and q_agree >= _QUANT_AGREE_MIN),
        ("int8 leg: calibrate event carries quant_sites",
         q_calibrated),
        ("int8 engaged: quantized trace is all-int8/int32 data-path "
         f"dots ({quant.get('int8_dots_quant')} int8, "
         f"{quant.get('float_dots_quant')} float)",
         quant.get("int8_dots_quant", 0) > 0
         and quant.get("float_dots_quant", 1) == 0),
        ("int8 vacuity guard: float trace keeps float data-path dots "
         f"({quant.get('float_dots_float')} float, "
         f"{quant.get('int8_dots_float')} int8)",
         quant.get("float_dots_float", 0) > 0
         and quant.get("int8_dots_float", 1) == 0),
        ("autotune leg: schema-v2 cache with a per-layer plan field",
         at.returncode == 0 and plan_blob.get("version") == 2
         and "layers" in plan_blob.get("platforms", {}).get("cpu", {})),
        ("autotune leg: cache replay is deterministic "
         "(two identical tuned pred files, 96 lines)",
         all(r.returncode == 0 for r in tuned_legs)
         and to1 is not None and to1 == to2 and len(to1) == 96),
    ]
    ok = True
    for label, passed in checks:
        print(f"  [{'ok' if passed else 'FAIL'}] {label}")
        ok = ok and bool(passed)
    if not ok:
        for tag, r in ([("train", train), ("train_act", train_a),
                        ("autotune", at), ("quant", quant_leg)]
                       + list(legs.items()) + list(act_legs.items())):
            if r.returncode != 0:
                print(f"--- {tag} stderr tail ---")
                print(r.stderr[-2000:])
        if "error" in merge:
            print(f"--- merge leg ---\n{merge['error']}")
    with open(os.path.join(out_dir, "pass_sizes.json"), "w") as f:
        json.dump(sizes, f, indent=1, sort_keys=True)
    # the quant-leg verdict rides the pass-smoke artifact upload
    with open(os.path.join(out_dir, "quant_report.json"), "w") as f:
        json.dump({"argmax_agree": q_agree, "rows": 96,
                   "agree_min": _QUANT_AGREE_MIN,
                   "calibrate_event": q_calibrated, **quant},
                  f, indent=1, sort_keys=True)
    print(f"pass_smoke: {'PASS' if ok else 'FAIL'} "
          f"(raw max diff {raw_diff:.2e}; extract traced "
          f"{ex_off['eqns']}->{ex_on['eqns']} eqns)")
    return 0 if ok else 1


def main() -> int:
    args = sys.argv[1:]
    if "--merge-leg" in args:
        print("MERGELEG=" + json.dumps(merge_leg()))
        return 0
    if "--out" in args:
        i = args.index("--out")
        if i + 1 >= len(args):
            print("usage: pass_smoke [--out DIR] [--keep]")
            return 2
        out = args[i + 1]
        os.makedirs(out, exist_ok=True)
        return run_smoke(out)
    if "--keep" in args:
        d = tempfile.mkdtemp(prefix="pass_smoke_")
        rc = run_smoke(d)
        print(f"pass_smoke: artifacts kept in {d}")
        return rc
    with tempfile.TemporaryDirectory() as d:
        return run_smoke(d)


if __name__ == "__main__":
    sys.exit(main())
