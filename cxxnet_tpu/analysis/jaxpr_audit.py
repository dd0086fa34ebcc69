"""graftlint tier 2: audit the LOWERED artifact, not the source.

Tier 1 trusts what the Python says; this tier inspects what we
actually dispatch (the TVM/Relay argument - PAPERS.md): trace the
real jitted executables of a representative trainer and assert on
the jaxpr + StableHLO + compiled HLO:

- **no-f64**: no float64 anywhere in the lowered module. An
  accidental x64 leak (np.float64 scalar, JAX_ENABLE_X64 drift)
  doubles bandwidth and silently changes trajectories.
- **no-host-callback**: no `custom_call` to a python/io callback and
  no infeed/outfeed - a host round-trip inside the step caps
  throughput at the host, invisibly.
- **donation-applied**: `donate_argnums` plumbed all the way through:
  donated params carry `tf.aliasing_output` in the lowered module AND
  the compiled HLO has a non-empty `input_output_alias` table. jax
  only *warns* when donation is dropped; this makes it a CI failure.
  (Non-donating executables are asserted alias-free, so the check
  cannot pass vacuously.)
- **no-captured-consts**: no weight-sized arrays baked into the
  executable as constants (params must arrive as ARGUMENTS - a
  captured weight re-embeds per compile and defeats donation).
- **recompile-audit**: the executable count stays bounded across a
  simulated round WITH a short final chunk - the PR 3 program-shape
  trap: `steps_per_dispatch=K` retraces once per distinct chunk
  length, so a round of 4+4+1 must cost exactly 2 `_train_chunk`
  lowering cache entries (K=4 and the K=1 flush), stable across
  rounds; padded short batches must NOT add `train_step`/eval
  entries.

- **zero-audit**: the ZeRO stage-2/3 and tensor-parallel executables
  (docs/parallel.md) audited on a REAL 8-device mesh (forced CPU host
  platform; in a subprocess when the current process has fewer
  devices): the compiled stage-2 HLO must contain a literal
  `reduce-scatter` of the gradients and an `all-gather` of the fresh
  weights, must NOT all-reduce any eligible weight's full-gradient
  shape (the accidental full-gradient materialization ZeRO removes),
  and every eligible weight's shard shape must appear as a
  reduce-scatter output (the update really runs on 1/N shards).
  Stage 3 additionally proves the weights are STORED sharded: no
  eligible full weight shape among the entry parameters - full
  shapes appear only as all-gather results (the just-in-time
  per-layer gathers). This closes the audit-coverage gap for the
  parallel executables the ROADMAP called out.

- **serve-audit**: the continuous-batching serving layer
  (serve/server.py, docs/SERVING.md) audited at the executable level:
  after warmup the inference executable's compiled-program count
  equals the bucket count and stays FLAT over 100 mixed-size
  requests (zero steady-state recompiles - the serving SLO depends
  on it); each bucket executable is additionally put through the
  artifact checks with donation asserted ABSENT (a donated param
  would free the weights a concurrent replica still needs).

- **pass-audit**: the graph-pass pipeline (nnet/passes.py,
  docs/GRAPH_PASSES.md) audited at the traced-program level on a
  fullc+batch_norm trainer with
  `graph_passes = fold_conv_bn,dead_layer_elim`: the FOLDED
  infer_step jaxpr contains no BN moment/variance pipeline (zero
  rsqrt - the stats are frozen host constants - and strictly fewer
  equations than the unfolded trace, which is asserted to contain
  the rsqrt so the check cannot pass vacuously); the dead-layer-
  eliminated early-node extract contains none of the pruned
  subgraph's matmuls; and the fold adds ZERO new steady-state
  executables - after the one-time calibration, repeated full+short
  padded predicts and extracts leave every per-node infer cache at
  exactly 1 (the recompile audit stays flat).

- **quant-audit**: the int8 post-training-quantization path
  (quantize_int8 pass + ops/int8.py, docs/GRAPH_PASSES.md
  "Quantization") audited at the traced-program level: the quantized
  infer trace's DATA-PATH matmuls (output leading dim = the batch)
  all carry int8 operand dtypes with int32 accumulation and ZERO
  float data-path dots remain, vacuity-guarded against the float
  trace (which must carry the f32 dots, or the comparison proves
  nothing - the GRAPH_PASSES.md key finding that wins are measured
  at the traced-jaxpr level); an explicit `layer_quant = float` pin
  keeps exactly its layer's dot float; and quantized SERVING stays
  zero-recompile - calibrate first, then a warmed Server's
  executable count equals the bucket count and stays flat over a
  mixed-size request storm, with each bucket executable's trace
  int8-engaged.

Audited executables: `train_step`, `_train_chunk` (K=1 and K=4), the
eval pair (`eval_step`, `eval_metric_step`) and the dedicated
`infer_step` (predict/extract/serve share it), over the tiny-MLP
config the fused-dispatch smoke uses, plus the zero-audit set
(stage-2 `train_step`/`_train_chunk[K=4]` on `data:8`, stage-3
`train_step` on `data:8`, stage-2 `train_step` on `data:4,model:2`),
the serve bucket set, the pass-audit pair and the quant-audit set.
Run under `JAX_PLATFORMS=cpu` in CI; the checks are artifact-level,
so they hold for any backend that compiles the same programs.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# weight-sized constant bound: the tiny net's legitimate lowering
# constants (iota tables, padding masks) stay well under this; its
# smallest weight (fc1: 36x16 f32) is 2.3 KiB and a captured one
# grows with the model - 4 KiB separates the two populations
_CONST_BYTES_MAX = 4096

_CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
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
eval_train = 1
silent = 1
seed = 7
"""


def _make_trainer():
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    tr = NetTrainer()
    for k, v in parse_config_string(_CONF):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _batch(i: int, b: int = 32):
    from cxxnet_tpu.io.data import DataBatch
    rng = np.random.RandomState(100 + i)
    return DataBatch(
        data=rng.rand(b, 1, 1, 36).astype(np.float32),
        label=(rng.randint(0, 3, size=(b, 1))
               .astype(np.float32)))


# ---------------------------------------------------------------------------
# artifact checks
# ---------------------------------------------------------------------------
def _check(target: str, check: str, ok: bool,
           detail: str = "") -> Dict[str, Any]:
    return {"target": target, "check": check, "ok": bool(ok),
            "detail": detail}


_F64_RE = re.compile(r"\bf64\b|xf64>|tensor<f64>")
_CALLBACK_RE = re.compile(
    r"custom_call[^\n]*(callback|py_func)|infeed|outfeed",
    re.IGNORECASE)


def _audit_executable(target: str, jitfn, args: Tuple,
                      donated: bool) -> List[Dict[str, Any]]:
    checks: List[Dict[str, Any]] = []
    lowered = jitfn.lower(*args)
    text = lowered.as_text()

    hits = _F64_RE.findall(text)
    checks.append(_check(
        target, "no-f64", not hits,
        f"{len(hits)} f64 type(s) in lowered module" if hits else ""))

    cb = _CALLBACK_RE.search(text)
    checks.append(_check(
        target, "no-host-callback", cb is None,
        f"host transfer in lowered module: {cb.group(0)[:60]}"
        if cb else ""))

    n_alias = text.count("tf.aliasing_output")
    ctext = lowered.compile().as_text()
    has_compiled_alias = ("input_output_alias={" in ctext
                          and "input_output_alias={}" not in ctext)
    if donated:
        checks.append(_check(
            target, "donation-applied",
            n_alias > 0 and has_compiled_alias,
            f"{n_alias} aliased params in lowered module; compiled "
            f"alias table {'present' if has_compiled_alias else 'MISSING'}"))
    else:
        checks.append(_check(
            target, "no-spurious-donation",
            n_alias == 0,
            f"{n_alias} aliased params on a non-donating executable"
            if n_alias else ""))

    consts = list(jitfn.trace(*args).jaxpr.consts)
    big = [c for c in consts
           if getattr(c, "nbytes", 0) > _CONST_BYTES_MAX]
    checks.append(_check(
        target, "no-captured-consts", not big,
        (f"{len(big)} constant(s) over {_CONST_BYTES_MAX} B captured "
         f"(largest {max(c.nbytes for c in big)} B) - weights must "
         "be arguments") if big else
        f"{len(consts)} small consts"))
    return checks


# ---------------------------------------------------------------------------
# zero-audit: ZeRO stage-2/3 + tensor-parallel executables
# ---------------------------------------------------------------------------
def _hlo_lhs(txt: str, op: str) -> List[str]:
    """LHS (shapes incl. combined-tuple members) of every `op`
    instruction in an HLO text dump."""
    out = []
    for line in txt.splitlines():
        s = line.strip()
        if f" {op}(" in s and "=" in s:
            out.append(s.split(f" {op}(")[0])
    return out


def _shape_tokens(tr, mesh_sizes) -> Tuple[set, set]:
    """(device_full, device_shard) HLO shape tokens of every
    zero-ELIGIBLE weight: full = the per-device shape with the zero
    cut restored (global divided by any tensor-parallel placement),
    shard = full with the eligible dim further cut by the data-axis
    size. Computed from the same parallel/sharding.py helpers the
    trainer compiles with, so the audit cannot drift from the rule."""
    import jax
    from cxxnet_tpu.parallel.sharding import zero_partition_dims
    dims = zero_partition_dims(tr.mesh, tr.net, tr._pshard)
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    dsize = mesh_sizes.get("data", 1)
    full, shard = set(), set()
    for lk, d in dims.items():
        for pn, i in d.items():
            if i is None:
                continue
            gshape = list(shapes[lk][pn].shape)
            spec = list(tr._pshard[lk][pn].spec)
            spec += [None] * (len(gshape) - len(spec))
            dev_full = [s // mesh_sizes.get(ax, 1) if ax else s
                        for s, ax in zip(gshape, spec)]
            dev_shard = list(dev_full)
            dev_shard[i] //= dsize
            full.add("f32[" + ",".join(map(str, dev_full)) + "]")
            shard.add("f32[" + ",".join(map(str, dev_shard)) + "]")
    return full, shard


def _zero_collective_checks(target: str, txt: str, full: set,
                            shard: set, exact: bool,
                            stored_sharded: bool
                            ) -> List[Dict[str, Any]]:
    checks = []
    rs = _hlo_lhs(txt, "reduce-scatter")
    ag = _hlo_lhs(txt, "all-gather")
    ar = _hlo_lhs(txt, "all-reduce")
    checks.append(_check(
        target, "zero-reduce-scatter-present", bool(rs),
        "" if rs else "no reduce-scatter in compiled HLO - gradients "
        "are not being reduce-scattered"))
    gathered = {tok for tok in full if any(tok in l for l in ag)}
    checks.append(_check(
        target, "zero-weight-all-gather-present",
        bool(gathered) if not exact else gathered == full,
        f"all-gather restores {len(gathered)}/{len(full)} eligible "
        f"weight shapes" if gathered != full else ""))
    bad_ar = {tok for tok in full if any(tok in l for l in ar)}
    checks.append(_check(
        target, "zero-no-full-grad-allreduce", not bad_ar,
        f"full-gradient all-reduce of shapes {sorted(bad_ar)} - the "
        f"gradient materializes unsharded" if bad_ar else ""))
    if exact:
        missing = {tok for tok in shard
                   if not any(tok in l for l in rs)}
        checks.append(_check(
            target, "zero-sharded-update", not missing,
            f"shard shapes {sorted(missing)} missing from "
            f"reduce-scatter outputs - their update is not running "
            f"on 1/N shards" if missing else ""))
    if stored_sharded:
        entry = txt.split("ENTRY", 1)[-1]
        params = _hlo_lhs(entry, "parameter")
        leaked = {tok for tok in full
                  if any(tok in l for l in params)}
        checks.append(_check(
            target, "zero3-params-stored-sharded", not leaked,
            f"entry parameters carry full weight shapes "
            f"{sorted(leaked)} - stage 3 must store shards between "
            f"steps" if leaked else ""))
    return checks


def zero_audit_checks() -> List[Dict[str, Any]]:
    """Build the stage-2/3 and tensor-parallel trainers on the live
    mesh and audit their compiled HLO. Requires >= 8 devices (the
    run_audit entry arranges that via subprocess when needed)."""
    import jax
    from cxxnet_tpu.parallel import distributed
    checks: List[Dict[str, Any]] = []
    rng = jax.random.PRNGKey(0)

    def build(extra: str):
        from cxxnet_tpu.nnet.trainer import NetTrainer
        from cxxnet_tpu.utils.config import parse_config_string
        tr = NetTrainer()
        for k, v in parse_config_string(_CONF + extra):
            tr.set_param(k, v)
        tr.init_model()
        sizes = dict(zip(tr.mesh.axis_names, tr.mesh.devices.shape))
        full, shard = _shape_tokens(tr, sizes)
        return tr, full, shard

    # stage 2 on a pure data:8 mesh - exact coverage assertions
    tr, full, shard = build("mesh = data:8\nzero_stage = 2\n")
    sb = tr.stage_batch(_batch(0))
    args = (tr.state, sb.data, sb.extras, sb.labels, sb.mask, rng)
    txt = tr._train_step.lower(*args).compile().as_text()
    checks += _zero_collective_checks(
        "zero2[data:8]/train_step", txt, full, shard, exact=True,
        stored_sharded=False)
    checks += _audit_executable(
        "zero2[data:8]/train_step", tr._train_step, args, donated=True)
    # fused composition: the K=4 chunk must keep the same collectives
    chunk = tr.stage_chunk([_batch(i) for i in range(4)])
    step_idx = distributed.put_global(
        np.arange(4, dtype=np.int32), tr._replicated)
    ctxt = tr._train_chunk.lower(
        tr.state, chunk.data, chunk.extras, chunk.labels, chunk.mask,
        step_idx, rng).compile().as_text()
    checks += _zero_collective_checks(
        "zero2[data:8]/train_chunk[K=4]", ctxt, full, shard,
        exact=True, stored_sharded=False)

    # stage 3: params stored sharded, gathered just-in-time
    tr3, full3, shard3 = build("mesh = data:8\nzero_stage = 3\n")
    sb3 = tr3.stage_batch(_batch(0))
    txt3 = tr3._train_step.lower(
        tr3.state, sb3.data, sb3.extras, sb3.labels, sb3.mask,
        rng).compile().as_text()
    checks += _zero_collective_checks(
        "zero3[data:8]/train_step", txt3, full3, shard3, exact=True,
        stored_sharded=True)

    # tensor-parallel composition: collectives present, no eligible
    # full-gradient all-reduce (activation all-reduces over 'model'
    # are legitimate, so coverage stays presence-level here)
    trt, fullt, shardt = build(
        "mesh = data:4,model:2\nzero_stage = 2\n")
    sbt = trt.stage_batch(_batch(0))
    txtt = trt._train_step.lower(
        trt.state, sbt.data, sbt.extras, sbt.labels, sbt.mask,
        rng).compile().as_text()
    checks += _zero_collective_checks(
        "zero2[data:4,model:2]/train_step", txtt, fullt, shardt,
        exact=False, stored_sharded=False)
    return checks


def _zero_audit(checks: List[Dict[str, Any]]) -> None:
    """Run zero_audit_checks on >= 8 devices: in-process when this
    process already has them (the test suite's forced host platform),
    else in a CPU subprocess with 8 forced devices (the CI CLI). A
    subprocess failure is a FAILING check - the gate must not pass
    vacuously."""
    import jax
    if (jax.default_backend() == "cpu"
            and jax.device_count() >= 8
            and jax.process_count() == 1):
        checks.extend(zero_audit_checks())
        return
    import json
    import os
    import subprocess
    import sys
    flags = [t for t in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in t]
    flags.append("--xla_force_host_platform_device_count=8")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=" ".join(flags))
    code = ("import json\n"
            "from cxxnet_tpu.analysis.jaxpr_audit import "
            "zero_audit_checks\n"
            "print('ZEROAUDIT=' + json.dumps(zero_audit_checks()))\n")
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=540)
        payload = [line for line in r.stdout.splitlines()
                   if line.startswith("ZEROAUDIT=")]
        if r.returncode != 0 or not payload:
            checks.append(_check(
                "zero-audit", "subprocess", False,
                f"rc={r.returncode}: {r.stderr[-300:]}"))
            return
        checks.extend(json.loads(payload[0][len("ZEROAUDIT="):]))
    except (subprocess.TimeoutExpired, OSError) as e:
        checks.append(_check("zero-audit", "subprocess", False,
                             str(e)[:300]))


# ---------------------------------------------------------------------------
# recompile audit (the PR 3 program-shape trap)
# ---------------------------------------------------------------------------
def _cache_size(jitfn) -> Optional[int]:
    fn = getattr(jitfn, "_cache_size", None)
    return fn() if callable(fn) else None


# ---------------------------------------------------------------------------
# serve audit: warmed bucket executables, zero steady-state recompiles
# ---------------------------------------------------------------------------
def _serve_audit(checks: List[Dict[str, Any]]) -> Dict[str, int]:
    """Build the continuous-batching server over a FRESH tiny trainer
    (predict would pre-populate the shared infer cache and muddy the
    bucket count) and assert the serving SLO's compile-time story:
    bucket executables all compiled at warmup, none after."""
    from cxxnet_tpu.serve import Server
    tr = _make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=2)
    if _cache_size(srv._fn) is None:
        checks.append(_check(
            "serve", "cache-size-api", False,
            "jit._cache_size unavailable on this jax version"))
        return {}
    srv.warmup()
    n_warm = _cache_size(srv._fn)
    checks.append(_check(
        "serve", "bucket-executables==bucket-count",
        n_warm == len(srv.buckets),
        f"cache={n_warm} buckets={list(srv.buckets)}"))
    # 100 mixed-size requests over every bucket: the executable count
    # must not move (steady-state serving performs zero recompiles)
    srv.start()
    rng = np.random.RandomState(7)
    futs = [srv.submit(rng.rand(1 + int(rng.randint(8)), 1, 1, 36)
                       .astype(np.float32))
            for _ in range(100)]
    for f in futs:
        f.result(timeout=120)
    stats = srv.stop()
    n_after = _cache_size(srv._fn)
    checks.append(_check(
        "serve", "no-recompile-over-100-mixed-requests",
        n_after == n_warm,
        f"cache {n_warm} -> {n_after} after {stats['batches']} "
        f"batches / {stats['rows']} rows"))
    checks.append(_check(
        "serve", "no-dispatch-errors", stats["errors"] == 0,
        f"{stats['errors']} dispatch errors"))
    # executable introspection plane (telemetry/flight.py,
    # docs/OBSERVABILITY.md "/executables"): warmup must have
    # registered exactly one registry entry per bucket executable,
    # each stamped with its compile wall-time and counting the storm's
    # dispatches - an empty or stale registry would blind the stall
    # dump to the serving path
    from cxxnet_tpu import telemetry
    by_fp = {e["fingerprint"]: e
             for e in telemetry.executables().snapshot()}
    want = {b: srv._exec_fp.get(b) for b in srv.buckets}
    missing = [b for b, fp in want.items() if fp not in by_fp]
    checks.append(_check(
        "serve", "executables-registry-lists-buckets", not missing,
        f"buckets missing from /executables registry: {missing}"
        if missing else f"{len(want)} bucket entries registered"))
    if not missing:
        no_compile = [b for b, fp in want.items()
                      if by_fp[fp]["compile_s"] is None]
        checks.append(_check(
            "serve", "executables-compile-walltime-recorded",
            not no_compile,
            f"buckets with no compile_s: {no_compile}" if no_compile
            else ""))
        n_disp = sum(by_fp[fp]["dispatches"] for fp in want.values())
        checks.append(_check(
            "serve", "executables-dispatch-counts-accumulate",
            n_disp >= stats["batches"],
            f"registry counts {n_disp} dispatches over "
            f"{stats['batches']} storm batches"))
    # artifact checks per bucket executable - donation asserted ABSENT
    # (a donated weight buffer would be freed under a concurrent
    # replica's dispatch); run AFTER the flatness checks so .lower()
    # cannot perturb the counted cache
    for b in srv.buckets:
        data = np.zeros((b, 1, 1, 36), np.float32)
        gdata, gextras = tr.stage_infer_rows(data, ())
        checks += _audit_executable(
            f"serve[b={b}]", srv._fn,
            (tr.state["params"], gdata, gextras), donated=False)
    return {"serve_infer_warm": n_warm, "serve_infer_after": n_after}


_CONF_BN = _CONF.replace(
    "layer[+1:sg1] = tanh",
    "layer[+1:bn1] = batch_norm:bn1\nlayer[+1:sg1] = tanh")


def _traced(jitfn, args):
    """(jaxpr_text, eqn_count, dot_count) of a jit's PRE-DCE trace -
    the program the pass pipeline is responsible for (jax's own jit
    DCE already prunes the LOWERED module, so lowered-size checks
    would pass with the passes off; measured in pass_smoke)."""
    tr = jitfn.trace(*args)
    eqns = tr.jaxpr.jaxpr.eqns
    return (str(tr.jaxpr), len(eqns),
            sum(1 for e in eqns
                if e.primitive.name == "dot_general"))


def _traced_prims(jitfn, args) -> Tuple[int, Dict[str, int]]:
    """(eqn_count, {primitive: count}) of a jit's PRE-DCE trace."""
    eqns = jitfn.trace(*args).jaxpr.jaxpr.eqns
    prims: Dict[str, int] = {}
    for e in eqns:
        prims[e.primitive.name] = prims.get(e.primitive.name, 0) + 1
    return len(eqns), prims


def _pass_audit(checks: List[Dict[str, Any]]) -> Dict[str, int]:
    """Audit the graph-pass pipeline: build the BN trainer twice
    (passes off / fold+dle on), calibrate the fold on a fixed batch,
    and assert the docstring's pass-audit contract."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    def build(extra: str = ""):
        tr = NetTrainer()
        for k, v in parse_config_string(_CONF_BN + extra):
            tr.set_param(k, v)
        tr.init_model()
        return tr

    off = build()
    on = build("graph_passes = fold_conv_bn,dead_layer_elim\n")
    on.calibrate_graph_passes(_batch(0))
    final = on.net_cfg.num_nodes - 1
    early = on.net.node_index("fc1")
    data = np.zeros((32, 1, 1, 36), np.float32)
    gdata, gextras = on.stage_infer_rows(data)
    fold_fn = on._infer_fn(final)
    args_on = (on.state["params"], gdata, gextras)
    gdo, geo = off.stage_infer_rows(data)
    args_off = (off.state["params"], gdo, geo)
    ftxt, feqns, fdots = _traced(fold_fn, args_on)
    utxt, ueqns, udots = _traced(off._infer_fn(final), args_off)
    checks.append(_check(
        "passes/fold", "no-bn-moment-ops",
        "rsqrt" not in ftxt and "rsqrt" in utxt,
        f"folded rsqrt={ftxt.count('rsqrt')}, unfolded "
        f"rsqrt={utxt.count('rsqrt')} (unfolded must carry it or "
        "this check is vacuous)"))
    checks.append(_check(
        "passes/fold", "strictly-smaller-traced-program",
        feqns < ueqns and fdots == udots,
        f"folded {feqns} eqns/{fdots} dots vs unfolded {ueqns}/"
        f"{udots} (fold removes the BN pipeline, never a matmul)"))
    dtxt, deqns, ddots = _traced(on._infer_fn(early), args_on)
    checks.append(_check(
        "passes/dle", "pruned-subgraph-absent",
        ddots == 1 and deqns < ueqns,
        f"early-node extract traces {ddots} matmul(s)/{deqns} eqns "
        f"(full graph: {udots}/{ueqns}) - the dead fc2/softmax tail "
        "must not be traced"))
    sizes: Dict[str, int] = {}
    if _cache_size(fold_fn) is None:
        checks.append(_check(
            "passes", "cache-size-api", False,
            "jit._cache_size unavailable on this jax version"))
        return sizes
    # steady state: full + padded-short predicts and repeated
    # extracts add no executables past the per-shape compile
    on.predict(_batch(70))
    on.predict(_batch(71, b=20))
    on.predict(_batch(72))
    on.extract_feature(_batch(73), "fc1")
    on.extract_feature(_batch(74, b=20), "fc1")
    sizes["pass_infer_final"] = _cache_size(on._infer_fn(final))
    sizes["pass_infer_early"] = _cache_size(on._infer_fn(early))
    checks.append(_check(
        "passes/fold", "zero-new-steady-state-executables",
        sizes["pass_infer_final"] == 1
        and sizes["pass_infer_early"] == 1,
        f"final-node cache={sizes['pass_infer_final']}, early-node "
        f"cache={sizes['pass_infer_early']} after full+short "
        "predicts and extracts (want 1 each - padding keeps the "
        "program shape static, folding adds nothing per dispatch)"))
    _new_pattern_audit(checks)
    return sizes


# fuse_activation workload: fullc + separate bias layer + relu - the
# chain whose standalone elementwise equations the fused node removes
_CONF_ACT = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+0] = bias:bs1
  init_bias = 0.05
layer[+1:r1] = relu
layer[+1:fc2] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,36
batch_size = 32
dev = cpu
eta = 0.3
silent = 1
seed = 7
"""

# merge_conv_1x1 workload: 3x3 conv feeding a 1x1 conv
_CONF_1X1 = """
netconfig=start
layer[+1:c1] = conv:c1
  nchannel = 4
  kernel_size = 3
  pad = 1
layer[+1:c2] = conv:c2
  nchannel = 6
  kernel_size = 1
layer[+1:fl] = flatten
layer[+1:fc] = fullc:fc
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 8
dev = cpu
eta = 0.1
silent = 1
seed = 5
"""

# cse_share workload: a primary and its share[...] sibling reading the
# SAME input node - provably identical, the dedupable duplicate
_CONF_CSE = """
netconfig=start
layer[0->a] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[0->b] = share[fc1]
layer[a,b->c] = concat
layer[+1:fc2] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,12
batch_size = 8
dev = cpu
eta = 0.1
silent = 1
seed = 3
"""


def _new_pattern_audit(checks: List[Dict[str, Any]]) -> None:
    """Pass-audit legs for the PR-11 patterns (fuse_activation,
    merge_conv_1x1, cse_share), each asserted at the traced-jaxpr
    level against the same pipeline WITHOUT the pattern pass, and
    each vacuity-guarded: the off-trace must actually contain the
    pattern (the rsqrt-style guard) or the comparison proves
    nothing."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    def build(conf, extra=""):
        tr = NetTrainer()
        for k, v in parse_config_string(conf + extra):
            tr.set_param(k, v)
        tr.init_model()
        return tr

    def traces(conf, passes, shape):
        off = build(conf, "graph_passes = dead_layer_elim\n")
        on = build(conf, f"graph_passes = dead_layer_elim,{passes}\n")
        node = on.net_cfg.num_nodes - 1
        data = np.zeros(shape, np.float32)
        g, ge = on.stage_infer_rows(data)
        g2, ge2 = off.stage_infer_rows(data)
        e_on, p_on = _traced_prims(on._infer_fn(node),
                                   (on.state["params"], g, ge))
        e_off, p_off = _traced_prims(off._infer_fn(node),
                                     (off.state["params"], g2, ge2))
        gm_on = on._build_infer_graph(node)[2]
        gm_off = off._build_infer_graph(node)[2]
        return e_off, p_off, gm_off, e_on, p_on, gm_on

    # fuse_activation: strictly fewer equations, equal matmul count
    e_off, p_off, gm_off, e_on, p_on, gm_on = traces(
        _CONF_ACT, "fuse_activation", (32, 1, 1, 36))
    checks.append(_check(
        "passes/fuse_activation", "pattern-matched",
        len(gm_on.cfg.layers) < len(gm_off.cfg.layers),
        f"fused graph keeps {len(gm_on.cfg.layers)} layers vs "
        f"{len(gm_off.cfg.layers)} unfused - the bias+relu chain "
        "must actually fuse (vacuity guard)"))
    checks.append(_check(
        "passes/fuse_activation", "fewer-eqns-equal-matmuls",
        e_on < e_off and p_on.get("dot_general", 0)
        == p_off.get("dot_general", 0),
        f"fused {e_on} eqns/{p_on.get('dot_general', 0)} dots vs "
        f"unfused {e_off}/{p_off.get('dot_general', 0)} (fusion "
        "removes the standalone elementwise eqns, never a matmul)"))

    # merge_conv_1x1: exactly one data-path conv fewer
    _e_off, p_off, _gm_off, _e_on, p_on, gm_on = traces(
        _CONF_1X1, "merge_conv_1x1", (8, 3, 8, 8))
    co = p_off.get("conv_general_dilated", 0)
    cn = p_on.get("conv_general_dilated", 0)
    checks.append(_check(
        "passes/merge_conv_1x1", "one-conv-fewer",
        co >= 2 and cn == co - 1 and gm_on.merges,
        f"merged trace carries {cn} convs vs {co} unmerged (want "
        "exactly one fewer, with the unmerged trace carrying >= 2 - "
        "the vacuity guard - and a recorded merge site)"))

    # cse_share: the duplicate share's matmul disappears
    e_off, p_off, gm_off, e_on, p_on, gm_on = traces(
        _CONF_CSE, "cse_share", (8, 1, 1, 12))
    do = p_off.get("dot_general", 0)
    dn = p_on.get("dot_general", 0)
    checks.append(_check(
        "passes/cse_share", "duplicate-matmul-deduped",
        do >= 3 and dn == do - 1 and e_on < e_off
        and len(gm_on.cfg.layers) < len(gm_off.cfg.layers),
        f"deduped trace carries {dn} dots/{e_on} eqns vs {do}/"
        f"{e_off} undeduped (want one dot fewer; the undeduped "
        "trace must carry the duplicate - vacuity guard)"))

    # elim_reshape: the flatten layer's reshape equation disappears,
    # matmul/conv counts unchanged (pure graph cleanup)
    e_off, p_off, gm_off, e_on, p_on, gm_on = traces(
        _CONF_1X1, "elim_reshape", (8, 3, 8, 8))
    ro = p_off.get("reshape", 0)
    rn = p_on.get("reshape", 0)
    checks.append(_check(
        "passes/elim_reshape", "fewer-eqns-equal-matmuls",
        e_on < e_off and rn == ro - 1 and ro >= 1
        and p_on.get("dot_general", 0) == p_off.get("dot_general", 0)
        and p_on.get("conv_general_dilated", 0)
        == p_off.get("conv_general_dilated", 0)
        and len(gm_on.cfg.layers) < len(gm_off.cfg.layers),
        f"elim trace carries {rn} reshapes/{e_on} eqns vs {ro}/"
        f"{e_off} (want one reshape fewer at equal matmul/conv "
        "counts; the off trace must carry the flatten - vacuity "
        "guard)"))


def _data_path_dots(jitfn, args, batch: int) -> Tuple[int, int]:
    """(int8_dots, float_dots) among the DATA-PATH contractions of a
    jit's PRE-DCE trace: dot_general/conv_general_dilated equations
    whose output's leading dim is the batch. Weight-side dots (the
    1x1-merge contraction, fold arithmetic) are weight-shaped and
    excluded - quantization's claim is about the data path only."""
    eqns = jitfn.trace(*args).jaxpr.jaxpr.eqns
    i8 = fp = 0
    for e in eqns:
        if e.primitive.name not in ("dot_general",
                                    "conv_general_dilated"):
            continue
        out = e.outvars[0].aval
        if not out.shape or out.shape[0] != batch:
            continue
        dts = {str(v.aval.dtype) for v in e.invars}
        if dts == {"int8"} and str(out.dtype) == "int32":
            i8 += 1
        elif any(d.startswith(("float", "bfloat")) for d in dts):
            fp += 1
    return i8, fp


_QUANT_PASSES = "dead_layer_elim,fold_conv_bn,quantize_int8"


def _quant_audit(checks: List[Dict[str, Any]]) -> Dict[str, int]:
    """Audit the int8 PTQ path (module docstring): int8 operands +
    int32 accumulation on every eligible data-path matmul of the
    quantized trace, zero float data-path dots (vacuity-guarded
    against the float trace), `layer_quant = float` pin honored, and
    quantized serving zero-recompile after calibration."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.serve import Server
    from cxxnet_tpu.utils.config import parse_config_string

    def build(extra: str = "", conf: str = _CONF_BN):
        tr = NetTrainer()
        for k, v in parse_config_string(conf + extra):
            tr.set_param(k, v)
        tr.init_model()
        return tr

    off = build("graph_passes = dead_layer_elim,fold_conv_bn\n")
    on = build(f"graph_passes = {_QUANT_PASSES}\n")
    pin = build(f"graph_passes = {_QUANT_PASSES}\n",
                conf=_CONF_BN.replace(
                    "nhidden = 3",
                    "nhidden = 3\n  layer_quant = float"))
    cal = _batch(0)
    for tr in (off, on, pin):
        tr.calibrate_graph_passes(cal)
    final = on.net_cfg.num_nodes - 1
    data = np.zeros((32, 1, 1, 36), np.float32)

    def dots(tr):
        g, ge = tr.stage_infer_rows(data)
        return _data_path_dots(tr._infer_fn(final),
                               (tr.state["params"], g, ge), 32)

    i8_on, fp_on = dots(on)
    i8_off, fp_off = dots(off)
    checks.append(_check(
        "quant", "int8-data-path-engaged",
        i8_on == 2 and fp_on == 0,
        f"quantized trace: {i8_on} int8/int32 data-path dots, "
        f"{fp_on} float (want 2 and 0 - both fullc layers must "
        "route through ops/int8.py)"))
    checks.append(_check(
        "quant", "float-trace-vacuity-guard",
        i8_off == 0 and fp_off == 2,
        f"float (fold-only) trace: {i8_off} int8 / {fp_off} float "
        "data-path dots (want 0 and 2, or the engagement check "
        "proves nothing)"))
    i8_pin, fp_pin = dots(pin)
    checks.append(_check(
        "quant", "layer_quant-float-pin-honored",
        i8_pin == 1 and fp_pin == 1,
        f"pinned trace: {i8_pin} int8 / {fp_pin} float data-path "
        "dots (want 1 each - fc2's explicit float pin must survive "
        "while fc1 quantizes)"))

    # quantized serving: calibrate BEFORE the Server pins its
    # executable, then the warmed bucket set must stay flat over a
    # mixed-size storm (the serve-audit contract on the int8 path)
    sizes: Dict[str, int] = {}
    srv = Server(on, max_batch=8, max_wait_ms=1.0, replicas=2)
    if _cache_size(srv._fn) is None:
        checks.append(_check(
            "quant/serve", "cache-size-api", False,
            "jit._cache_size unavailable on this jax version"))
        return sizes
    srv.warmup()
    n_warm = _cache_size(srv._fn)
    b8, ge8 = on.stage_infer_rows(np.zeros((8, 1, 1, 36), np.float32))
    i8_srv, fp_srv = _data_path_dots(
        srv._fn, (on.state["params"], b8, ge8), 8)
    checks.append(_check(
        "quant/serve", "bucket-executables-int8-engaged",
        i8_srv == 2 and fp_srv == 0
        and _cache_size(srv._fn) == n_warm,
        f"bucket-8 trace: {i8_srv} int8 / {fp_srv} float data-path "
        "dots (tracing must not add executables either)"))
    srv.start()
    rng = np.random.RandomState(11)
    futs = [srv.submit(rng.rand(1 + int(rng.randint(8)), 1, 1, 36)
                       .astype(np.float32))
            for _ in range(30)]
    for f in futs:
        f.result(timeout=120)
    stats = srv.stop()
    n_after = _cache_size(srv._fn)
    checks.append(_check(
        "quant/serve", "zero-recompile-after-calibration",
        n_warm == len(srv.buckets) and n_after == n_warm
        and stats["errors"] == 0,
        f"cache {n_warm} -> {n_after} over {stats['batches']} "
        f"batches (buckets={list(srv.buckets)}, "
        f"errors={stats['errors']})"))
    sizes["quant_serve_warm"] = n_warm
    sizes["quant_serve_after"] = n_after
    return sizes


def _recompile_audit(checks: List[Dict[str, Any]]) -> Dict[str, int]:
    tr = _make_trainer()
    if _cache_size(tr._train_step) is None:
        checks.append(_check(
            "recompile", "cache-size-api", False,
            "jit._cache_size unavailable on this jax version"))
        return {}

    def round_of(k: int, n: int) -> None:
        """One training pass: n batches dispatched in chunks of k
        with the round-boundary short-chunk flush (main.py's loop)."""
        pending = []
        for i in range(n):
            pending.append(_batch(i))
            if len(pending) >= k:
                tr.update_chunk(pending)
                pending = []
        if pending:
            tr.update_chunk(pending)

    # round 1: 9 batches at K=4 -> chunks 4+4+1 (short final chunk)
    round_of(4, 9)
    sizes = {"train_chunk_round1": _cache_size(tr._train_chunk)}
    checks.append(_check(
        "recompile", "chunk-cache==2 after 4+4+1 round",
        sizes["train_chunk_round1"] == 2,
        f"cache={sizes['train_chunk_round1']} (want 2: one K=4 "
        f"executable + one short-chunk K=1)"))
    # round 2, same shape mix: NO new executables
    round_of(4, 9)
    sizes["train_chunk_round2"] = _cache_size(tr._train_chunk)
    checks.append(_check(
        "recompile", "chunk-cache stable across rounds",
        sizes["train_chunk_round2"] == sizes["train_chunk_round1"],
        f"cache={sizes['train_chunk_round2']} after round 2"))

    # streamed path: full batch + SHORT batch (padded to static
    # shape) must share one train_step executable
    tr.update(_batch(50))
    tr.update(_batch(51, b=20))
    sizes["train_step"] = _cache_size(tr._train_step)
    checks.append(_check(
        "recompile", "step-cache==1 incl. padded short batch",
        sizes["train_step"] == 1,
        f"cache={sizes['train_step']} (padding must keep the "
        f"program shape static)"))

    # inference executable (the predict/extract/serve split): full +
    # short batch pad to ONE program shape
    tr.predict(_batch(60))
    tr.predict(_batch(61, b=20))
    nfin = tr.net_cfg.num_nodes - 1
    sizes["infer_step"] = _cache_size(tr._infer_fn(nfin))
    checks.append(_check(
        "recompile", "infer-cache==1 incl. padded short batch",
        sizes["infer_step"] == 1, f"cache={sizes['infer_step']}"))
    return sizes


# ---------------------------------------------------------------------------
# executable introspection plane (telemetry/flight.py)
# ---------------------------------------------------------------------------
def _executables_audit(checks: List[Dict[str, Any]]) -> None:
    """The sections above dispatched real train/infer/serve
    executables, so the process-wide executable registry
    (`/executables`, docs/OBSERVABILITY.md) must be NON-EMPTY with a
    stable entry schema and accumulated dispatch counts - the
    vacuity-guard stance of the other audits: an introspection plane
    that registers nothing would pass every per-entry check."""
    from cxxnet_tpu import telemetry
    execs = telemetry.executables().snapshot()
    checks.append(_check(
        "executables", "registry-non-empty", len(execs) > 0,
        f"{len(execs)} registered executables"))
    kinds = {e["kind"] for e in execs}
    checks.append(_check(
        "executables", "covers-train-infer-serve",
        {"train", "infer", "serve"} <= kinds,
        f"kinds registered: {sorted(kinds)}"))
    required = {"fingerprint", "name", "kind", "shape", "arg_bytes",
                "device", "donated", "compile_s", "flops",
                "cost_bytes", "out_bytes", "dispatches", "dispatch_s",
                "last_used_ts"}
    bad = [e.get("name", "?") for e in execs
           if not required <= set(e)]
    checks.append(_check(
        "executables", "entry-schema", not bad,
        f"entries missing schema fields: {bad[:5]}" if bad else
        f"all {len(execs)} entries carry the full schema"))
    dispatched = sum(1 for e in execs if e["dispatches"] > 0)
    checks.append(_check(
        "executables", "dispatch-counts-accumulate", dispatched > 0,
        f"{dispatched}/{len(execs)} entries saw dispatches"))
    donated = [e for e in execs if e["kind"] == "train"]
    checks.append(_check(
        "executables", "train-donation-footprint-recorded",
        bool(donated) and all(e["donated"] for e in donated),
        f"{len(donated)} train entries, donated="
        f"{[e['donated'] for e in donated]}"))


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------
def run_audit() -> Dict[str, Any]:
    """Trace + compile the representative executables and run every
    artifact check. Returns {platform, checks, cache_sizes}."""
    import jax
    from cxxnet_tpu.parallel import distributed

    checks: List[Dict[str, Any]] = []
    tr = _make_trainer()
    sb = tr.stage_batch(_batch(0))
    rng = jax.random.PRNGKey(0)

    checks += _audit_executable(
        "train_step", tr._train_step,
        (tr.state, sb.data, sb.extras, sb.labels, sb.mask, rng),
        donated=True)

    for k in (1, 4):
        chunk = tr.stage_chunk([_batch(i) for i in range(k)])
        step_idx = distributed.put_global(
            np.arange(k, dtype=np.int32), tr._replicated)
        checks += _audit_executable(
            f"train_chunk[K={k}]", tr._train_chunk,
            (tr.state, chunk.data, chunk.extras, chunk.labels,
             chunk.mask, step_idx, rng),
            donated=True)

    checks += _audit_executable(
        "eval_step", tr._eval_step,
        (tr.state["params"], sb.data, sb.extras), donated=False)
    if tr._eval_metric_step is not None:
        checks += _audit_executable(
            "eval_metric_step", tr._eval_metric_step,
            (tr.state["params"], sb.data, sb.extras, sb.labels,
             sb.mask, rng), donated=False)
    # the dedicated inference executable (predict/extract/serve all
    # share it - docs/SERVING.md); the serve audit below additionally
    # covers its bucket-shaped instantiations
    checks += _audit_executable(
        "infer_step", tr._infer_fn(tr.net_cfg.num_nodes - 1),
        (tr.state["params"], sb.data, sb.extras), donated=False)

    _zero_audit(checks)
    cache_sizes = _recompile_audit(checks)
    cache_sizes.update(_serve_audit(checks))
    cache_sizes.update(_pass_audit(checks))
    cache_sizes.update(_quant_audit(checks))
    _executables_audit(checks)
    return {
        "platform": jax.default_backend(),
        "jax_version": jax.__version__,
        "checks": checks,
        "cache_sizes": cache_sizes,
        "failed": sum(1 for c in checks if not c["ok"]),
    }
