"""NetTrainer: the INetTrainer product surface, TPU-native.

Role parity with CXXNetThreadTrainer (nnet_impl-inl.hpp:16-455) - the full
virtual API of nnet.h:18-92: SetParam / InitModel / SaveModel / LoadModel /
StartRound / Update / Evaluate / Predict / ExtractFeature / CopyModelFrom /
SetWeight / GetWeight - but the execution model is re-designed for TPU:

reference                               this trainer
---------                               ------------
per-GPU host thread + stream            one SPMD program over a Mesh
batch sliced into per-device chunks     batch dim sharded over 'data' axis
mshadow-ps push/pull + AsyncUpdater     XLA AllReduce inserted by GSPMD
updater objects mutating weights        pure per-tensor updater transforms
                                        folded into the same jitted step
AdjustBatchSize for short batches       pad-to-static + validity mask
update_period grad accumulation         carried accumulator + lax.cond

The entire train step (forward + backward + gradient all-reduce +
optimizer) compiles to ONE XLA executable; eval/predict use a second
forward-only executable.
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cxxnet_tpu import telemetry
from cxxnet_tpu.telemetry import spans
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet import checkpoint
from cxxnet_tpu.nnet.net_config import NetConfig
from cxxnet_tpu.nnet.network import Network, param_key
from cxxnet_tpu.parallel import distributed
from cxxnet_tpu.parallel.mesh import (
    MeshSpec, build_mesh, parse_device_spec, parse_mesh_spec)
from cxxnet_tpu.parallel.sharding import shardings_for
from cxxnet_tpu.updater import UpdaterParam, create_updater
from cxxnet_tpu.utils import fault
from cxxnet_tpu.utils.fault import DivergenceError
from cxxnet_tpu.utils.metric import MetricSet


class StagedBatch(NamedTuple):
    """A training batch whose device buffers are already staged under
    the jitted step's in_shardings (stage_batch). update() accepts it
    and skips ALL per-step host work (pad, cast, H2D) - the TPU-first
    analog of the reference's membuffer (iter_mem_buffer-inl.hpp: a
    RAM-resident HOST buffer): a dataset that fits HBM streams zero
    bytes per step, so e2e throughput equals the compute ceiling even
    over a slow host link."""
    data: Any
    extras: Tuple[Any, ...]
    labels: Dict[str, Any]
    mask: Any
    n_examples: int


class StagedChunk(NamedTuple):
    """K staged batches stacked along a leading microstep axis - the
    input of ONE fused dispatch (steps_per_dispatch=K): a single jitted
    lax.scan carries the train state through all K updates, so the
    host pays one dispatch + one readback per chunk instead of K
    (docs/PERFORMANCE.md). Built by stage_chunk from the exact
    per-batch staging pipeline, so the weight trajectory is bitwise
    identical to K streamed updates."""
    data: Any                      # (K, ...) under the chunked sharding
    extras: Tuple[Any, ...]        # each (K, ...)
    labels: Dict[str, Any]         # each (K, ...)
    mask: Any                      # (K, batch)
    n_examples: Tuple[int, ...]    # distinct instances per microstep

    @property
    def n_steps(self) -> int:
        return len(self.n_examples)


def _masked_absmax(x, mask):
    """Valid-row absmax of a tapped activation (f32) - the
    quantize_int8 act-scale arithmetic, shared by the single-batch
    and multi-batch calibration paths so their pinned agreement
    cannot drift: padding rows carry bias/activation garbage at
    depth, so the mask keeps them from widening the frozen range."""
    xf = x.astype(jnp.float32)
    m = jnp.broadcast_to(
        mask.astype(jnp.float32).reshape(
            (-1,) + (1,) * (xf.ndim - 1)), xf.shape)
    return jnp.max(jnp.abs(xf) * m)


def _bf16_cast(data: np.ndarray) -> np.ndarray:
    """f32 -> bf16 on the HOST, fast path via torch (~1.8x faster than
    ml_dtypes on this class of host, bitwise identical round-to-
    nearest-even - measured in round 4; an AlexNet b256 batch is ~40M
    elements, so this cast sits on the e2e critical path)."""
    import ml_dtypes
    try:
        import torch
        t = torch.from_numpy(np.ascontiguousarray(data))
        # AttributeError: torch.uint16 needs torch >= 2.3;
        # RuntimeError: torch built against numpy 1.x under numpy 2.x
        # ("Numpy is not available") - any such host must fall back,
        # not crash the staging path
        return (t.to(torch.bfloat16).view(torch.uint16).numpy()
                .view(ml_dtypes.bfloat16))
    except (ImportError, AttributeError, RuntimeError):
        return data.astype(ml_dtypes.bfloat16)


class NetTrainer:
    """Config-driven trainer for one network."""

    def __init__(self, dev: str = "", cfg: str = ""):
        self.cfg_pairs: List[Tuple[str, str]] = []
        self.net_cfg = NetConfig()
        self.net: Optional[Network] = None
        self.batch_size = 0
        self.update_period = 1
        self.eval_train = 1
        self.seed = 0
        self.silent = 0
        self.compute_dtype = jnp.float32
        self.metric = MetricSet()
        self.train_metric = MetricSet()
        # (node_name or "", node_id or -1) per metric - "" = final node
        self.eval_nodes: List[Tuple[str, int]] = []
        self.mesh_spec = MeshSpec()
        self.mesh: Optional[Mesh] = None
        self.epoch = 0       # update counter (reference epoch_counter)
        self.round = 0
        self._step_counter = 0
        self.state: Optional[Dict[str, Any]] = None
        self._loaded_params = None
        self._loaded_opt = None
        self.save_optimizer = 0
        # ZeRO weight-update sharding stage (docs/parallel.md,
        # arXiv:2004.13336): 0 = fully replicated update; 1 = optimizer
        # state sharded over 'data' (`shard_optimizer=1` stays as the
        # legacy alias); 2 = + gradients reduce-scattered and the
        # update run on each device's shard only, fresh weights
        # all-gathered once per step; 3 = + parameters sharded BETWEEN
        # steps, each weight all-gathered just in time for its layer
        # in the forward pass
        self.zero_stage = 0
        self._zero_src = ""   # config key that last set zero_stage
        self.stage_dtype = ""   # "" = follow compute_dtype
        self.device_augment = 0
        # augment spec, shared config keys with the host iterator
        # pipeline (the CLI feeds every conf pair to every component,
        # reference-style, so these arrive without extra wiring)
        self._daug_cfg: Dict[str, str] = {}
        self._augment_fn = None
        self.remat = 0
        # divergence guard (docs/FAULT_TOLERANCE.md): check_nan=1 adds
        # a jitted all-finite check over loss+params to the train step;
        # a non-finite step is dropped (params rolled back in-jit) and
        # max_bad_rounds CONSECUTIVE bad steps raise DivergenceError
        self.check_nan = 0
        self._check_nan_built = False
        self.max_bad_rounds = 3
        self.bad_rounds = 0        # total dropped steps (this process)
        self._bad_consec = 0
        self._skipped_steps = 0
        self.model_format = "native"
        # fused multi-step dispatch (docs/PERFORMANCE.md): K staged
        # batches scan through ONE jitted executable per chunk. 1 =
        # today's streamed/staged per-step dispatch, byte-for-byte.
        self.steps_per_dispatch = 1
        # eval loop in-flight bound: sync on the tiny metric rows every
        # N batches so at most N batches of input buffers pin HBM
        # (0 = never sync - the whole eval set may stage ahead)
        self.eval_inflight = 8
        # continuous-batching serving knobs (serve/server.py,
        # docs/SERVING.md): largest request bucket (0 = batch_size),
        # fill-or-timeout admission wait, and dispatcher replica count
        self.serve_max_batch = 0
        self.serve_max_wait_ms = 2.0
        self.serve_replicas = 1
        # serving production front (docs/SERVING.md "Serving over
        # HTTP"): serve_port arms the /predict HTTP request path on
        # the attached exposition listener (0 = off, in-process
        # submit only); serve_queue_limit is the hard admission bound
        # in rows (0 = unlimited - submits past it shed with 429 /
        # QueueFullError); serve_deadline_ms the default per-request
        # deadline (0 = none, expired requests drop before dispatch);
        # serve_shed_clear_ms the shed->healthy /healthz hysteresis
        self.serve_port = 0
        self.serve_queue_limit = 0
        self.serve_deadline_ms = 0.0
        self.serve_shed_clear_ms = 1000.0
        # zero-downtime checkpoint hot-swap (docs/SERVING.md "Hot-swap
        # runbook"): a live Server polls swap_watch every swap_poll_ms
        # and swaps weights from any newly published (atomic,
        # checksummed) checkpoint; "" = off
        self.swap_watch = ""
        self.swap_poll_ms = 200.0
        # canaried rollout (docs/SERVING.md "Canary runbook"): with
        # swap_canary_frac in (0, 1] a validated new checkpoint is
        # STAGED, not promoted - that fraction of requests (hashed by
        # trace id) serves the candidate params while a judge thread
        # scores it for swap_canary_window seconds (error/deadline
        # rates vs incumbent + shadow-pair divergence), then
        # auto-promotes or auto-rolls-back. 0 = off (PR-16 immediate
        # swap, byte-identical behavior)
        self.swap_canary_frac = 0.0
        self.swap_canary_window = 10.0
        # connection-level ingress hardening (docs/SERVING.md
        # "Connection limits & drain"; all 0 = off, the PR-16
        # listener): per-connection read deadline so a slow-loris
        # client cannot pin a listener thread, a hard cap on
        # concurrent connections (503 + Retry-After past it, own
        # `serve_conns` health source), and a max request-body size
        # (413 past it, rejected before the body is read)
        self.serve_conn_timeout_ms = 0.0
        self.serve_max_conns = 0
        self.serve_max_body_bytes = 0
        # explicit serving bucket ladder (serve_bucket_ladder = comma
        # ints; None = power-of-two default): Server(trainer) reads
        # it; a tuning-cache serve_ladder fills it as a default under
        # the explicit-keys-win rule (docs/GRAPH_PASSES.md)
        self.serve_ladder: Optional[List[int]] = None
        # graph-level optimizing passes over the NetConfig DAG
        # (nnet/passes.py, docs/GRAPH_PASSES.md): comma list of pass
        # names ("" = off, "all" = every registered pass) plus
        # per-pass `pass_<name> = 0|1` toggles. Graph-stage passes
        # (space_to_depth stamp, autocast plan) apply to the built
        # network; infer-stage passes (dead_layer_elim, fold_conv_bn)
        # apply only to the clone the inference executables compile
        # from - training trajectories and checkpoints are untouched
        self.graph_passes = ""
        self._pass_toggles: Dict[str, int] = {}
        self._pipeline = None
        self._graph_dtype_plan = None
        # fold_conv_bn calibration batches: 1 = the historic
        # single-batch freeze (bitwise-pinned); N > 1 averages moments
        # over N calibration batches (calibrate_graph_passes with a
        # batch sequence - main.py's pass_calibration_iter feeds it)
        self.pass_calibration_batches = 1
        # fold_conv_bn calibration state: bn param key -> (mean,
        # rstd) frozen at calibration; epoch keys the per-node infer
        # executable cache so a recalibration rebuilds cleanly
        self._fold_stats: Optional[Dict[str, Any]] = None
        # quantize_int8 calibration state: eligible conv/fullc param
        # key -> activation absmax from the same calibration sweep
        # (the per-tensor act scale is absmax/127; the per-channel
        # weight scales freeze later, per transformed infer graph -
        # _fill_quant_scales). Shares the fold epoch/eviction.
        self._quant_stats: Optional[Dict[str, float]] = None
        self._fold_epoch = 0
        self._infer_graph_cache: Dict[Any, Any] = {}
        # dispatch-site fingerprint cache (telemetry/flight.py): one
        # executable-registry registration per compiled program shape;
        # steady-state dispatches pay a dict hit
        self._flight_fps: Dict[Any, str] = {}
        # TVM-style tuning cache (nnet/tuning.py, tools/autotune.py):
        # tuned knob values are DEFAULTS - explicitly-set config keys
        # always win (tracked per key at set_param time)
        self.tuning_cache = ""
        self._explicit_tunables: set = set()
        self.profile = 0
        self.profile_dir = ""
        self.trace_round = 1
        self._epoch_base = 0
        self.profiler = None
        # telemetry_steps=0 opts OUT of per-step instrumentation while
        # keeping event logging: per-step timing costs a device sync +
        # loss readback per update (honest step times), which kills the
        # async-dispatch overlap - event-only production runs can keep
        # checkpoint/fault telemetry without paying it
        self.telemetry_steps = 1
        # per-step telemetry armed? captured at _build_net so the
        # per-step branch is one attribute check (and consistent with
        # what the compiled run actually instruments)
        self._tel_steps = False
        if dev:
            self.set_param("dev", dev)
        if cfg:
            from cxxnet_tpu.utils.config import parse_config_string
            for k, v in parse_config_string(cfg):
                self.set_param(k, v)

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        if name == "dev":
            (self.mesh_spec.kind,
             self.mesh_spec.device_indices) = parse_device_spec(val)
        if name == "mesh":
            self.mesh_spec.axes = parse_mesh_spec(val)
        if name == "batch_size":
            self.batch_size = int(val)
        if name == "update_period":
            self.update_period = int(val)
        if name == "eval_train":
            self.eval_train = int(val)
        if name == "seed":
            self.seed = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "save_optimizer":
            self.save_optimizer = int(val)
        if name == "zero_stage":
            self._set_zero_stage(name, int(val))
        if name == "shard_optimizer":
            # legacy alias: ZeRO-1, optimizer state only
            self._set_zero_stage(name, 1 if int(val) else 0)
        if name == "update_on_server" and int(val):
            # reference knob (nnet_ps_server.cpp): run the updater on
            # the PS instead of replicating it per worker. The TPU
            # analog is sharding the optimizer state (docs/parallel.md).
            # Enable-only: an explicit =0 (the reference default in
            # non-PS configs) must not clobber shard_optimizer=1.
            self._set_zero_stage(name, max(1, self.zero_stage))
        if name == "remat":
            self.remat = int(val)
        if name == "check_nan":
            self.check_nan = int(val)
        if name == "max_bad_rounds":
            self.max_bad_rounds = int(val)
        if name == "stage_dtype":
            if val not in ("", "float32", "bfloat16"):
                raise ValueError("stage_dtype must be float32 or bfloat16")
            self.stage_dtype = val
        if name == "device_augment":
            self.device_augment = int(val)
        if name in ("image_mean", "mean_value", "scale", "divideby",
                    "rand_crop", "rand_mirror", "mirror",
                    "crop_y_start", "crop_x_start",
                    "max_random_contrast", "max_random_illumination"):
            # crop/mirror/mean/scale spec for device_augment=1 (same
            # key names the host AugmentIterator consumes; ignored
            # unless device_augment is set). divideby is the
            # reciprocal-scale alias, like augment.py's handler.
            if name == "divideby":
                name, val = "scale", str(1.0 / float(val))
            self._daug_cfg[name] = val
        if name == "model_format":
            if val not in ("native", "cxxnet"):
                raise ValueError("model_format must be native or cxxnet")
            self.model_format = val
        if name == "steps_per_dispatch":
            if int(val) < 1:
                raise ValueError("steps_per_dispatch must be >= 1")
            self.steps_per_dispatch = int(val)
        if name == "eval_inflight":
            if int(val) < 0:
                raise ValueError("eval_inflight must be >= 0")
            self.eval_inflight = int(val)
        if name == "serve_max_batch":
            if int(val) < 0:
                raise ValueError("serve_max_batch must be >= 0")
            self.serve_max_batch = int(val)
        if name == "serve_max_wait_ms":
            if float(val) < 0:
                raise ValueError("serve_max_wait_ms must be >= 0")
            self.serve_max_wait_ms = float(val)
        if name == "serve_replicas":
            if int(val) < 1:
                raise ValueError("serve_replicas must be >= 1")
            self.serve_replicas = int(val)
        if name == "serve_port":
            if int(val) < 0 or int(val) > 65535:
                raise ValueError("serve_port must be in [0, 65535]")
            self.serve_port = int(val)
        if name == "serve_queue_limit":
            if int(val) < 0:
                raise ValueError("serve_queue_limit must be >= 0")
            self.serve_queue_limit = int(val)
        if name == "serve_deadline_ms":
            if float(val) < 0:
                raise ValueError("serve_deadline_ms must be >= 0")
            self.serve_deadline_ms = float(val)
        if name == "serve_shed_clear_ms":
            if float(val) < 0:
                raise ValueError("serve_shed_clear_ms must be >= 0")
            self.serve_shed_clear_ms = float(val)
        if name == "swap_watch":
            self.swap_watch = val
        if name == "swap_poll_ms":
            if float(val) <= 0:
                raise ValueError("swap_poll_ms must be > 0")
            self.swap_poll_ms = float(val)
        if name == "swap_canary_frac":
            if not 0.0 <= float(val) <= 1.0:
                raise ValueError("swap_canary_frac must be in [0, 1]")
            self.swap_canary_frac = float(val)
        if name == "swap_canary_window":
            if float(val) <= 0:
                raise ValueError("swap_canary_window must be > 0")
            self.swap_canary_window = float(val)
        if name == "serve_conn_timeout_ms":
            if float(val) < 0:
                raise ValueError("serve_conn_timeout_ms must be >= 0")
            self.serve_conn_timeout_ms = float(val)
        if name == "serve_max_conns":
            if int(val) < 0:
                raise ValueError("serve_max_conns must be >= 0")
            self.serve_max_conns = int(val)
        if name == "serve_max_body_bytes":
            if int(val) < 0:
                raise ValueError("serve_max_body_bytes must be >= 0")
            self.serve_max_body_bytes = int(val)
        if name == "serve_bucket_ladder":
            rungs = [int(t) for t in val.split(",") if t.strip()]
            if (not rungs or any(r < 1 for r in rungs)
                    or sorted(set(rungs)) != rungs):
                raise ValueError(
                    "serve_bucket_ladder must be a strictly "
                    f"increasing comma list of positive ints, got "
                    f"{val!r}")
            self.serve_ladder = rungs
        if name == "graph_passes":
            self.graph_passes = val
        if name == "pass_calibration_batches":
            if int(val) < 1:
                raise ValueError(
                    "pass_calibration_batches must be >= 1")
            self.pass_calibration_batches = int(val)
        if (name.startswith("pass_")
                and name not in ("pass_calibration_batches",
                                 "pass_calibration_iter")):
            # per-pass toggles layered over graph_passes (membership
            # add/remove): prefix-form so a new @register_pass needs
            # no handler edit here; the name is validated against the
            # pass registry at _build_net with did-you-mean.
            # pass_calibration_* are calibration knobs, not toggles
            # (pass_calibration_iter is consumed by main.LearnTask)
            self._pass_toggles[name[len("pass_"):]] = int(val)
        if name == "tuning_cache":
            self.tuning_cache = val
        if name in ("steps_per_dispatch", "serve_max_batch",
                    "stage_dtype", "serve_bucket_ladder"):
            # explicit config keys beat tuning-cache defaults
            self._explicit_tunables.add(name)
        if name == "profile":
            self.profile = int(val)
        if name == "profile_dir":
            self.profile_dir = val
            self.profile = max(self.profile, 1)
        if name == "trace_round":
            # which profiled round profile_dir traces (1-based; round 1
            # is compile-dominated, steady state wants >= 2)
            self.trace_round = int(val)
        if name == "telemetry_steps":
            self.telemetry_steps = int(val)
        if name == "dtype":
            self.compute_dtype = {"float32": jnp.float32,
                                  "bfloat16": jnp.bfloat16}[val]
        if name.startswith("metric"):
            import re
            m = re.match(r"^metric\[([^,\]]+),([^\]]+)\]$", name)
            if m:
                self.metric.add_metric(val, m.group(1))
                self.train_metric.add_metric(val, m.group(1))
                self.eval_nodes.append((m.group(2), 0))
            elif name == "metric":
                self.metric.add_metric(val, "label")
                self.train_metric.add_metric(val, "label")
                self.eval_nodes.append(("", -1))
        self.cfg_pairs.append((name, val))

    def _set_zero_stage(self, key: str, stage: int) -> None:
        """zero_stage with alias handling: `shard_optimizer` /
        `update_on_server` are legacy spellings of stage <= 1.
        Last-writer-wins holds only WITHIN one key - an alias arriving
        after an explicit `zero_stage = 2|3` must not silently
        downgrade the run to ZeRO-1; it warns and is ignored."""
        if not 0 <= stage <= 3:
            raise ValueError("zero_stage must be 0, 1, 2 or 3")
        if key != "zero_stage" and self._zero_src == "zero_stage":
            if stage != self.zero_stage:
                telemetry.stderr(
                    f"warning: {key} (a zero_stage={stage} alias) "
                    f"conflicts with the explicit zero_stage="
                    f"{self.zero_stage}; keeping zero_stage="
                    f"{self.zero_stage}\n",
                    event_kind="config", type="zero_stage_conflict",
                    key=key, requested=stage, kept=self.zero_stage)
            # agreeing alias: the explicit setting stays authoritative
            return
        self.zero_stage = stage
        self._zero_src = key

    @property
    def shard_optimizer(self) -> int:
        """Legacy view of the ZeRO knob: any stage shards the
        optimizer state (readers predate zero_stage)."""
        return int(self.zero_stage >= 1)

    # ------------------------------------------------------------------
    # initialization
    # ------------------------------------------------------------------
    def init_model(self) -> None:
        if (self.stage_dtype == "bfloat16"
                and self.compute_dtype == jnp.float32):
            # would silently stage f32 anyway (_host_input): reject the
            # no-op combination instead of hiding a misconfiguration
            raise ValueError(
                "stage_dtype=bfloat16 requires dtype=bfloat16 "
                "(f32 compute always stages f32)")
        # param_server=dist -> join the multi-controller job before any
        # device is touched (replaces InitParamServer,
        # nnet_impl-inl.hpp:376-390)
        distributed.init_from_config(self.cfg_pairs)
        self.net_cfg.configure(self.cfg_pairs)
        self._build_net()
        key = jax.random.PRNGKey(self.seed)
        params = self.net.init_params(key)
        self._init_state(params)
        self.epoch = 0
        self._epoch_base = 0
        self._step_counter = 0
        self._skipped_steps = 0
        self._bad_consec = 0

    def _build_net(self) -> None:
        if self.batch_size <= 0:
            raise ValueError("batch_size must be set")
        self._apply_tuning_cache()
        # graph-pass pipeline (nnet/passes.py): graph-stage passes
        # stamp the live NetConfig (layer configs / dtype plan only -
        # structure, and with it the checkpoint format, is untouched);
        # infer-stage passes run lazily per requested node in
        # _build_infer_graph. An empty graph_passes config builds an
        # empty pipeline and every path below is byte-identical to
        # the pass-less trainer.
        from cxxnet_tpu.nnet.passes import (
            GraphModule, PassPipeline)
        self._pipeline = PassPipeline.from_config(self.graph_passes,
                                                  self._pass_toggles)
        self._graph_dtype_plan = None
        self._fold_stats = None
        self._quant_stats = None
        self._fold_epoch = 0
        self._infer_graph_cache = {}
        # fold/quant sites depend only on the graph structure: matched
        # ONCE here, not per inference batch (passes_need_calibration
        # sits on the predict hot path)
        from cxxnet_tpu.nnet.passes import (
            find_fold_sites, find_quant_sites)
        self._fold_sites = (find_fold_sites(self.net_cfg)
                            if self._pipeline.has("fold_conv_bn")
                            else [])
        self._quant_sites = (find_quant_sites(self.net_cfg)
                             if self._pipeline.has("quantize_int8")
                             else [])
        if self._pipeline.graph_passes:
            gm = GraphModule.from_net_config(
                self.net_cfg, self.batch_size, self.compute_dtype)
            gm = self._pipeline.run_graph(gm)
            self._graph_dtype_plan = gm.dtype_plan or None
            if not self.silent and gm.log:
                for line in gm.log:
                    telemetry.stdout(f"graph_passes: {line}")
        self.net = Network(self.net_cfg, self.batch_size)
        self.net.dtype_plan = self._graph_dtype_plan
        if not self.silent:
            for i, s in enumerate(self.net.node_shapes):
                telemetry.stdout(
                    f"node[{self.net_cfg.node_names[i]}].shape: "
                    f"{s[0]},{s[1]},{s[2]},{s[3]}")
        self.mesh = build_mesh(self.mesh_spec, self.batch_size)
        self._local_rows = self._compute_local_rows()
        # tensor-parallel parameter shardings over the 'model' mesh axis
        # (all-replicated on a pure-data mesh - parallel/sharding.py)
        self._pshard = shardings_for(self.mesh, self.net)
        self._resolve_eval_nodes()
        self._build_updaters()
        self._compile()
        # telemetry reuses the profiler's per-round accumulator for its
        # round records even when profile=0 (summaries print only under
        # profile=1, so the profile-less stderr stays untouched)
        self._tel_steps = (bool(self.telemetry_steps)
                           and telemetry.get().enabled)
        if (self.profile or self._tel_steps) and self.profiler is None:
            from cxxnet_tpu.utils.profiler import StepProfiler
            self.profiler = StepProfiler(self.profile_dir,
                                         self.trace_round)

    def _resolve_eval_nodes(self) -> None:
        resolved = []
        for name, _ in self.eval_nodes:
            if name == "":
                resolved.append(("", self.net_cfg.num_nodes - 1))
            else:
                resolved.append((name, self.net.node_index(name)))
        self.eval_nodes = resolved

    def _build_updaters(self) -> None:
        """One Updater per weight tensor, configured with defcfg +
        layercfg[i] under its tag (neural_net-inl.hpp:177-204)."""
        self.updaters: Dict[str, Dict[str, Any]] = {}
        utype = self.net_cfg.updater_type
        for idx, info in enumerate(self.net_cfg.layers):
            if info.is_shared:
                continue
            tags = self.net.layer_objs[idx].param_tags()
            if not tags:
                continue
            key = param_key(self.net_cfg, idx)
            self.updaters[key] = {}
            for pname, tag in tags.items():
                up = UpdaterParam(tag)
                kwargs = {}
                for k, v in (self.net_cfg.defcfg
                             + self.net_cfg.layercfg[idx]):
                    up.set_param(k, v)
                    if utype == "adam" and k == "beta1":
                        kwargs["decay1"] = float(v)
                    if utype == "adam" and k == "beta2":
                        kwargs["decay2"] = float(v)
                self.updaters[key][pname] = create_updater(utype, up,
                                                           **kwargs)

    def _retire_calibration_state(self) -> None:
        """Weights changed (set_weight / copy_model_from / checkpoint
        reload): any frozen fold statistics or quant scales describe
        the OLD activations/weight ranges - drop them AND retire the
        executables compiled against them (bumping the epoch +
        evicting, same as a recalibration), so an infer_rows/Server
        built afterwards can never silently dispatch an executable
        frozen with the previous model's constants. Folded weights
        and the int8 values themselves are live functions of the
        params argument; only the baked mean/rstd and act/weight
        scales go stale - the next inference recalibrates them."""
        if (self._fold_stats is not None
                or self._quant_stats is not None):
            self._fold_stats = None
            self._quant_stats = None
            self._fold_epoch += 1
            self._evict_stale_infer_caches()

    def _init_state(self, params) -> None:
        self._retire_calibration_state()
        ustate = {
            lk: {pn: up.init_state(params[lk][pn])
                 for pn, up in d.items() if pn in params.get(lk, {})}
            for lk, d in self.updaters.items()}
        # the gradient accumulator exists only where steps are
        # accumulated: with update_period = 1 it was a parameter-sized
        # tree of zeros, held, donated and rewritten every step and
        # never read (2.4 GB beside 602M parameters)
        accum = (jax.tree.map(jnp.zeros_like, params)
                 if self.update_period > 1 else {})
        state = {
            "params": params,
            "ustate": ustate,
            "accum": accum,
            "count": jnp.zeros((), jnp.int32),
            "epoch": jnp.asarray(self.epoch, jnp.int32),
            # on-device train-metric accumulator: one (sum, comp,
            # count) row per configured metric; `comp` is the Kahan
            # compensation term so a long round's f32 sum doesn't
            # drift (the eval path avoids this with per-batch host f64
            # reduction; the train path cannot read back per step)
            "tmetric": jnp.zeros((len(self.train_metric), 3), jnp.float32),
        }
        if self._count_layers:
            state["counters"] = {
                f"{param_key(self.net_cfg, i)}.{n}": jnp.zeros(
                    (), jnp.float32)
                for i, l in enumerate(self.net.layer_objs)
                if hasattr(l, "apply_with_stats")
                and not self.net_cfg.layers[i].is_shared
                for n in l.stat_names}
        if self._loaded_opt is not None:
            state["ustate"] = jax.tree.map(
                lambda a: jnp.asarray(a), self._loaded_opt)
            self._loaded_opt = None
        # prefix pytree: one sharding per weight covers its updater-state
        # dict too; same tree drives the jitted steps' in/out_shardings
        if jax.process_count() == 1:
            self.state = jax.device_put(state, self._state_shardings)
        else:
            # multi-controller: every process holds the full value of
            # each state leaf; put_global_full materializes only the
            # locally-owned shards (handles sharded optimizer state)
            full = self._expand_prefix(self._state_shardings, state)
            self.state = jax.tree.map(distributed.put_global_full, state,
                                      full)

    @staticmethod
    def _expand_prefix(prefix, tree):
        """Expand a sharding prefix pytree to a full per-leaf tree."""
        return jax.tree.map(
            lambda p, sub: jax.tree.map(lambda _: p, sub),
            prefix, tree,
            is_leaf=lambda x: isinstance(x, NamedSharding))

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------
    @property
    def _replicated(self):
        return NamedSharding(self.mesh, P())

    @property
    def _batch_sharded(self):
        # a mesh without a 'data' axis (e.g. pure pipeline parallelism,
        # mesh=pipe:4) replicates the batch
        d = "data" if "data" in self.mesh.axis_names else None
        return NamedSharding(self.mesh, P(d) if d else P())

    @property
    def _data_sharded(self):
        """Input-tensor sharding: batch over 'data' and, for sequence
        models on a mesh with a 'seq' axis, the sequence (y) dim over
        'seq' (parallel/ring.py). Labels/mask stay batch-only."""
        d = "data" if "data" in self.mesh.axis_names else None
        nseq = self.mesh.shape.get("seq", 1)
        if nseq > 1 and self.net_cfg.input_shape[1] % nseq == 0:
            return NamedSharding(self.mesh, P(d, None, "seq", None))
        return self._batch_sharded

    def _label_fields(self, label: np.ndarray) -> Dict[str, np.ndarray]:
        fields = {}
        for fname, idx in self.net_cfg.label_name_map.items():
            a, b = self.net_cfg.label_range[idx]
            fields[fname] = label[:, a:b]
        return fields

    def _apply_tuning_cache(self) -> None:
        """Apply tuned knob defaults from `tuning_cache =` (nnet/
        tuning.py): only knobs the config never set explicitly, and
        only values applicable to this trainer (an inapplicable
        tuned value is skipped, never an error - a shared cache file
        must not break a valid config). Schema-v2 caches additionally
        carry a PER-LAYER plan (s2d per conv, layer_dtype feeding the
        autocast pass) stamped onto the layer configs here - a key
        the config already names for that layer (or globally in
        defcfg) always wins - and a serve bucket ladder picked up
        unless `serve_bucket_ladder =` was set."""
        if not self.tuning_cache:
            return
        from cxxnet_tpu.nnet import tuning
        entry = tuning.platform_entry(self.tuning_cache)
        knobs = {k: str(v) for k, v in entry.get("knobs", {}).items()}
        explicit = self._explicit_tunables
        applied = {}
        # tuning.int_knob is THE shared apply rule (explicit keys
        # win, malformed values skip) - main.LearnTask consumes the
        # same cache through the same helper
        v = tuning.int_knob(knobs, "steps_per_dispatch", explicit, 1)
        if v is not None:
            self.steps_per_dispatch = applied["steps_per_dispatch"] = v
        v = tuning.int_knob(knobs, "serve_max_batch", explicit, 0)
        if v is not None:
            self.serve_max_batch = applied["serve_max_batch"] = v
        if ("stage_dtype" in knobs
                and "stage_dtype" not in explicit):
            val = knobs["stage_dtype"]
            if (val in ("", "float32", "bfloat16")
                    and not (val == "bfloat16"
                             and self.compute_dtype
                             == jnp.float32)):
                self.stage_dtype = applied["stage_dtype"] = val
        plan_applied = self._apply_layer_plan(entry.get("layers") or {})
        if plan_applied:
            applied["layers"] = plan_applied
        ladder = entry.get("serve_ladder")
        if (ladder and self.serve_ladder is None
                and "serve_bucket_ladder" not in explicit):
            try:
                rungs = sorted({int(b) for b in ladder if int(b) >= 1})
            except (TypeError, ValueError):
                rungs = []
            if rungs:
                self.serve_ladder = rungs
                applied["serve_ladder"] = rungs
        if applied:
            telemetry.event("tuning", op="apply",
                            cache=self.tuning_cache, **applied)

    def _apply_layer_plan(self, plan: Dict[str, Any]) -> Dict[str, Any]:
        """Stamp a v2 cache's per-layer plan onto the layer configs
        (the per-layer analog of the scalar knob pickup): skip
        unknown layers, inapplicable knobs (s2d on a non-conv),
        malformed values, and any key the config names for that
        layer or globally - explicit keys always win. Stamps go into
        net_cfg.layercfg, which NetConfig.configure rebuilds from
        the user's pairs on every (re)configure, so they never
        accumulate or masquerade as explicit keys."""
        applied: Dict[str, Any] = {}
        valid = {"space_to_depth": ("0", "1", "auto"),
                 "layer_dtype": ("float32", "bfloat16"),
                 "layer_quant": ("int8", "float")}
        for lname, kv in plan.items():
            idx = self.net_cfg.layer_name_map.get(lname)
            if idx is None or not isinstance(kv, dict):
                continue
            info = self.net_cfg.layers[idx]
            for k, v in kv.items():
                v = str(v)
                if k not in valid or v not in valid[k]:
                    continue
                if k == "space_to_depth" and info.type_name != "conv":
                    continue
                if (k == "layer_quant"
                        and info.type_name not in ("conv", "fullc")):
                    continue  # only layers with an int8 kernel route
                if any(kk == k for kk, _ in
                       (self.net_cfg.defcfg
                        + self.net_cfg.layercfg[idx])):
                    continue  # explicitly configured: the user wins
                self.net_cfg.layercfg[idx].append((k, v))
                applied.setdefault(lname, {})[k] = v
        return applied

    def _cast(self, tree):
        if (self.compute_dtype == jnp.float32
                or self._graph_dtype_plan is not None):
            # an autocast dtype plan owns the casts per layer
            # (Network.forward); a wholesale bf16 pre-cast here would
            # round the f32-stamped layers' inputs before they ever
            # ran
            return tree
        return jax.tree.map(
            lambda a: a.astype(self.compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    def _host_input(self, data: np.ndarray) -> np.ndarray:
        """Input image batch as staged to device.

        Under dtype=bfloat16 the default stages bf16: the cast happens
        on the HOST, halving the H2D transfer (the step's _cast then
        no-ops; labels/mask stay f32). `stage_dtype = float32` flips
        the trade: stage f32 (2x bytes) and let the step's in-jit
        _cast do it on DEVICE, fused into the first conv - wins when
        the host CPU, not the link, is the staging bottleneck (an
        AlexNet b256 host cast is ~40M elements, tens of ms
        single-threaded; no benchmark cell streams input yet, so
        neither side has a chip number)."""
        if self.net.integer_input:
            # token ids stay integers from the batch to the `embed`
            # layer: a bf16 cast cannot hold an id over 256
            if not np.issubdtype(np.asarray(data).dtype, np.integer):
                raise TypeError(
                    "this net reads token ids (its input feeds an "
                    f"`embed` layer): the batch must be integers, got "
                    f"{np.asarray(data).dtype}")
            return np.asarray(data).astype(np.int32, copy=False)
        if self.device_augment and data.dtype == np.uint8:
            # raw pixels stage as uint8: 1/4 the f32 H2D bytes and
            # ZERO host arithmetic; the in-step augment casts on device
            return data
        if (self.compute_dtype == jnp.float32
                or self.stage_dtype == "float32"
                or (self.device_augment and self.stage_dtype != "bfloat16")):
            # device_augment defaults to f32 staging (integer pixel
            # values; no host cast) - stage_dtype=bfloat16 opts into
            # the halved transfer at host-cast cost (lossless for
            # integer-valued pixels <= 256). copy=False: an
            # already-f32 batch must not pay a 150 MB memcpy
            return data.astype(np.float32, copy=False)
        return _bf16_cast(data)

    def _compile(self) -> None:
        net = self.net
        # rebuilt executables get re-registered on first dispatch (the
        # registry is idempotent per fingerprint; shapes key the cache)
        self._flight_fps = {}
        # ZeRO effective stage for THIS mesh (docs/parallel.md): stages
        # >= 2 need a real 'data' axis to cut over; a single-device or
        # data-less mesh compiles the replicated stage-0 program (the
        # same degradation rule zero1_shardings applies to stage 1)
        dsize = self.mesh.shape.get("data", 1)
        zrun = self.zero_stage if dsize > 1 else min(self.zero_stage, 1)
        if zrun >= 2:
            extra_axes = [a for a in self.mesh.axis_names
                          if a not in ("data", "model")
                          and self.mesh.shape[a] > 1]
            if extra_axes:
                raise ValueError(
                    f"zero_stage={self.zero_stage} composes with "
                    f"'data'/'model' mesh axes only; axes {extra_axes} "
                    "drive layers that shard_map over the full mesh "
                    "(ring/ulysses attention, pipelined stacks, moe), "
                    "which cannot nest inside the manual-'data' ZeRO "
                    "region - use zero_stage<=1 on seq/pipe/expert "
                    "meshes")
            for lk, d in self.updaters.items():
                for pn, up in d.items():
                    if not getattr(up, "zero_shardable", False):
                        raise ValueError(
                            f"updater '{up.kind or type(up).__name__}' "
                            f"({lk}.{pn}) declares zero_shardable="
                            "False (its math reduces over the full "
                            "tensor, so a per-shard update computes "
                            "different results); use zero_stage<=1")
            for idx, _info in enumerate(self.net_cfg.layers):
                lay = self.net.layer_objs[idx]
                if (getattr(lay, "type_name", "") == "batch_norm"
                        and getattr(lay, "global_stats", 0)):
                    raise ValueError(
                        "batch_norm global_stats=1 (sync-BN) needs "
                        "global-batch statistics, but zero_stage>=2 "
                        "runs the forward per data shard (per-shard "
                        "stats, the reference's per-GPU semantics); "
                        "use zero_stage<=1 with sync-BN")
        self._zero_run = zrun
        eval_node_ids = sorted({nid for _, nid in self.eval_nodes})
        scale = 1.0 / (self.batch_size * self.update_period)
        update_period = self.update_period
        updaters = self.updaters
        # train metrics accumulate on device inside the step (the
        # reference computes them from the same forward pass,
        # nnet_impl-inl.hpp:174-180; a per-step host readback here would
        # serialize the device - metric_jit.py)
        from cxxnet_tpu.utils import metric_jit
        metric_specs = self.train_metric.specs
        metric_fns = [metric_jit.create_step_fn(name)
                      for name, _ in metric_specs]
        eval_train = bool(self.eval_train and metric_specs)
        # captured at build time: the jitted step's return arity (2- vs
        # 3-tuple) is baked into the compiled function, so update()
        # must branch on what was BUILT, not on a check_nan later
        # toggled through set_param
        check_nan = self._check_nan_built = bool(self.check_nan)

        def metric_rows(outs, labels, mask, rng, base):
            """Stacked (n_metrics, 2) device rows of (sum, count); the
            single definition both the train and eval steps fold in."""
            rows = []
            for i, ((_, field), fn, (_, nid)) in enumerate(
                    zip(metric_specs, metric_fns, self.eval_nodes)):
                pred = outs[nid].reshape(outs[nid].shape[0], -1)
                s, c = fn(pred, labels[field], mask,
                          jax.random.fold_in(rng, base + i))
                rows.append(jnp.stack([s, c]))
            return jnp.stack(rows)

        from cxxnet_tpu.layers.base import active_step
        from cxxnet_tpu.parallel.mesh import active_mesh

        daug = None
        if self.device_augment:
            from cxxnet_tpu.ops.augment_jit import make_device_augment
            dc = self._daug_cfg
            mean_loader = None
            if dc.get("image_mean"):
                def mean_loader(path=dc["image_mean"]):
                    # lazy: called at TRACE time (first update), after
                    # the iterator's init had its chance to create the
                    # mean file on a fresh dataset
                    if not os.path.exists(path):
                        raise FileNotFoundError(
                            f"device_augment: mean image '{path}' not "
                            "found; run the data pipeline once (the "
                            "iterator creates it) or point image_mean "
                            "at an existing mean file")
                    from cxxnet_tpu.io.augment import load_mean_image
                    return load_mean_image(path)
            mean_values = None
            if dc.get("mean_value"):
                b_, g_, r_ = (float(t)
                              for t in dc["mean_value"].split(","))
                mean_values = (b_, g_, r_)
            daug = make_device_augment(
                tuple(self.net_cfg.input_shape),
                mean_loader=mean_loader, mean_values=mean_values,
                scale=float(dc.get("scale", "1.0")),
                rand_crop=int(dc.get("rand_crop", "0")),
                rand_mirror=int(dc.get("rand_mirror", "0")),
                mirror=int(dc.get("mirror", "0")),
                crop_y_start=int(dc.get("crop_y_start", "-1")),
                crop_x_start=int(dc.get("crop_x_start", "-1")),
                max_random_contrast=float(
                    dc.get("max_random_contrast", "0")),
                max_random_illumination=float(
                    dc.get("max_random_illumination", "0")))
        self._augment_fn = daug

        # zero_stage>=2 traces the TRAIN forward inside a manual-'data'
        # shard_map region (per-device values): the mesh-keyed op
        # routes (per-shard batch_norm, fullc_gather, Pallas device
        # routes) must decline there - their plain per-device fallback
        # IS the right semantics inside the region (batch_norm's local
        # stats are bitwise the stats its shard_map route computes) -
        # so the region binds no active mesh. Eval keeps self.mesh.
        fwd_mesh = None if zrun >= 2 else self.mesh
        # layers that count while they run (layers/moe.py): their
        # counters ride in the step's state, the last step's values,
        # and are fetched after it (`fetch_counters`), never inside
        count_layers = zrun < 2 and any(
            hasattr(l, "apply_with_stats") for l in net.layer_objs)
        self._count_layers = count_layers

        def loss_fn(params, data, extras, labels, mask, rng, step):
            cparams = self._cast(params)
            if daug is not None:
                with jax.named_scope("augment"):
                    data = daug(data, jax.random.fold_in(rng, 0xA6), True)
            inputs = {0: self._cast(data)}
            for i, e in enumerate(extras):
                inputs[1 + i] = self._cast(e)
            counters = {} if count_layers else None
            with active_mesh(fwd_mesh), active_step(step):
                values, loss = net.forward(
                    cparams, inputs, train=True, rng=rng,
                    labels=labels, mask=mask, counters=counters)
            outs = {nid: values[nid].astype(jnp.float32)
                    for nid in eval_node_ids}
            if count_layers:
                # beside the eval nodes' values, under a key no node
                # has (a pytree dict's keys must sort: all integers)
                outs[-1] = counters
            return loss.astype(jnp.float32) * scale, outs

        # remat=1: one `jax.checkpoint` around each layer whose class
        # says it is worth it (kda, glu_ffn, gqa - Network._run_layer): the
        # backward keeps those layers' inputs and recomputes what is
        # inside them - trades FLOPs for memory, the standard lever for
        # long sequences on TPU. mla and moe keep their activations:
        # their second forward bought a third of the memory a
        # millisecond. (One checkpoint round the whole loss, which this
        # key used to mean, saved nothing: the backward's recomputation
        # held every activation again.)
        net.remat = bool(self.remat)
        if net.remat and not self.silent:
            held = net.checkpointed
            kinds = collections.Counter(s.split(".")[0] for s in held)
            telemetry.stdout(
                f"remat: {len(held)} of {len(net.layer_objs)} "
                "layers checkpointed ("
                + (", ".join(f"{k} x{n}" for k, n in kinds.items())
                   or "no layer of a kind worth it") + ")")

        # ZeRO-2/3 sharding trees (parallel/sharding.py): the per-weight
        # 'data' cut shared by optimizer state, gradients/accumulator
        # and (stage 3) the parameters themselves
        zdims = zshard = scatter_specs = gather_specs = None
        zshapes = None
        if zrun >= 2:
            from cxxnet_tpu.parallel.sharding import (
                zero2_shardings, zero_partition_dims, zero_region_specs)
            # one abstract init trace shared by every zero helper (it
            # scales with the model, and ZeRO targets big models)
            zshapes = jax.eval_shape(net.init_params,
                                     jax.random.PRNGKey(0))
            zdims = zero_partition_dims(self.mesh, self.net,
                                        self._pshard, zshapes)
            zshard = zero2_shardings(self.mesh, self.net, self._pshard,
                                     zshapes, zdims)
            scatter_specs, gather_specs = zero_region_specs(
                self.mesh, self.net, self._pshard, zshapes, zdims)

        grad_inner = jax.value_and_grad(loss_fn, has_aux=True)
        grad_and_loss = grad_inner
        if zrun >= 2:
            # The cross-replica weight-update sharding recipe
            # (arXiv:2004.13336) needs the gradients in UNREDUCED
            # per-device form - GSPMD only exposes them post-allreduce -
            # so the fwd/bwd runs manual over 'data' (shard_map; every
            # other mesh axis stays auto, i.e. the tensor-parallel
            # 'model' placement keeps riding GSPMD) and ends in an
            # explicit psum_scatter: the literal reduce-scatter the
            # jaxpr audit asserts on. Everything after (accumulate,
            # updater, counters, guard) stays plain GSPMD on the
            # zero-sharded global values.
            from cxxnet_tpu.parallel.sharding import shard_map_manual

            def _scatter(grads):
                # reduce-scatter eligible weights onto their zero cut;
                # ineligible ones psum (replicated update, stage-0
                # semantics for that tensor)
                return {
                    lk: {pn: (lax.psum(g, "data")
                              if zdims[lk][pn] is None else
                              lax.psum_scatter(
                                  g, "data",
                                  scatter_dimension=zdims[lk][pn],
                                  tiled=True))
                         for pn, g in d.items()}
                    for lk, d in grads.items()}

            def zero_region(params, data, extras, labels, mask, rng,
                            step):
                # per-device RNG stream: random layers (dropout, device
                # augment) must not draw the same local pattern on
                # every data shard
                rng = jax.random.fold_in(rng, lax.axis_index("data"))
                (loss, outs), grads = grad_inner(
                    params, data, extras, labels, mask, rng, step)
                with jax.named_scope("grad_reduce"):
                    grads = _scatter(grads)
                return (lax.psum(loss, "data"), outs), grads

            dspec = P("data")
            # params enter replicated-over-'data' (P()): under stage 3
            # they LIVE on their zero cut between steps, so GSPMD
            # inserts one all-gather per weight at the region boundary
            # - the just-in-time gather, one op per layer's weight,
            # placed by the scheduler (a manual 'data' in_spec on a
            # tensor that also rides the auto 'model' axis trips an
            # XLA manual-subgroup partitioner check in this jax)
            param_in = gather_specs
            grad_and_loss = shard_map_manual(
                zero_region, self.mesh, ("data",),
                in_specs=(param_in, dspec,
                          (dspec,) * self.net_cfg.extra_data_num,
                          {f: dspec
                           for f in self.net_cfg.label_name_map},
                          dspec, P(), P()),
                out_specs=((P(), {nid: dspec
                                  for nid in eval_node_ids}),
                           scatter_specs))

        def train_step(state, data, extras, labels, mask, rng):
            # per-forward training-step counter (updates so far) for
            # step-dependent layers (insanity anneal)
            step = state["epoch"] * update_period + state["count"]
            (loss, outs), grads = grad_and_loss(
                state["params"], data, extras, labels, mask, rng, step)
            if update_period == 1:
                # state["accum"] is invariantly all-zero between
                # updates; adding it would stream the whole gradient-
                # sized zero tree through HBM every step for nothing
                accum = grads
            else:
                with jax.named_scope("accum"):
                    accum = jax.tree.map(jnp.add, state["accum"], grads)
            count = state["count"] + 1
            do_update = count >= update_period

            def apply_updates(args):
                params, ustate, accum = args
                new_params = jax.tree.map(lambda x: x, params)
                new_ustate = jax.tree.map(lambda x: x, ustate)
                for lk, d in updaters.items():
                    for pn, up in d.items():
                        if lk not in params or pn not in params[lk]:
                            continue
                        w = params[lk][pn]
                        if zrun == 2 and zdims[lk][pn] is not None:
                            # slice the replicated weight down to this
                            # device's zero shard (no comm - a local
                            # dynamic-slice): the updater then runs at
                            # 1/N FLOPs on shard-shaped state/grad, and
                            # the params out_sharding all-gathers the
                            # fresh weights once per update. Stage 3
                            # skips the slice - params arrive sharded.
                            w = lax.with_sharding_constraint(
                                w, zshard[lk][pn])
                        with jax.named_scope(lk):
                            st, w = up.apply(ustate[lk][pn], w,
                                             accum[lk][pn], state["epoch"])
                        new_params[lk][pn] = w
                        new_ustate[lk][pn] = st
                # graftlint: disable=GL007 the zero tree inherits accum's zero-stage sharding via donation/out_shardings
                zero = jax.tree.map(jnp.zeros_like, accum)
                return new_params, new_ustate, zero

            with jax.named_scope("update"):
                if update_period == 1:
                    # do_update is tautologically true every step; a
                    # lax.cond here is not just dead weight - the
                    # conditional boundary blocks XLA from fusing the
                    # optimizer into the backward fusions (measured ~6%
                    # of AlexNet b256 device step time as a standalone
                    # %conditional in the round-4 on-chip profile)
                    params, ustate, _ = apply_updates(
                        (state["params"], state["ustate"], accum))
                    accum = {}
                else:
                    params, ustate, accum = lax.cond(
                        do_update, apply_updates, lambda a: a,
                        (state["params"], state["ustate"], accum))
            tmetric = state["tmetric"]
            if eval_train:
                with jax.named_scope("metric"):
                    rows = metric_rows(outs, labels, mask, rng, 1000)
                    # Kahan-compensated sum in column 0; plain count in 2
                    s, comp, cnt = (tmetric[:, 0], tmetric[:, 1],
                                    tmetric[:, 2])
                    y = rows[:, 0] - comp
                    t = s + y
                    tmetric = jnp.stack(
                        [t, (t - s) - y, cnt + rows[:, 1]], axis=1)
            new_state = {
                "params": params,
                "ustate": ustate,
                "accum": accum,
                "count": jnp.where(do_update, 0, count),
                "epoch": state["epoch"] + do_update.astype(jnp.int32),
                "tmetric": tmetric,
            }
            if count_layers:
                new_state["counters"] = outs[-1]
            if not check_nan:
                return new_state, loss
            # divergence guard, fully in-jit: all-finite over loss,
            # updated params, and (update_period>1) the gradient
            # accumulator - a micro-step whose grads go NaN with a
            # finite loss leaves params untouched, so checking params
            # alone would commit the NaN into accum and make every
            # retry of that update non-finite. update_period==1 skips
            # accum: it is invariantly zero post-update and NaN grads
            # reach params in the same step. A non-finite step selects
            # the ENTIRE old state (params, updater state, grad accum,
            # counters, train metrics) - a select, not a host copy
            check_tree = {"params": new_state["params"]}
            if update_period > 1:
                check_tree["accum"] = new_state["accum"]
            finite = jax.tree.reduce(
                lambda acc, leaf: jnp.logical_and(
                    acc, jnp.all(jnp.isfinite(leaf))),
                check_tree, jnp.isfinite(loss))
            new_state = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_state, state)
            return new_state, loss, finite

        def eval_step(params, data, extras):
            cparams = self._cast(params)
            if daug is not None:
                # deterministic eval augment (center crop, no mirror/
                # jitter); the key is never consumed on this path
                data = daug(data, jax.random.PRNGKey(0), False)
            inputs = {0: self._cast(data)}
            for i, e in enumerate(extras):
                inputs[1 + i] = self._cast(e)
            with active_mesh(self.mesh):
                values, _ = net.forward(cparams, inputs, train=False)
            return {nid: values[nid].astype(jnp.float32)
                    for nid in range(net.cfg.num_nodes)
                    if values[nid] is not None}

        def eval_metric_step(params, data, extras, labels, mask, rng):
            """Forward + per-batch metric rows fully on device: the eval
            loop keeps the tiny (n_metrics, 2) results and sums them on
            the host in float64 after the dataset - no per-batch
            readback of node outputs (nnet_impl-inl.hpp:224-245 does
            that on the host every batch) and no cross-batch f32
            accumulation drift."""
            outs = eval_step(params, data, extras)
            return metric_rows(outs, labels, mask, rng, 2000)

        rep, shd = self._replicated, self._batch_sharded
        dshd = self._data_sharded
        # ustate prefix tree: one sharding per weight, prefixing the inner
        # updater-state dict ({m} / {m1,m2}); mirrors _init_state's filter
        ushard = self._pshard
        if zrun >= 1:
            # ZeRO-1 / update_on_server analog: optimizer state sharded
            # over 'data' (parallel/sharding.py:zero1_shardings)
            from cxxnet_tpu.parallel.sharding import zero1_shardings
            ushard = zero1_shardings(self.mesh, self.net, self._pshard,
                                     zshapes, zdims)
        ustate_prefix = {
            lk: {pn: ushard[lk][pn] for pn in d
                 if pn in ushard.get(lk, {})}
            for lk, d in self.updaters.items()}
        self._ustate_shard = ustate_prefix
        # stage 3 keeps the PARAMETERS on their zero cut between steps;
        # stage 2 additionally stores the update_period>1 accumulator
        # sharded (each microstep reduce-scatters into it)
        pstore = self._pshard
        if zrun == 3:
            from cxxnet_tpu.parallel.sharding import zero3_shardings
            pstore = zero3_shardings(self.mesh, self.net, self._pshard,
                                     zshapes, zdims)
        self._params_store_shard = pstore
        state_shardings = {
            "params": pstore, "ustate": ustate_prefix,
            "accum": {} if update_period == 1 else (
                zshard if zrun >= 2 else self._pshard),
            "count": rep, "epoch": rep, "tmetric": rep,
        }
        if count_layers:
            state_shardings["counters"] = rep
        self._state_shardings = state_shardings
        label_shardings = {
            f: shd for f in self.net_cfg.label_name_map}
        eshd = (shd,) * self.net_cfg.extra_data_num
        self._train_step = jax.jit(
            train_step,
            in_shardings=(state_shardings, dshd, eshd, label_shardings,
                          shd, rep),
            out_shardings=((state_shardings, rep, rep) if check_nan
                           else (state_shardings, rep)),
            donate_argnums=(0,))

        # fused multi-step dispatch (steps_per_dispatch=K): ONE jitted
        # lax.scan carries state through K full train steps. The scan
        # body IS train_step - same math, same metric folds, same
        # in-jit guard rollback - with the per-step RNG folded ON
        # DEVICE from the identical (seed, step_counter) stream, so
        # the trajectory is bitwise K streamed updates. Per-microstep
        # (loss, finite) vectors come back so the divergence guard and
        # loss gauge keep exact per-step semantics with one host
        # readback per chunk. Chunk length K is read from the stacked
        # leading axis (a short final chunk just retraces).
        def _chunked(s: NamedSharding) -> NamedSharding:
            return NamedSharding(self.mesh, P(None, *s.spec))

        cshd, cdshd = _chunked(shd), _chunked(dshd)
        ceshd = (cshd,) * self.net_cfg.extra_data_num
        clabel_shardings = {f: cshd for f in self.net_cfg.label_name_map}
        self._chunk_stack_shardings = (cdshd, ceshd, clabel_shardings,
                                       cshd)

        def train_chunk(state, data, extras, labels, mask, step_idx,
                        base_rng):
            def body(st, xs):
                d, ex, lb, mk, idx = xs
                rng = jax.random.fold_in(base_rng, idx)
                if check_nan:
                    st, loss, finite = train_step(st, d, ex, lb, mk,
                                                  rng)
                else:
                    st, loss = train_step(st, d, ex, lb, mk, rng)
                    finite = jnp.bool_(True)
                return st, (loss, finite)

            # unroll=True: ONE flat XLA program with the K microstep
            # bodies inlined - the whole point (hand the compiler the
            # full dataflow region so it can schedule across step
            # boundaries), and the condition for the bitwise guarantee:
            # a rolled while-loop body compiled the fc backward with
            # ~1-ULP different contractions than the standalone step
            # (measured on jax-cpu), while the inlined bodies compile
            # identically. Cost: compile time grows with K, and each
            # distinct chunk length (e.g. the short round-end chunk)
            # retraces once - keep K modest (docs/PERFORMANCE.md).
            state, (losses, finites) = lax.scan(
                body, state, (data, extras, labels, mask, step_idx),
                unroll=True)
            return state, losses, finites

        self._train_chunk = jax.jit(
            train_chunk,
            in_shardings=(state_shardings, cdshd, ceshd,
                          clabel_shardings, cshd, rep, rep),
            out_shardings=(state_shardings, rep, rep),
            donate_argnums=(0,))
        # device-side stacker: K staged batches -> one chunk. Pure
        # data movement after the per-batch staging pipeline, which is
        # the structural trajectory-equality argument (stage_chunk).
        self._stack_chunk = jax.jit(
            lambda *bs: jax.tree.map(lambda *ls: jnp.stack(ls), *bs),
            out_shardings=self._chunk_stack_shardings)
        # eval consumes params at their BETWEEN-STEPS layout: under
        # zero_stage=3 they arrive sharded and GSPMD inserts the
        # gathers where the forward needs full tensors
        self._eval_step = jax.jit(
            eval_step, in_shardings=(pstore, dshd, eshd),
            out_shardings=shd)

        # dedicated inference executable (docs/SERVING.md): donation-
        # free, dropout-free, and - unlike eval_step, which returns
        # EVERY node's value - computes only the requested node, so
        # XLA dead-code-eliminates the rest and the host reads back
        # one output tensor per batch instead of the whole node set
        # (the wrapper predict path used to fetch every intermediate).
        # Batch-size POLYMORPHIC: the first dim is whatever the caller
        # stages, and jit caches one executable per distinct shape -
        # the serving layer's per-bucket executables are exactly this
        # cache (serve/server.py counts it to prove zero steady-state
        # recompiles). One jit per requested node, built lazily;
        # predict/extract/serve all share the cache.
        def infer_step(node, params, data, extras):
            outs = eval_step(params, data, extras)
            return outs[node]

        infer_jits: Dict[Any, Any] = {}
        pass_infer = bool(self._pipeline.infer_passes
                          if self._pipeline is not None else False)

        def infer_graph_step(node, net2, pfn, params, data, extras):
            """Inference forward over the pass-transformed graph
            (nnet/passes.py): params remapped/folded in-jit by pfn
            (pruned weights are unused arguments jit drops), then the
            same eval semantics as eval_step - deterministic augment,
            train=False forward, f32 readout of the requested node."""
            gp = self._cast(pfn(params))
            if daug is not None:
                data = daug(data, jax.random.PRNGKey(0), False)
            inputs = {0: self._cast(data)}
            for i, e in enumerate(extras):
                inputs[1 + i] = self._cast(e)
            with active_mesh(self.mesh):
                values, _ = net2.forward(gp, inputs, train=False)
            return values[node].astype(jnp.float32)

        def infer_fn(node: int):
            import functools
            if not pass_infer:
                fn = infer_jits.get(node)
                if fn is None:
                    fn = jax.jit(
                        functools.partial(infer_step, node),
                        in_shardings=(pstore, dshd, eshd),
                        out_shardings=shd)
                    infer_jits[node] = fn
                return fn
            # pass-transformed inference: one executable per
            # (node, fold calibration epoch) - a recalibration
            # rebuilds; existing callables (e.g. a running Server's)
            # keep working on their frozen stats
            key = (node, self._fold_epoch)
            fn = infer_jits.get(key)
            if fn is None:
                net2, pfn, _gm = self._build_infer_graph(node)
                fn = jax.jit(
                    functools.partial(infer_graph_step, node, net2,
                                      pfn),
                    in_shardings=(pstore, dshd, eshd),
                    out_shardings=shd)
                infer_jits[key] = fn
            return fn

        self._infer_fn = infer_fn
        # exposed so a recalibration can evict the previous epoch's
        # compiled executables (_calibrate_staged)
        self._infer_jits = infer_jits
        self._eval_metric_step = None
        if metric_specs:
            self._eval_metric_step = jax.jit(
                eval_metric_step,
                in_shardings=(pstore, dshd, eshd, label_shardings,
                              shd, rep),
                out_shardings=rep)

    # ------------------------------------------------------------------
    # dispatch introspection (telemetry/flight.py)
    # ------------------------------------------------------------------
    def _register_executable(self, site: str, key, kind: str,
                             name: str, shape, arg_bytes: int,
                             donated: int) -> str:
        """First sight of one compiled program shape at a jit-cache
        site: fingerprint it and register it with the executable
        registry (the `/executables` plane + flight-recorder entries
        name executables by this fingerprint). Callers cache the
        result in _flight_fps so the steady state pays one dict hit."""
        from cxxnet_tpu.telemetry.flight import fingerprint
        fp = fingerprint(site, *key)
        telemetry.get().executables.register(
            fp, name=name, kind=kind, shape=str(tuple(shape)),
            arg_bytes=int(arg_bytes), device=jax.default_backend(),
            donated=donated)
        self._flight_fps[key] = fp
        return fp

    @contextlib.contextmanager
    def _flight_record(self, site: str, key, kind: str, name: str,
                       shape, nbytes: int, donated: int = 0,
                       bucket: Optional[int] = None, fields=None):
        """One dispatch under flight-recorder + executable-registry
        accounting (the single definition every trainer dispatch site
        wraps itself in): register the program shape on first sight,
        open a ring entry when armed, close it WITH the error if the
        block raises (a failed dispatch must not read as a hung one -
        only one that never returns stays in-flight), and count the
        dispatch on success."""
        tel = telemetry.get()
        fp = self._flight_fps.get(key)
        if fp is None:
            fp = self._register_executable(
                site, key, kind=kind, name=name, shape=shape,
                arg_bytes=nbytes, donated=donated)
        fl = (tel.flight.start(
                  kind, fp=fp,
                  bucket=shape[0] if bucket is None else bucket,
                  nbytes=int(nbytes), fields=fields)
              if tel.flight.enabled else None)
        try:
            yield
        except BaseException as e:
            tel.flight.fail(fl, f"{type(e).__name__}: {e}")
            raise
        tel.flight.finish(fl)
        tel.executables.count_dispatch(fp)

    # ------------------------------------------------------------------
    # training api
    # ------------------------------------------------------------------
    def start_round(self, round_counter: int) -> None:
        self.round = round_counter
        if self.profiler is not None:
            # close out + report the previous round's profile, then arm
            # the next (the trace_round-th profiled round also dumps
            # the trace). The stderr summary stays profile=1-only; a
            # telemetry-only profiler feeds round records silently.
            if self.profile and self.profiler.step_s:
                telemetry.stderr(self.profiler.summary() + "\n")
            self.profiler.round_end()
            self.profiler.round_start()

    def finish_round_profile(self) -> None:
        """Close the round's trace right after the update loop so the
        dump scopes to TRAINING steps only, not the eval passes or the
        checkpoint save that follow in the round (round_end is
        idempotent; start_round still prints the summary)."""
        if self.profiler is not None:
            self.profiler.round_end()

    def profile_summary(self) -> str:
        """Summary line for the round in progress ('' when profiling is
        off or no steps ran); closes any open trace either way. A
        telemetry-only profiler (profile=0) reports nothing here - the
        stderr surface under profile=0 is pinned byte-identical."""
        if self.profiler is None:
            return ""
        self.profiler.round_end()
        if not self.profile or not self.profiler.step_s:
            return ""
        return self.profiler.summary()

    def round_stats(self) -> Optional[Dict[str, float]]:
        """Step/data timing stats of the round in progress (None when
        nothing is instrumented or no steps ran) - the payload of the
        telemetry `round` event/metrics record (main.py emits them)."""
        if self.profiler is None:
            return None
        return self.profiler.stats()

    def _compute_local_rows(self) -> Tuple[int, int]:
        """(rows this process feeds, their global start row) under the
        batch sharding - batch/nproc on a pure-data mesh, but the FULL
        batch when the batch dim is replicated across processes (e.g. a
        cross-host 'seq' mesh, where hosts split the sequence dim
        instead - parallel/ring.py). Mesh-invariant after _build_net,
        so computed once there (this sits on the per-step hot path)."""
        if jax.process_count() == 1:
            return self.batch_size, 0
        shd = self._batch_sharded
        imap = shd.devices_indices_map((self.batch_size,))
        spans = {imap[d][0].indices(self.batch_size)[:2]
                 for d in shd.addressable_devices}
        total = sum(stop - start for start, stop in spans)
        lo = min(start for start, _ in spans)
        hi = max(stop for _, stop in spans)
        if total != hi - lo:
            # put_global_rows slices the host batch as ONE contiguous
            # range; a mesh/device ordering that fragments a process's
            # row ownership would silently feed wrong rows - fail loudly
            raise RuntimeError(
                f"process-local batch rows are not contiguous: spans="
                f"{sorted(spans)} over batch {self.batch_size} (mesh "
                f"device order fragments row ownership; reorder the "
                f"mesh axes or devices so each process owns one range)")
        return total, lo

    @property
    def _local_batch(self) -> int:
        return self._local_rows[0]

    @property
    def _local_row_start(self) -> int:
        return self._local_rows[1]

    def _put_data(self, data: np.ndarray) -> jax.Array:
        """Stage the input tensor under _data_sharded; correct even
        when the 'seq' axis spans processes (put_global_rows)."""
        gshape = (self.batch_size,) + data.shape[1:]
        return distributed.put_global_rows(
            self._host_input(data), self._data_sharded, gshape,
            self._local_row_start)

    def _pad_batch(self, batch: DataBatch, train: bool = False):
        """Pad a short batch up to the local batch (static shapes).

        Sparse CSR batches (data.h:96-181) densify to the net input
        shape first - the jitted step consumes static dense tensors.

        `train`: every DELIVERED row is valid. num_batch_padd marks
        round_batch wrap-fill rows, which are REAL instances consumed
        early from the next epoch - the reference trains them and trims
        them only from eval/pred (nnet_impl-inl.hpp:239); masking them
        in training would mean they are never trained at all (the
        iterator deliberately does not re-serve them). Eval paths keep
        the trimming mask.

        Returns (data, label, mask, extras) where extras are the padded
        extra-data arrays feeding input nodes 1..k (network.py)."""
        b = batch.batch_size
        if batch.is_sparse():
            c, y, x = self.net_cfg.input_shape
            batch = DataBatch(
                data=batch.to_dense(c * y * x).reshape(b, c, y, x),
                label=batch.label, inst_index=batch.inst_index,
                num_batch_padd=batch.num_batch_padd,
                extra_data=batch.extra_data)
        n_extra = self.net_cfg.extra_data_num
        extras = list(batch.extra_data[:n_extra])
        if len(extras) < n_extra:
            raise ValueError(
                f"net declares extra_data_num={n_extra} but the batch "
                f"carries {len(extras)} extra arrays (use attachtxt or "
                "fill DataBatch.extra_data)")
        valid = np.ones(b, np.float32) if train else batch.valid_mask()
        if b == self._local_batch:
            return batch.data, batch.label, valid, tuple(
                np.asarray(e, np.float32) for e in extras)
        if b > self._local_batch:
            raise ValueError("batch larger than configured batch_size")
        pad = self._local_batch - b

        def padrows(a):
            a = np.asarray(a)
            return np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)

        mask = np.concatenate([valid, np.zeros(pad, np.float32)])
        return (padrows(batch.data), padrows(batch.label), mask,
                tuple(padrows(e).astype(np.float32) for e in extras))

    def step_hlo(self, batch: StagedBatch) -> str:
        """The compiled train step for this batch (or a StagedBatch of
        `jax.ShapeDtypeStruct`s) as HLO text: every instruction, inside
        fused computations too, with the layer / `update` scope it was
        traced under in its `op_name`. What to read when `/executables`
        or a device trace names an operation (docs/OBSERVABILITY.md
        "Reading a device trace"). One compile per call, nothing kept."""
        return self._train_step.lower(
            self.state, batch.data, batch.extras, batch.labels,
            batch.mask, jax.random.PRNGKey(0)).compile().as_text()

    def fetch_counters(self) -> Dict[str, float]:
        """What the layers counted in the last step dispatched
        (`<layer key>.<name>`, layers/moe.py), fetched from the step's
        state - this waits for that step - and set as gauges
        `train.<layer key>.<name>` in the telemetry registry. {} for a
        net that counts nothing."""
        got = jax.device_get((self.state or {}).get("counters", {}))
        out = {k: float(v) for k, v in got.items()}
        for k, v in out.items():
            telemetry.set_gauge(f"train.{k}", v)
        return out

    def stage_batch(self, batch: DataBatch) -> StagedBatch:
        """Stage a batch's device buffers ONCE for repeated update()
        calls (see StagedBatch). The staging runs the exact per-step
        pipeline (pad, host cast, put under the step's in_shardings),
        so a staged update is trajectory-identical to a streamed one."""
        if fault.fault_point("stage_batch") == "corrupt":
            # NaN-poison the batch (fault injection): models a decode /
            # DMA error feeding garbage into the step - the divergence
            # guard must drop the step, not ship NaN into the weights
            bad = np.full(np.shape(batch.data), np.nan, np.float32)
            batch = DataBatch(
                data=bad, label=batch.label,
                inst_index=batch.inst_index,
                num_batch_padd=batch.num_batch_padd,
                extra_data=batch.extra_data)
        data, label, mask, extras = self._pad_batch(batch, train=True)
        labels = self._label_fields(label.astype(np.float32))
        shd = self._batch_sharded
        return StagedBatch(
            data=self._put_data(data),
            extras=tuple(distributed.put_global(e, shd)
                         for e in extras),
            labels={k: distributed.put_global(v, shd)
                    for k, v in labels.items()},
            mask=distributed.put_global(mask.astype(np.float32), shd),
            n_examples=batch.batch_size - batch.num_batch_padd)

    def stage_chunk(self, batches: Sequence) -> StagedChunk:
        """Stack K batches into one fused-dispatch chunk (StagedChunk).
        Each unstaged batch runs the EXACT per-batch staging pipeline
        (stage_batch), then a jitted device-side stack prepends the
        microstep axis - pure data movement, so a fused chunk is
        trajectory-identical to streaming its batches one by one.
        Accepts DataBatch and StagedBatch mixed; K is len(batches)
        (a short final chunk at round end is fine - the scan reads
        its length from the stacked axis)."""
        if not batches:
            raise ValueError("stage_chunk needs at least one batch")
        staged = [b if isinstance(b, StagedBatch) else
                  self.stage_batch(b) for b in batches]
        data, extras, labels, mask = self._stack_chunk(
            *((s.data, s.extras, s.labels, s.mask) for s in staged))
        return StagedChunk(
            data=data, extras=extras, labels=labels, mask=mask,
            n_examples=tuple(s.n_examples for s in staged))

    def prefetch(self, data_iter, depth: int = 1, chunk: int = 1):
        """Wrap a DataIter so batch k+1 is staged (pad + cast + H2D)
        on a worker thread while step k runs - the reference's
        ThreadBuffer idea applied at the host->device edge
        (io/prefetch.py). update() consumes the staged values with
        zero per-step host work; trajectory-identical to streaming.

        chunk=K assembles fused-dispatch chunks (stage_chunk) on the
        worker instead of single batches - the staging half of
        steps_per_dispatch=K. HBM budget: K*(depth+1) batches resident
        (docs/PERFORMANCE.md)."""
        from cxxnet_tpu.io.prefetch import StagedPrefetcher
        return StagedPrefetcher(self.stage_batch, data_iter, depth,
                                chunk=chunk, chunk_fn=self.stage_chunk)

    # graftlint: hot-path
    def update(self, batch) -> None:
        """One training mini-batch (CXXNetThreadTrainer::Update).
        Accepts a DataBatch (streamed: per-step pad/cast/H2D), a
        StagedBatch (device-resident: zero per-step host work), or a
        StagedChunk (fused: K microsteps in one dispatch)."""
        if isinstance(batch, StagedChunk):
            return self.update_chunk(batch)
        step_idx = self._step_counter
        with StepTraceAnnotation(spans.TRAIN, step_num=step_idx):
            track = bool(self.profile) or self._tel_steps
            t0 = time.perf_counter() if track else 0.0
            if not isinstance(batch, StagedBatch):
                # the streamed path IS one stage_batch call - structural
                # guarantee of the staged/streamed trajectory equivalence.
                # Staging also validates; a rejected batch must raise
                # BEFORE the step counter moves, or a caller that catches
                # the error would silently shift the whole RNG stream
                with TraceAnnotation(spans.TRAIN_STAGE):
                    batch = self.stage_batch(batch)
            with TraceAnnotation(spans.TRAIN_KEY):
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(self.seed + 100), step_idx)
            self._step_counter += 1
            gdata, gextras = batch.data, batch.extras
            glabels, gmask = batch.labels, batch.mask
            n_examples = batch.n_examples
            data_s = 0.0
            if track:
                # host-side prep (padding, casting, H2D staging) vs device
                # step, reported separately by StepProfiler.summary
                t1 = time.perf_counter()
                data_s = t1 - t0
                if self.profiler is not None:
                    self.profiler.add_data(data_s)
                t0 = t1
            # collective-scope fault point (docs/FAULT_TOLERANCE.md
            # "Elastic pod"): the dispatched step carries the pod-wide
            # gradient AllReduce, so kill_rank/hang_rank/delay_collective
            # armed here murder or wedge ONE worker at a deterministic
            # step - every rank hits this point in the same order under
            # SPMD, so @N names the same step on every worker
            fault.fault_point("collective")
            # the step is dispatched asynchronously and train metrics
            # accumulate on device - nothing here blocks on the result, so
            # host-side input prep for batch k+1 overlaps compute of batch
            # k. The _flight_record wrapper spans the dispatch + guard
            # readback (the sync a hung backend wedges in) so a stall dump
            # names this exact executable.
            ok = None
            with self._flight_record(
                    "train_step", ("train_step", tuple(gdata.shape)),
                    kind="train", name=f"train_step@b{gdata.shape[0]}",
                    shape=gdata.shape, nbytes=gdata.nbytes, donated=1,
                    fields={"step": step_idx}):
                with TraceAnnotation(spans.TRAIN_CALL):
                    out = self._train_step(
                        self.state, gdata, gextras, glabels, gmask, rng)
                if self._check_nan_built:
                    # divergence guard: the per-step finite flag must be
                    # read back (a device sync - the cost of check_nan=1;
                    # staging prefetch still overlaps on its worker thread)
                    self.state, loss, finite = out
                    with TraceAnnotation(spans.TRAIN_GUARD):
                        # graftlint: disable=GL002 the guard's documented sync: the finite flag must be read back before the next step commits
                        ok = bool(np.asarray(distributed.fetch_local(finite)))
                else:
                    self.state, loss = out
            if ok is not None:
                self._guard_step(ok, step_idx)
            # host mirror of the device epoch counter (one update per
            # update_period steps) - avoids forcing a device sync per step;
            # guard-dropped steps never advanced the device counters
            self.epoch = self._epoch_base + (
                (self._step_counter - self._skipped_steps)
                // self.update_period)
            # progress beacon for the hang watchdog / absence alert rules
            # (docs/OBSERVABILITY.md): one dict store, no device sync -
            # the step DISPATCHED; a hung backend blocks above, in the
            # step call or the guard readback, and the beacon goes stale
            telemetry.beacon("train.step")
            if track:
                # per-step timing forces a device sync (same cost profile=1
                # always paid; staging prefetch still overlaps on its
                # worker thread) - the price of honest step times
                # graftlint: disable=GL002 honest per-step timing requires the sync - profile/telemetry_steps opt-in only
                jax.block_until_ready(self.state["epoch"])
                step_s = time.perf_counter() - t0
                if self.profiler is not None:
                    # distinct-instance count: wrap/pad rows in
                    # num_batch_padd would inflate images/sec
                    self.profiler.add_step(step_s, n_examples)
                if self._tel_steps:
                    tel = telemetry.get()
                    # graftlint: disable=GL002 loss gauge readback, gated by telemetry_steps=1
                    loss_val = float(np.asarray(
                        distributed.fetch_local(loss)))
                    tel.observe("train.data_s", data_s)
                    tel.observe("train.step_s", step_s)
                    tel.inc("train.images", n_examples)
                    tel.set_gauge("train.loss", loss_val)
                    tel.event("span", name="train.data", secs=data_s,
                              round=self.round, step=step_idx)
                    tel.event("span", name="train.step", secs=step_s,
                              round=self.round, step=step_idx,
                              loss=loss_val, examples=n_examples)

    # graftlint: hot-path
    def update_chunk(self, chunk) -> None:
        """K training microsteps in ONE dispatch (steps_per_dispatch):
        a jitted lax.scan over a StagedChunk - accepts a sequence of
        DataBatch/StagedBatch too (staged + stacked here). One host
        readback per chunk serves the divergence guard, loss gauge and
        per-step accounting for all K microsteps. Trajectory-bitwise-
        identical to K update() calls; the one semantic difference is
        that a DivergenceError can surface up to K-1 microsteps after
        the fatal one (the chunk has already run on device), with the
        in-jit rollback semantics unchanged."""
        with StepTraceAnnotation(spans.TRAIN,
                                 step_num=self._step_counter):
            track = bool(self.profile) or self._tel_steps
            t0 = time.perf_counter() if track else 0.0
            if not isinstance(chunk, StagedChunk):
                # staging validates; a rejected batch must raise BEFORE
                # the step counter moves (same contract as update())
                with TraceAnnotation(spans.TRAIN_STAGE):
                    chunk = self.stage_chunk(chunk)
            k = chunk.n_steps
            first_step = self._step_counter
            with TraceAnnotation(spans.TRAIN_KEY):
                base_rng = jax.random.PRNGKey(self.seed + 100)
                step_idx = distributed.put_global(
                    np.arange(first_step, first_step + k, dtype=np.int32),
                    self._replicated)
            self._step_counter += k
            data_s = 0.0
            if track:
                t1 = time.perf_counter()
                data_s = t1 - t0
                if self.profiler is not None:
                    self.profiler.add_data(data_s)
                t0 = t1
            # same collective-scope fault point as the streamed path: one
            # hit per DISPATCH (K microsteps), still rank-deterministic
            fault.fault_point("collective")
            # flight-recorder entry: one per K-step chunk dispatch, same
            # contract as update()'s (in-flight across the guard readback)
            fin = None
            with self._flight_record(
                    "train_chunk",
                    ("train_chunk", k, tuple(chunk.data.shape)),
                    kind="train",
                    name=f"train_chunk@K{k}b{chunk.data.shape[1]}",
                    shape=chunk.data.shape, nbytes=chunk.data.nbytes,
                    donated=1, bucket=chunk.data.shape[1],
                    fields={"step": first_step, "steps": k}):
                with TraceAnnotation(spans.TRAIN_CALL):
                    self.state, losses, finites = self._train_chunk(
                        self.state, chunk.data, chunk.extras,
                        chunk.labels, chunk.mask, step_idx, base_rng)
                if self._check_nan_built:
                    # ONE readback per chunk (vs one per step streamed) -
                    # the whole point of the fused dispatch; the guard then
                    # walks the per-microstep flags in order, so drop
                    # counts and consecutive-failure accounting match
                    # streaming exactly
                    with TraceAnnotation(spans.TRAIN_GUARD):
                        # graftlint: disable=GL002 ONE guard readback per K-step chunk - the fused dispatch's whole point
                        fin = np.asarray(distributed.fetch_local(finites))
            if fin is not None:
                for i in range(k):
                    self._guard_step(bool(fin[i]), first_step + i)
            self.epoch = self._epoch_base + (
                (self._step_counter - self._skipped_steps)
                // self.update_period)
            # K dispatched microsteps of progress (same beacon the
            # streamed path marks - the watchdog is dispatch-mode-blind)
            telemetry.beacon("train.step", k)
            if track:
                # graftlint: disable=GL002 honest per-chunk timing requires the sync - profile/telemetry_steps opt-in only
                jax.block_until_ready(self.state["epoch"])
                chunk_s = time.perf_counter() - t0
                n_examples = sum(chunk.n_examples)
                if self.profiler is not None:
                    self.profiler.add_chunk(chunk_s, k, n_examples)
                if self._tel_steps:
                    tel = telemetry.get()
                    # graftlint: disable=GL002 per-chunk loss readback, gated by telemetry_steps=1
                    loss_v = np.asarray(distributed.fetch_local(losses),
                                        np.float64)
                    per_s = chunk_s / k
                    for _ in range(k):
                        # per-step amortized cost: keeps the registry's
                        # windowed p50/p99 on a per-STEP scale, comparable
                        # across steps_per_dispatch settings (data_s too -
                        # a non-prefetched chunk stages all K batches here,
                        # and a per-chunk sample would read as a Kx staging
                        # regression next to a K=1 run)
                        tel.observe("train.step_s", per_s)
                        tel.observe("train.data_s", data_s / k)
                    tel.inc("train.images", n_examples)
                    tel.set_gauge("train.loss", float(loss_v[-1]))
                    tel.event("span", name="train.data", secs=data_s,
                              round=self.round, step=first_step)
                    tel.event("span", name="train.chunk", secs=chunk_s,
                              round=self.round, step=first_step, steps=k,
                              loss=[float(v) for v in loss_v],
                              examples=n_examples)

    def _guard_step(self, ok: bool, step_idx: int) -> None:
        """Host half of the divergence guard: count dropped steps and
        abort after max_bad_rounds CONSECUTIVE non-finite steps (the
        jitted step already rolled the state back)."""
        if ok:
            self._bad_consec = 0
            return
        self._bad_consec += 1
        self.bad_rounds += 1
        self._skipped_steps += 1
        telemetry.inc("fault.nan_rollback")
        telemetry.stderr(
            f"divergence guard: non-finite loss/params at update "
            f"{step_idx}; batch dropped, params rolled "
            f"back ({self._bad_consec}/{self.max_bad_rounds} "
            f"consecutive)\n",
            event_kind="fault", type="nan_rollback",
            step=step_idx, consecutive=self._bad_consec,
            max_bad_rounds=self.max_bad_rounds)
        if self._bad_consec >= self.max_bad_rounds:
            raise DivergenceError(
                f"training diverged: {self._bad_consec} consecutive "
                f"non-finite update rounds (loss or params hit NaN/Inf "
                f"every round); lower eta or inspect the data pipeline "
                f"- params remain at the last finite state")

    def update_all(self, data_iter, eval_iters=None,
                   eval_names=None) -> str:
        """Convenience: one full pass (round) over a data iterator,
        then evaluate each of eval_iters (named by eval_names,
        default eval/eval2/...) - the reference's per-round loop body
        (cxxnet_main.cpp:367-405). Returns the concatenated
        reference-format metric string ('' when no eval iters)."""
        data_iter.before_first()
        while data_iter.next():
            self.update(data_iter.value())
        parts = []
        for i, it in enumerate(eval_iters or ()):
            name = (eval_names[i] if eval_names and i < len(eval_names)
                    else ("eval" if i == 0 else f"eval{i + 1}"))
            parts.append(self.evaluate(it, name))
        return "".join(parts)

    # ------------------------------------------------------------------
    # evaluation / inference api
    # ------------------------------------------------------------------
    def _forward_nodes(self, batch: DataBatch) -> Dict[int, np.ndarray]:
        data, _, mask, extras = self._pad_batch(batch)
        gdata = self._put_data(data)
        shd = self._batch_sharded
        gextras = tuple(distributed.put_global(e, shd) for e in extras)
        with self._flight_record(
                "eval_step", ("eval_step", tuple(gdata.shape)),
                kind="eval", name=f"eval_step@b{gdata.shape[0]}",
                shape=gdata.shape, nbytes=gdata.nbytes):
            outs = self._eval_step(self.state["params"], gdata,
                                   gextras)
            valid = int(mask.sum())
            got = {nid: distributed.fetch_local(v)[:valid]
                   for nid, v in outs.items()}
        return got

    def _infer_node(self, batch: DataBatch, node: int) -> np.ndarray:
        """One node's output rows for a batch via the dedicated
        inference executable (_compile's infer_fn): pad to the static
        batch, stage, run, read back ONLY the requested node, trim the
        padding rows. The predict/extract path - evaluate's metric-less
        fallback keeps _forward_nodes (it needs several nodes from one
        forward)."""
        data, _, mask, extras = self._pad_batch(batch)
        gdata = self._put_data(data)
        shd = self._batch_sharded
        gextras = tuple(distributed.put_global(e, shd) for e in extras)
        if self.passes_need_calibration():
            # fold_conv_bn freezes its statistics from the FIRST
            # inference batch (docs/GRAPH_PASSES.md) - staged through
            # this very pipeline, so on a single-shard mesh a
            # single-batch predict is contraction-ULP-identical to
            # the unfolded path (data-sharded meshes: per-shard vs
            # global stats, warned at calibration)
            self._calibrate_staged(
                gdata, gextras,
                distributed.put_global(np.asarray(mask, np.float32),
                                       shd))
        with self._flight_record(
                "infer",
                ("infer", node, self._fold_epoch, tuple(gdata.shape)),
                kind="infer", name=f"infer:n{node}@b{gdata.shape[0]}",
                shape=gdata.shape, nbytes=gdata.nbytes):
            out = self._infer_fn(node)(self.state["params"], gdata,
                                       gextras)
            valid = int(mask.sum())
            got = distributed.fetch_local(out)[:valid]
        return got

    def stage_infer_rows(self, data: np.ndarray, extras: Sequence = ()):
        """Stage an ARBITRARY-row-count inference input under the infer
        executable's in_shardings (the serving layer's bucket staging,
        serve/server.py). Single-process serving only - the multi-
        controller batch-row split of _put_data does not apply; the
        row count must divide over the mesh's data axis (the Server's
        bucket rule guarantees that)."""
        if jax.process_count() > 1:
            raise RuntimeError(
                "stage_infer_rows is single-process (serving a "
                "multi-controller mesh is not supported)")
        gdata = jax.device_put(self._host_input(np.ascontiguousarray(data)),
                               self._data_sharded)
        shd = self._batch_sharded
        gextras = tuple(
            jax.device_put(np.ascontiguousarray(e, dtype=np.float32), shd)
            for e in extras)
        return gdata, gextras

    def infer_rows(self, gdata, gextras=(), node: int = -1) -> jax.Array:
        """Dispatch the inference executable on staged rows (the device
        half of the serving hot path; stage_infer_rows is the host
        half). node=-1 = the final node. Returns the device array -
        the caller decides when to read back."""
        if node < 0:
            node = self.net_cfg.num_nodes - 1
        return self._infer_fn(node)(self.state["params"], gdata,
                                    tuple(gextras))

    # ------------------------------------------------------------------
    # graph passes: infer-graph construction + fold calibration
    # ------------------------------------------------------------------
    def _build_infer_graph(self, node: int):
        """(Network, param_fn, GraphModule) for the pass-transformed
        inference graph of one output node (nnet/passes.py): the
        infer-stage pipeline over a CLONE of the net config - prune
        to the target's ancestors, then fold conv+bn sites whose
        calibration stats exist. Cached per (node, fold epoch)."""
        from cxxnet_tpu.nnet.passes import (
            GraphModule, PassContext, make_param_fn)
        key = (node, self._fold_epoch)
        hit = self._infer_graph_cache.get(key)
        if hit is not None:
            return hit
        gm = GraphModule.from_net_config(
            self.net_cfg.clone(), self.batch_size, self.compute_dtype)
        gm.dtype_plan = dict(self._graph_dtype_plan or {})
        gm = self._pipeline.run_infer(
            gm, PassContext(target_node=node,
                            fold_stats=self._fold_stats,
                            quant_stats=self._quant_stats))
        self._fill_quant_scales(gm)
        net2 = Network(gm.cfg, self.batch_size)
        net2.dtype_plan = gm.dtype_plan or None
        out = (net2, make_param_fn(gm), gm)
        self._infer_graph_cache[key] = out
        return out

    def _fill_quant_scales(self, gm) -> None:
        """Freeze each QuantSite's per-channel weight scale from the
        TRANSFORMED float weights (nnet/passes.py QuantSite): evaluate
        the float view of the staged param transforms once (eager -
        a few weight-sized ops) and absmax per output channel on the
        host, so a folded or merged weight is scaled at its COMPOSED
        values. The scale is the frozen constant make_param_fn's in-jit
        quantize stage divides by; the int8 values themselves stay live
        functions of the params argument."""
        sites = [s for s in gm.quants if s.wscale is None]
        if not sites:
            return
        from cxxnet_tpu.nnet.passes import make_param_fn
        from cxxnet_tpu.ops.int8 import per_channel_scale
        fl = make_param_fn(gm, quantize=False)(self.state["params"])
        by_live = {live: new for new, live in gm.param_map().items()}
        for site in sites:
            entry = fl.get(by_live.get(site.key))
            if entry is None or "wmat" not in entry:
                continue  # pruned between matching and build: float
            # fetch_local, not device_get: params may be sharded
            # across processes (zero_stage=3 / tensor parallelism),
            # like every other host read-back in this file
            site.wscale = per_channel_scale(np.asarray(
                distributed.fetch_local(entry["wmat"]), np.float32))

    def _needs_fold_stats(self) -> bool:
        return (self._fold_stats is None
                and bool(getattr(self, "_fold_sites", ())))

    def _needs_quant_stats(self) -> bool:
        return (self._quant_stats is None
                and bool(getattr(self, "_quant_sites", ())))

    def passes_need_calibration(self) -> bool:
        """True when a calibrating pass (fold_conv_bn's frozen moments,
        quantize_int8's activation ranges) is configured with at least
        one matched site whose statistics are missing - the
        predict/extract paths then calibrate on their first batch;
        serving without calibration runs the un-rewritten graph (the
        Server warns - docs/GRAPH_PASSES.md)."""
        if self._pipeline is None:
            return False
        return self._needs_fold_stats() or self._needs_quant_stats()

    def calibrate_graph_passes(self, batch) -> bool:
        """Capture the fold_conv_bn statistics from one calibration
        DataBatch (staged through the exact inference pipeline, so on
        a single-shard mesh a later inference of the SAME batch
        reproduces the unfolded values to contraction-order ULP; on a
        mesh whose data axis is > 1 the unfolded BN normalizes
        per shard while calibration captures GLOBAL stats - see
        _calibrate_staged). A SEQUENCE of batches instead averages
        the frozen moments over all of them (multi-batch
        calibration, `pass_calibration_batches` - less sensitive to
        one unlucky batch; the single-batch path stays
        bitwise-unchanged). Returns True when stats were
        (re)captured, False when nothing needed calibration."""
        if isinstance(batch, (list, tuple)):
            if len(batch) == 1:
                # one-element sequence rides the pinned single-batch
                # arithmetic (bitwise default)
                return self.calibrate_graph_passes(batch[0])
            return self._calibrate_batches(list(batch))
        if not self.passes_need_calibration():
            return False
        data, _, mask, extras = self._pad_batch(batch)
        gdata = self._put_data(data)
        shd = self._batch_sharded
        gextras = tuple(distributed.put_global(e, shd)
                        for e in extras)
        return self._calibrate_staged(
            gdata, gextras,
            distributed.put_global(np.asarray(mask, np.float32), shd))

    def _calibrate_batches(self, batches: List) -> bool:
        """Multi-batch fold calibration: ONE jitted moments forward
        (mean, var per fold site - the same tap + f32 arithmetic as
        _calibrate_staged) run per calibration batch, the per-batch
        moments pooled on the host (valid-row-weighted mean of means;
        var from the pooled second moment), rstd = 1/sqrt(var + eps)
        precomputed so the folded jaxpr still carries no rsqrt.
        Padding rows (a round_batch=0 iterator zero-fills its tail
        batch) are masked out of both the per-batch moments and the
        pooling weights."""
        if not batches:
            raise ValueError("calibration needs at least one batch")
        if not self.passes_need_calibration():
            return False
        from cxxnet_tpu.parallel.mesh import active_mesh
        sites = self._fold_sites if self._needs_fold_stats() else []
        qsites = (self._quant_sites if self._needs_quant_stats()
                  else [])
        if sites and self.mesh.shape.get("data", 1) > 1:
            # same documented caveat as _calibrate_staged: global
            # frozen stats vs the unfolded BN's per-shard stats
            telemetry.stderr(
                "graph_passes: fold_conv_bn calibrating GLOBAL batch "
                "statistics on a data-sharded mesh; the unfolded BN "
                "uses per-shard stats, so folded outputs are not "
                "ULP-comparable to unfolded ones here "
                "(docs/GRAPH_PASSES.md)\n",
                event_kind="graph_passes", op="calibrate_sharded",
                data_axis=self.mesh.shape.get("data", 1))
        net = self.net
        daug = self._augment_fn
        eps_by_key = {param_key(self.net_cfg, j):
                      net.layer_objs[j].eps for _i, j in sites}

        def moments_fn(params, data, extras, mask):
            cparams = self._cast(params)
            if daug is not None:
                data = daug(data, jax.random.PRNGKey(0), False)
            inputs = {0: self._cast(data)}
            for i, e in enumerate(extras):
                inputs[1 + i] = self._cast(e)
            taps: Dict[int, Any] = {j: None for _i, j in sites}
            taps.update({q: None for q in qsites})
            with active_mesh(self.mesh):
                net.forward(cparams, inputs, train=False, taps=taps)
            out = {}
            for _i, j in sites:
                lay = net.layer_objs[j]
                xf = taps[j].astype(jnp.float32)
                axes, _slices = lay._axes(taps[j].shape)
                # moments over REAL rows only: a round_batch=0
                # iterator zero-pads its tail batch, and all-zero
                # rows would drag the pooled frozen stats toward 0
                # (the pinned single-batch path keeps them - there
                # the calibration batch IS the inference batch)
                m = jnp.broadcast_to(
                    mask.astype(jnp.float32).reshape(
                        (-1,) + (1,) * (xf.ndim - 1)), xf.shape)
                denom = jnp.sum(m, axis=axes, keepdims=True)
                mean = jnp.sum(xf * m, axis=axes,
                               keepdims=True) / denom
                var = jnp.sum(m * (xf - mean) ** 2, axis=axes,
                              keepdims=True) / denom
                out[param_key(self.net_cfg, j)] = (mean.reshape(-1),
                                                   var.reshape(-1))
            qout = {param_key(self.net_cfg, q):
                    _masked_absmax(taps[q], mask) for q in qsites}
            return out, qout

        jfn = jax.jit(
            moments_fn,
            in_shardings=(self._params_store_shard,
                          self._data_sharded,
                          (self._batch_sharded,)
                          * self.net_cfg.extra_data_num,
                          self._batch_sharded),
            out_shardings=self._replicated)
        per_batch: List[Dict[str, Any]] = []
        q_batch: List[Dict[str, float]] = []
        weights: List[float] = []
        for b in batches:
            data, _, mask, extras = self._pad_batch(b)
            gdata = self._put_data(data)
            shd = self._batch_sharded
            gextras = tuple(distributed.put_global(e, shd)
                            for e in extras)
            gmask = distributed.put_global(
                np.asarray(mask, np.float32), shd)
            res, qres = jfn(self.state["params"], gdata, gextras,
                            gmask)
            per_batch.append({
                k: (np.asarray(distributed.fetch_local(m)),
                    np.asarray(distributed.fetch_local(v)))
                for k, (m, v) in res.items()})
            q_batch.append({
                k: float(np.asarray(distributed.fetch_local(v)))
                for k, v in qres.items()})
            weights.append(float(np.asarray(mask).sum()))
        w = np.asarray(weights, np.float64)
        w = w / w.sum()
        stats: Dict[str, Any] = {}
        for key in (per_batch[0] if per_batch else {}):
            means = np.stack([pb[key][0] for pb in per_batch])
            variances = np.stack([pb[key][1] for pb in per_batch])
            # pooled moments over the union of REAL rows: each batch
            # weighted by its valid-row count, var from the pooled
            # second moment E[x^2] - E[x]^2 with E[x^2]_i = var_i
            # + mean_i^2
            mean = (means * w[:, None]).sum(axis=0)
            var = ((variances + means ** 2)
                   * w[:, None]).sum(axis=0) - mean ** 2
            rstd = 1.0 / np.sqrt(np.maximum(var, 0.0)
                                 + eps_by_key[key])
            stats[key] = (mean.astype(np.float32),
                          rstd.astype(np.float32))
        if sites:
            self._fold_stats = stats
        if qsites:
            # ranges pool by MAX across batches - an absmax is an
            # absmax over the union of rows, no weighting involved
            self._quant_stats = {
                k: max(qb[k] for qb in q_batch) for k in q_batch[0]}
        self._fold_epoch += 1
        self._evict_stale_infer_caches()
        telemetry.event("graph_passes", op="calibrate",
                        sites=sorted(stats),
                        quant_sites=sorted(self._quant_stats or {}),
                        batches=len(batches))
        return True

    def _calibrate_staged(self, gdata, gextras, gmask) -> bool:
        """Fold calibration on already-staged device rows: ONE jitted
        forward over the UNFOLDED graph computing each fold site's BN
        input moments with BatchNormLayer._normalize's arithmetic
        (f32 stats, same axes, rsqrt(var + eps)) - the frozen
        (mean, rstd) the folded weights are built from. One-time
        executable; steady-state inference never recompiles it.

        `gmask` (staged valid-row mask) guards ONLY the quant absmax:
        a round_batch=0 iterator zero-fills its tail batch, and the
        padding rows' garbage activations at depth must not widen the
        frozen activation range (the `_calibrate_batches` arithmetic).
        The fold moments deliberately stay UNmasked here - on the
        pinned single-batch path the calibration batch IS the
        inference batch, padding included, and the unfolded BN
        normalizes over all of it.

        Sharding caveat (docs/GRAPH_PASSES.md "when folding loses"):
        the stats here are GLOBAL over the calibration batch, while
        the unfolded BN on a mesh with data-axis size > 1 normalizes
        each shard with its OWN stats - so the ULP-level fold parity
        holds on single-shard meshes only; on a sharded data mesh
        folding deliberately replaces per-shard batch statistics
        with the frozen global ones (warned below - for serving that
        is the batch-composition-independence feature, for accuracy
        work it is a semantics change to opt into knowingly)."""
        if not self.passes_need_calibration():
            return False
        from cxxnet_tpu.parallel.mesh import active_mesh
        sites = self._fold_sites if self._needs_fold_stats() else []
        qsites = (self._quant_sites if self._needs_quant_stats()
                  else [])
        net = self.net
        daug = self._augment_fn
        if sites and self.mesh.shape.get("data", 1) > 1:
            telemetry.stderr(
                "graph_passes: fold_conv_bn calibrating GLOBAL batch "
                "statistics on a data-sharded mesh; the unfolded BN "
                "uses per-shard stats, so folded outputs are not "
                "ULP-comparable to unfolded ones here "
                "(docs/GRAPH_PASSES.md)\n",
                event_kind="graph_passes", op="calibrate_sharded",
                data_axis=self.mesh.shape.get("data", 1))

        def stats_fn(params, data, extras, mask):
            cparams = self._cast(params)
            if daug is not None:
                data = daug(data, jax.random.PRNGKey(0), False)
            inputs = {0: self._cast(data)}
            for i, e in enumerate(extras):
                inputs[1 + i] = self._cast(e)
            # tap each fold site's BN INPUT as the layer receives it:
            # a `layer[+0] = batch_norm` self-loop overwrites its
            # node, so reading values[node] after the forward would
            # capture POST-normalization moments (~(beta, 1/slope))
            # and fold silently wrong weights. Quant sites tap the
            # same way: each eligible conv/fullc's INPUT activation,
            # whose absmax becomes the frozen per-tensor act scale.
            taps: Dict[int, Any] = {j: None for _i, j in sites}
            taps.update({q: None for q in qsites})
            with active_mesh(self.mesh):
                net.forward(cparams, inputs, train=False, taps=taps)
            out = {}
            for _i, j in sites:
                lay = net.layer_objs[j]
                x = taps[j]
                xf = x.astype(jnp.float32)
                axes, _slices = lay._axes(x.shape)
                mean = jnp.mean(xf, axis=axes, keepdims=True)
                var = jnp.mean((xf - mean) ** 2, axis=axes,
                               keepdims=True)
                rstd = lax.rsqrt(var + lay.eps)
                out[param_key(self.net_cfg, j)] = (mean.reshape(-1),
                                                   rstd.reshape(-1))
            qout = {param_key(self.net_cfg, q):
                    _masked_absmax(taps[q], mask) for q in qsites}
            return out, qout

        jfn = jax.jit(
            stats_fn,
            in_shardings=(self._params_store_shard,
                          self._data_sharded,
                          (self._batch_sharded,)
                          * self.net_cfg.extra_data_num,
                          self._batch_sharded),
            out_shardings=self._replicated)
        res, qres = jfn(self.state["params"], gdata, gextras, gmask)
        if sites:
            self._fold_stats = {
                k: (np.asarray(distributed.fetch_local(m)),
                    np.asarray(distributed.fetch_local(r)))
                for k, (m, r) in res.items()}
        if qsites:
            self._quant_stats = {
                k: float(np.asarray(distributed.fetch_local(v)))
                for k, v in qres.items()}
        self._fold_epoch += 1
        self._evict_stale_infer_caches()
        telemetry.event("graph_passes", op="calibrate",
                        sites=sorted(self._fold_stats or {}),
                        quant_sites=sorted(self._quant_stats or {}))
        return True

    def _evict_stale_infer_caches(self) -> None:
        """Drop transformed graphs + compiled executables of every
        fold epoch but the current one: nothing re-reads them through
        _infer_fn (a running Server pinned its own fn reference and
        keeps it) - without eviction a copy_model_from/predict reload
        loop would leak one compiled executable + Network clone per
        recalibration, and a stale-stats executable could be
        re-dispatched after a params reload."""
        epoch = self._fold_epoch
        self._infer_graph_cache = {
            k: v for k, v in self._infer_graph_cache.items()
            if k[1] == epoch}
        jits = getattr(self, "_infer_jits", None)
        if jits is not None:
            for k in [k for k in jits
                      if isinstance(k, tuple) and k[1] != epoch]:
                del jits[k]

    # graftlint: hot-path
    def evaluate(self, data_iter, data_name: str) -> str:
        """Run eval metrics over an iterator; returns the reference-format
        string `\\tname-metric:value...` (nnet_impl-inl.hpp:224-245).

        Metrics accumulate on device (one readback per dataset); the
        host MetricSet path remains for metric-less trainers."""
        from cxxnet_tpu.utils import metric_jit
        specs = self.metric.specs
        if self._eval_metric_step is not None:
            shd = self._batch_sharded
            per_batch = []  # tiny (n_metrics, 2) device arrays
            data_iter.before_first()
            step = 0
            while data_iter.next():
                batch = data_iter.value()
                data, label, mask, extras = self._pad_batch(batch)
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(self.seed + 200), step)
                step += 1
                labels = self._label_fields(label.astype(np.float32))
                gdata = self._put_data(data)
                with TraceAnnotation(spans.EVAL_STEP), self._flight_record(
                        "eval_metric",
                        ("eval_metric", tuple(gdata.shape)),
                        kind="eval",
                        name=f"eval_metric@b{gdata.shape[0]}",
                        shape=gdata.shape, nbytes=gdata.nbytes):
                    per_batch.append(self._eval_metric_step(
                        self.state["params"],
                        gdata,
                        tuple(distributed.put_global(e, shd)
                              for e in extras),
                        {k: distributed.put_global(v, shd)
                         for k, v in labels.items()},
                        distributed.put_global(
                            mask.astype(np.float32), shd),
                        rng))
                # eval progress beacon: round-boundary evals can
                # dwarf watchdog_secs without being a hang
                telemetry.beacon("eval.step")
                if self.eval_inflight and step % self.eval_inflight == 0:
                    # bound in-flight work: without a periodic sync the
                    # host loop stages the whole dataset's input
                    # buffers ahead of the device (HBM blow-up on large
                    # eval sets); syncing on the tiny metric rows keeps
                    # <= eval_inflight batches of inputs pinned. The
                    # knob trades HBM headroom for sync stalls
                    # (docs/PERFORMANCE.md); 0 = never sync
                    # graftlint: disable=GL002 eval_inflight HBM bound: sync every N batches by design
                    jax.block_until_ready(per_batch[-1])
            # host-side float64 reduction across batches (the host
            # MetricSet path accumulated in f64; per-batch f32 sums are
            # exact at batch scale, the cross-batch sum is not)
            vals = np.zeros((len(specs), 2), np.float64)
            for r in per_batch:
                # graftlint: disable=GL002 one tiny-row readback per eval batch, after the dataset dispatched
                vals += np.asarray(distributed.fetch_local(r),
                                   np.float64)
            return metric_jit.format_metrics(data_name, specs, vals)
        self.metric.clear()
        data_iter.before_first()
        while data_iter.next():
            batch = data_iter.value()
            with TraceAnnotation(spans.EVAL_STEP):
                nodes = self._forward_nodes(batch)
            nvalid = batch.batch_size - batch.num_batch_padd
            labels = self._label_fields(
                batch.label.astype(np.float32)[:nvalid])
            preds = []
            for _, nid in self.eval_nodes:
                p = nodes[nid][:nvalid]
                preds.append(p.reshape(p.shape[0], -1))
            self.metric.add_eval(preds, labels)
            telemetry.beacon("eval.step")
        return self.metric.print(data_name)

    def eval_train_metric(self) -> str:
        from cxxnet_tpu.utils import metric_jit
        specs = self.train_metric.specs
        if specs and self.state is not None:
            acc = distributed.fetch_local(self.state["tmetric"])
            # resolve the Kahan pair: true sum ~= sum - comp
            vals = np.stack([acc[:, 0] - acc[:, 1], acc[:, 2]], axis=1)
            out = metric_jit.format_metrics("train", specs, vals)
            self.clear_train_metric()
            return out
        out = self.train_metric.print("train")
        self.train_metric.clear()
        return out

    def clear_train_metric(self) -> None:
        """Zero the on-device train-metric accumulator."""
        self.train_metric.clear()
        if self.state is not None and "tmetric" in self.state:
            n = len(self.train_metric)
            self.state["tmetric"] = distributed.put_global(
                np.zeros((n, 3), np.float32), self._replicated)

    def predict(self, batch: DataBatch) -> np.ndarray:
        """Prediction = argmax of the final node (or raw scalar);
        nnet_impl-inl.hpp:186-199 TransformPred. Runs the dedicated
        inference executable (single-node readback, docs/SERVING.md)."""
        out = self._infer_node(batch, self.net_cfg.num_nodes - 1)
        if out.ndim == 4 and out.shape[1] == 1 and out.shape[2] > 1:
            # a sequence node on top (`lm_head`'s logits): the token
            # the last position predicts
            out = out[:, 0, -1]
        flat = out.reshape(out.shape[0], -1)
        if flat.shape[1] == 1:
            return flat[:, 0]
        return np.argmax(flat, axis=1).astype(np.float32)

    def predict_dist(self, batch: DataBatch) -> np.ndarray:
        """Full output distribution of the final node."""
        out = self._infer_node(batch, self.net_cfg.num_nodes - 1)
        return out.reshape(out.shape[0], -1)

    def extract_feature(self, batch: DataBatch,
                        node_name: str) -> np.ndarray:
        """Copy out any node by name or `top[-k]`
        (nnet_impl-inl.hpp:200-223)."""
        nid = self.net.node_index(node_name)
        return self._infer_node(batch, nid)

    # ------------------------------------------------------------------
    # checkpoint api
    # ------------------------------------------------------------------
    def _full_params(self):
        """Host params at FULL (stage-0) shapes: zero_stage=3 stores
        shards between steps, so gather first (one all-gather per
        weight) - checkpoints stay byte-compatible with stage 0 and
        resume works across differing zero_stage."""
        params = self.state["params"]
        if getattr(self, "_zero_run", 0) == 3:
            params = jax.jit(lambda t: t,
                             out_shardings=self._pshard)(params)
        return jax.tree.map(distributed.fetch_local, params)

    def save_model(self, fo) -> None:
        params = self._full_params()
        if self.model_format == "cxxnet":
            # reference-binary export (nnet/legacy_format.py)
            from cxxnet_tpu.nnet import legacy_format
            legacy_format.save_legacy_model(fo, self.net_cfg, self.net,
                                            params, self.epoch)
            return
        opt = None
        if self.save_optimizer:
            opt = self.state["ustate"]
            if getattr(self, "_zero_run", 0) >= 1:
                # re-replicate ZeRO-sharded state (one all-gather) so the
                # host readback sees full tensors on every process
                opt = jax.jit(lambda t: t,
                              out_shardings=self._replicated)(opt)
            opt = jax.tree.map(distributed.fetch_local, opt)
        checkpoint.save_model(fo, 0, self.net_cfg.to_dict(), self.epoch,
                              params, opt)

    def load_model(self, fi) -> None:
        # sniff the format: native files start with the CXTPU magic,
        # reference-binary files with a little int32 net_type
        head = fi.read(len(checkpoint.MAGIC))
        fi.seek(-len(head), 1)
        if head != checkpoint.MAGIC:
            self._load_legacy(fi)
            return
        blob = checkpoint.load_model(fi)
        self.net_cfg = NetConfig.from_dict(blob["net"])
        self.net_cfg.configure(self.cfg_pairs)
        self.epoch = blob["epoch"]
        self._epoch_base = self.epoch
        self._step_counter = 0
        self._skipped_steps = 0
        self._bad_consec = 0
        self._loaded_opt = blob["opt_state"]
        self._build_net()
        params = jax.tree.map(jnp.asarray, blob["params"])
        self._init_state(params)
        self.state["epoch"] = distributed.put_global(
            np.asarray(self.epoch, np.int32), self._replicated)

    def _load_legacy(self, fi) -> None:
        """Load a reference-binary model. Like the reference, the
        netconfig must come from the config file; the file supplies
        structure (validated for equality) + weights."""
        from cxxnet_tpu.nnet import legacy_format
        self.net_cfg = NetConfig()
        self.net_cfg.configure(self.cfg_pairs)
        self._build_net()
        # shapes only - no throwaway device init
        expected = jax.eval_shape(self.net.init_params,
                                  jax.random.PRNGKey(self.seed))
        blob = legacy_format.load_legacy_model(fi, self.net_cfg,
                                               self.net, expected)
        self.epoch = blob["epoch"]
        self._epoch_base = self.epoch
        self._step_counter = 0
        self._skipped_steps = 0
        self._bad_consec = 0
        params = jax.tree.map(jnp.asarray, blob["params"])
        self._init_state(params)
        self.state["epoch"] = distributed.put_global(
            np.asarray(self.epoch, np.int32), self._replicated)

    def copy_model_from(self, fi) -> None:
        """Finetune: copy params of layers whose names match
        (nnet_impl-inl.hpp:101-134). Must be called after init_model."""
        if self.state is None:
            raise RuntimeError("copy_model_from requires init_model first")
        head = fi.read(len(checkpoint.MAGIC))
        fi.seek(-len(head), 1)
        if head == checkpoint.MAGIC:
            blob = checkpoint.load_model(fi)
        else:
            from cxxnet_tpu.nnet import legacy_format
            blob = legacy_format.read_legacy_model(fi)
        params = self._full_params()
        copied = []
        for lk, d in blob["params"].items():
            if lk.startswith("layer_"):
                continue  # unnamed layers are not matched
            if lk in params:
                for pn, arr in d.items():
                    if pn not in params[lk]:
                        continue
                    want = params[lk][pn].shape
                    if arr.shape != want and arr.size == params[
                            lk][pn].size:
                        # legacy conv wmat arrives in the file's 3D
                        # layout - same memory order as our OIHW
                        arr = arr.reshape(want)
                    if arr.shape == want:
                        params[lk][pn] = arr
                copied.append(lk)
        if not self.silent:
            telemetry.stdout(f"finetune: copied layers {copied}")
        self._init_state(jax.tree.map(jnp.asarray, params))

    # ------------------------------------------------------------------
    # weight access api (visitor semantics)
    # ------------------------------------------------------------------
    def get_weight(self, layer_name: str,
                   tag: str) -> Tuple[np.ndarray, Tuple[int, ...]]:
        """Returns (2-D flattened weight, original shape); GetWeightVisitor
        flattening = (shape[0], prod(rest)) (visitor.h:26-100)."""
        lk = self._weight_key(layer_name, tag)
        leaf = self.state["params"][lk[0]][lk[1]]
        if getattr(self, "_zero_run", 0) == 3:
            # gather this weight's zero shards (visitors see full 2-D)
            leaf = jax.jit(
                lambda t: t,
                out_shardings=self._pshard[lk[0]][lk[1]])(leaf)
        arr = distributed.fetch_local(leaf)
        return arr.reshape(arr.shape[0], -1), arr.shape

    def set_weight(self, weight: np.ndarray, layer_name: str,
                   tag: str) -> None:
        lk = self._weight_key(layer_name, tag)
        cur = self.state["params"][lk[0]][lk[1]]
        arr = np.asarray(weight, dtype=np.float32).reshape(cur.shape)
        params = self.state["params"]
        # full global host value -> put_global_full (put_global would
        # misread it as a pre-cut local shard when the param is sharded
        # across processes, e.g. tensor parallelism over hosts); lands
        # on the between-steps layout (the zero cut under zero_stage=3)
        params[lk[0]][lk[1]] = distributed.put_global_full(
            arr, self._params_store_shard[lk[0]][lk[1]])
        self.state["params"] = params
        self._retire_calibration_state()

    def check_weights(self) -> List[str]:
        """test_on_server analog (async_updater-inl.hpp:144-153): verify
        replicated params are identical on every device/process."""
        return distributed.check_replicated(self.state["params"])

    def _weight_key(self, layer_name: str, tag: str) -> Tuple[str, str]:
        idx = self.net_cfg.get_layer_index(layer_name)
        tags = self.net.layer_objs[idx].param_tags()
        for pname, t in tags.items():
            if t == tag or pname == tag:
                return param_key(self.net_cfg, idx), pname
        raise KeyError(f"layer {layer_name} has no weight tagged {tag}")


def create_net(net_type: int = 0, dev: str = "", cfg: str = "") -> NetTrainer:
    """CreateNet factory parity (nnet.h:99-100; net_type is ignored by the
    reference too - nnet_impl-inl.hpp:457-460)."""
    return NetTrainer(dev, cfg)
