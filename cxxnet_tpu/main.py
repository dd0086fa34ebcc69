"""CLI task driver.

Behavior parity with CXXNetLearnTask (src/cxxnet_main.cpp:16-478):

    python -m cxxnet_tpu.main <config.conf> [k=v ...]

- tasks: train (default) / finetune / pred / pred_raw / extract /
  serve (pred_raw: raw top-node rows - the reference accepts the task
  name but never dispatches it, cxxnet_main.cpp:77-79 vs :242;
  serve: the pred iterator replayed as a ragged request stream
  through the continuous-batching server, docs/SERVING.md)
- `continue = 1` resumes from the newest `model_dir/%04d.model`
- per-round checkpoints gated by `save_model` period
- eval metrics printed per round to stderr as
  `[round]\\ttrain-metric:x\\tevalname-metric:y`
- `test_io = 1` drives the full data pipeline with Update skipped
- `pred = file` + task=pred writes one prediction per line;
  task=extract with `extract_node_name` dumps features (+ .meta)
"""

from __future__ import annotations

import os
import struct
import sys
import time
from typing import List, Optional, Tuple

from cxxnet_tpu import telemetry
from cxxnet_tpu.io import create_iterator
from cxxnet_tpu.nnet.trainer import NetTrainer, StagedChunk
from cxxnet_tpu.utils.config import parse_config_file
from cxxnet_tpu.utils.fault import DivergenceError, atomic_writer


def _eval_values(text: str) -> dict:
    """Parse a reference-format eval string ('\\tname-metric:value'
    repeated) into {name-metric: float} for structured eval events.
    Unparseable tokens are skipped - the event is best-effort, the
    stderr text is the ground truth."""
    out = {}
    for tok in text.split("\t"):
        tok = tok.strip()
        if not tok or ":" not in tok:
            continue
        key, _, val = tok.rpartition(":")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


class LearnTask:
    def __init__(self) -> None:
        self.task = "train"
        self.net_type = 0
        self.net_trainer: Optional[NetTrainer] = None
        self.itr_train = None
        self.itr_pred = None
        self.itr_evals = []
        self.eval_names: List[str] = []
        self.name_model_dir = "models"
        self.num_round = 10
        self.test_io = 0
        # depth of the H2D staging prefetch for the train loop
        # (io/prefetch.py); 0 streams batches on the update thread
        self.prefetch_stage = 1
        # fused multi-step dispatch: K staged batches scan through ONE
        # jitted executable per dispatch (docs/PERFORMANCE.md); 1 =
        # per-step dispatch, byte-for-byte today's behavior
        self.steps_per_dispatch = 1
        self.batch_size = 0
        self.silent = 0
        self.start_counter = 0
        self.max_round = 1 << 31
        self.continue_training = 0
        self.save_period = 1
        # checkpoint rotation: keep the newest k %04d.model files
        # (0 = keep everything, the reference behavior)
        self.keep_latest = 0
        # serving publish hook (docs/SERVING.md "Hot-swap runbook"):
        # after every saved round, atomically copy the checkpoint to
        # this path - the file a live Server's swap_watch= poller
        # picks up for a zero-downtime weight swap ("" = off)
        self.name_publish = ""
        self.name_model_in = "NULL"
        self.name_pred = "pred.txt"
        self.print_step = 100
        self.extract_node_name = ""
        self.output_format = 1
        # telemetry sinks (docs/OBSERVABILITY.md): empty = disabled,
        # and the CLI's stdout/stderr stay byte-identical to the
        # pre-telemetry behavior
        self.log_file = ""
        self.metrics_file = ""
        self.log_format = "json"
        self.heartbeat_secs = 0.0
        # live observability plane (docs/OBSERVABILITY.md): /metrics +
        # /healthz + /varz HTTP exposition, declarative alert rules,
        # hang watchdog. All off by default - unarmed runs never
        # import the plane, keeping CLI output byte-identical
        self.metrics_port = 0
        self.metrics_host = ""
        self.alert_rules = ""
        self.alert_cmd = ""
        self.watchdog_secs = 0.0
        # dispatch flight recorder (docs/OBSERVABILITY.md "Flight
        # recorder"): armed automatically with any sink / metrics_port
        # / watchdog_secs / alert_rules; flight_recorder = 1 arms the
        # in-memory ring alone (forensics without any other plane).
        # 0 (the default) adds nothing - byte-parity preserved
        self.flight_recorder = 0
        self.device = "tpu"
        self.eval_train = 1
        self.test_on_server = 0
        # elastic pod training (docs/FAULT_TOLERANCE.md "Elastic
        # pod"): elastic=1 arms the coordinated-checkpoint barrier at
        # every round boundary - the pod elects a leader over the
        # coord_dir control plane (default <model_dir>/coord), ONLY
        # the leader publishes the round's checkpoint, and an absent
        # member is convicted so the supervisor
        # (parallel/elastic.py) can roll back + reshape
        self.elastic = 0
        self.barrier_secs = 30.0
        self.leader_lease_secs = 10.0
        self.coord_dir = ""
        self._coordinator = None
        # config schema gate (docs/STATIC_ANALYSIS.md): unknown keys
        # error with a did-you-mean suggestion instead of silently
        # configuring nothing; schema_check = 0 bypasses
        self.schema_check = 1
        # TVM-style per-platform tuning cache (nnet/tuning.py,
        # tools/autotune.py, docs/GRAPH_PASSES.md): tuned values are
        # DEFAULTS for the task-level knobs below (prefetch_stage,
        # steps_per_dispatch) and the trainer's own tunables -
        # explicitly-set config keys always win
        self.tuning_cache = ""
        # task=serve load shape (docs/SERVING.md): rows per submitted
        # request when replaying the pred iterator through the server
        # (0 = a deterministic ragged size cycle, the bucket-coverage
        # mode the serve-smoke CI job uses)
        self.serve_rows = 1
        # explicit fold-calibration source (docs/GRAPH_PASSES.md
        # multi-batch calibration): which iterator feeds
        # `pass_calibration_batches` batches - "pred" (default),
        # "train", or an eval block's name. With N = 1 and no
        # iterator named, the lazy first-inference-batch path keeps
        # its pinned single-batch behavior
        self.pass_calibration_iter = ""
        self.pass_calibration_batches = 1
        self.cfg: List[Tuple[str, str]] = []
        # index of the first command-line override pair in self.cfg
        # (None = everything is file-like); _split_blocks uses it to
        # keep CLI pairs out of iterator-block scanning
        self._n_file_pairs: Optional[int] = None

    # ------------------------------------------------------------------
    def run(self, argv: List[str]) -> int:
        if len(argv) < 1:
            telemetry.stdout("Usage: <config> [k=v ...]")
            return 0
        for name, val in parse_config_file(argv[0]):
            self.set_param(name, val)
        n_file_pairs = self._n_file_pairs = len(self.cfg)
        for arg in argv[1:]:
            if "=" in arg:
                name, val = arg.split("=", 1)
                self.set_param(name.strip(), val.strip())
        if self.schema_check:
            # fail BEFORE any backend/iterator is touched: a typo'd
            # key must cost a ConfigError with a suggestion, not a
            # silently-default run (valid configs print nothing, so
            # the CLI byte-parity contract is untouched). File pairs
            # and argv overrides are labeled separately - "in
            # my.conf" for a typo that was actually on the command
            # line sends the user grepping the wrong place
            from cxxnet_tpu.utils.config import validate_known_keys
            validate_known_keys(self.cfg[:n_file_pairs],
                                source=argv[0])
            validate_known_keys(self.cfg[n_file_pairs:],
                                source="command-line override")
        if self.device.split(":")[0] == "cpu":
            # honor `dev = cpu` before any backend is touched: the
            # process runs on the host even where a chip is attached.
            # An accelerator kind is checked when the mesh is built
            # (parallel/mesh.py resolve_devices): `dev = tpu` without a
            # TPU raises there instead of training on the host
            import jax
            jax.config.update("jax_platforms", "cpu")
        # before the first compile: $JAX_COMPILATION_CACHE_DIR if set,
        # else <checkout>/.jax_cache (utils/platform.py)
        from cxxnet_tpu.utils.platform import setup_compile_cache
        setup_compile_cache()
        # arm telemetry before init() so resume walk-backs and model
        # loads are already on the record; with no sink keys set this
        # returns the process to the disabled (byte-parity) state
        telemetry.configure(
            log_file=self.log_file, metrics_file=self.metrics_file,
            log_format=self.log_format,
            heartbeat_secs=self.heartbeat_secs,
            tags={"device": self.device})
        # live observability plane (docs/OBSERVABILITY.md): watchdog,
        # alert rules, /metrics-/healthz-/varz HTTP exposition. With
        # all four keys unset this is a no-op that imports nothing;
        # metrics_port=0 means OFF on the CLI (an ephemeral bind is a
        # programmatic-only mode - an operator could never find it)
        telemetry.arm_observability(
            metrics_port=(self.metrics_port if self.metrics_port > 0
                          else None),
            metrics_host=self.metrics_host,
            alert_rules=self.alert_rules, alert_cmd=self.alert_cmd,
            watchdog_secs=self.watchdog_secs)
        if self.flight_recorder:
            # in-memory dispatch ring alone (no sink, no thread, no
            # socket): the cheapest forensics mode - a later watchdog
            # or /varz consumer reads what already accumulated
            telemetry.get().flight.arm()
        if self.tuning_cache:
            # AFTER the telemetry sinks armed (the apply_task event
            # must reach the stream), BEFORE init() builds anything
            # from the knobs; the trainer applies its own tunables
            # from the same cache (the `tuning_cache` pair reaches it
            # with the rest of the config) under the same
            # explicit-keys-win rule - so the two consumers can never
            # disagree on a shared knob like steps_per_dispatch
            self._apply_tuning_cache()
        telemetry.event("run_start", task=self.task, conf=argv[0],
                        num_round=self.num_round)
        t_run = time.monotonic()
        try:
            self.init()
            if telemetry.enabled():
                # distributed init (if any) happened inside init():
                # refine the process tag so multi-host streams merge
                import jax
                telemetry.set_tags(proc=jax.process_index())
            if not self.silent:
                telemetry.stdout("initializing end, start working")
            if self.task in ("train", "finetune"):
                self.task_train()
            elif self.task == "pred":
                self.task_predict()
            elif self.task == "pred_raw":
                self.task_predict_raw()
            elif self.task == "extract":
                self.task_extract_feature()
            elif self.task == "serve":
                self.task_serve()
            else:
                raise ValueError(f"unknown task {self.task}")
            return 0
        finally:
            if self._coordinator is not None:
                self._coordinator.close()
            # final snapshot + clean close even on an aborting task, so
            # the stream explains the crash (heartbeat stops with it)
            telemetry.event("run_end", task=self.task,
                            secs=time.monotonic() - t_run)
            telemetry.emit_metrics(kind="final", task=self.task)
            telemetry.close()

    def set_param(self, name: str, val: str) -> None:
        if val == "default":
            return
        if name == "net_type":
            self.net_type = int(val)
        if name == "print_step":
            self.print_step = int(val)
        if name == "continue":
            self.continue_training = int(val)
        if name == "save_model":
            self.save_period = int(val)
        if name == "keep_latest":
            self.keep_latest = int(val)
        if name == "publish_model":
            self.name_publish = val
        if name == "start_counter":
            self.start_counter = int(val)
        if name == "model_in":
            self.name_model_in = val
        if name == "model_dir":
            self.name_model_dir = val
        if name == "num_round":
            self.num_round = int(val)
        if name == "max_round":
            self.max_round = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "task":
            self.task = val
        if name == "dev":
            self.device = val
        if name == "test_io":
            self.test_io = int(val)
        if name == "prefetch_stage":
            self.prefetch_stage = int(val)
        if name == "steps_per_dispatch":
            self.steps_per_dispatch = int(val)
        if name == "batch_size":
            self.batch_size = int(val)
        if name == "eval_train":
            self.eval_train = int(val)
        if name == "test_on_server":
            self.test_on_server = int(val)
        if name == "elastic":
            self.elastic = int(val)
        if name == "barrier_secs":
            self.barrier_secs = float(val)
        if name == "leader_lease_secs":
            self.leader_lease_secs = float(val)
        if name == "coord_dir":
            self.coord_dir = val
        if name == "extract_node_name":
            self.extract_node_name = val
        if name == "output_format":
            self.output_format = 1 if val == "txt" else 0
        if name == "log_file":
            self.log_file = val
        if name == "metrics_file":
            self.metrics_file = val
        if name == "log_format":
            self.log_format = val
        if name == "heartbeat_secs":
            self.heartbeat_secs = float(val)
        if name == "metrics_port":
            self.metrics_port = int(val)
        if name == "metrics_host":
            self.metrics_host = val
        if name == "alert_rules":
            self.alert_rules = val
        if name == "alert_cmd":
            self.alert_cmd = val
        if name == "watchdog_secs":
            self.watchdog_secs = float(val)
        if name == "flight_recorder":
            self.flight_recorder = int(val)
        if name == "schema_check":
            self.schema_check = int(val)
        if name == "serve_rows":
            self.serve_rows = int(val)
        if name == "tuning_cache":
            self.tuning_cache = val
        if name == "pass_calibration_iter":
            self.pass_calibration_iter = val
        if name == "pass_calibration_batches":
            if int(val) < 1:
                raise ValueError(
                    "pass_calibration_batches must be >= 1")
            self.pass_calibration_batches = int(val)
        self.cfg.append((name, val))

    def _apply_tuning_cache(self) -> None:
        """Apply tuned task-level knob defaults from `tuning_cache =`
        (nnet/tuning.py): only knobs no config pair set explicitly.
        A cache with no entry for this platform applies nothing."""
        from cxxnet_tpu.nnet import tuning
        knobs = tuning.tuned_knobs(self.tuning_cache)
        explicit = {k for k, _ in self.cfg}
        applied = {}
        # tuning.int_knob is THE shared apply rule (explicit keys
        # win, malformed values skip) - the trainer consumes the same
        # cache through the same helper
        v = tuning.int_knob(knobs, "prefetch_stage", explicit, 0)
        if v is not None:
            self.prefetch_stage = applied["prefetch_stage"] = v
        v = tuning.int_knob(knobs, "steps_per_dispatch", explicit, 1)
        if v is not None:
            self.steps_per_dispatch = applied["steps_per_dispatch"] = v
        if applied and not self.silent:
            telemetry.stdout(
                "tuning_cache: applied "
                + " ".join(f"{k}={v}"
                           for k, v in sorted(applied.items())))
        if applied:
            telemetry.event("tuning", op="apply_task",
                            cache=self.tuning_cache, **applied)

    # ------------------------------------------------------------------
    def _split_blocks(self):
        """Segment the flat conf into (defcfg, train, evals, pred):
        defcfg = keys outside any iterator block, train/pred = that
        block's keys, evals = [(eval_name, keys), ...]. The ONE
        scanner both _create_net and _create_iterators consume - the
        two previous hand-rolled copies had already drifted (pred
        folded into eval, train keys in/out of defcfg). Also records
        self.name_pred from the `pred =` line."""
        defcfg: List[Tuple[str, str]] = []
        train = None
        evals: List[Tuple[str, List[Tuple[str, str]]]] = []
        pred = None
        cur: Optional[List[Tuple[str, str]]] = None
        evname = ""
        flag = 0
        for idx, (name, val) in enumerate(self.cfg):
            cli = (self._n_file_pairs is not None
                   and idx >= self._n_file_pairs)
            if name == "data":
                if cli:
                    continue  # a CLI pair is never a block marker
                flag, cur = 1, []
                continue
            if name == "eval":
                if cli:
                    continue
                flag, cur, evname = 2, [], val
                continue
            if name == "pred":
                self.name_pred = val
                if cli:
                    # `pred=file.txt` on the command line renames the
                    # output; opening an (unterminated) pred iterator
                    # block here would silently swallow every override
                    # after it - serve_max_batch=8 after pred= used to
                    # configure nothing
                    continue
                flag, cur = 3, []
                continue
            if name == "iter" and val == "end":
                assert flag != 0, "wrong configuration file"
                if flag == 1:
                    assert train is None, "can only have one data"
                    train = cur
                elif flag == 2:
                    evals.append((evname, cur))
                else:
                    assert pred is None, "can only have one data:test"
                    pred = cur
                flag, cur = 0, None
                continue
            (defcfg if cur is None else cur).append((name, val))
        return defcfg, train, evals, pred

    @staticmethod
    def _daug_spec(pairs) -> dict:
        """Canonical device-augment normalization spec from conf pairs
        (last-writer-wins): divideby folds into scale exactly as the
        trainer's own alias does, and defaults are filled so an
        explicit `mirror = 0` compares equal to an absent one."""
        spec = {"scale": 1.0, "mirror": "0", "crop_y_start": "-1",
                "crop_x_start": "-1", "image_mean": "", "mean_value": "",
                "input_shape": "", "device_augment": "0"}
        for k, v in pairs:
            if k == "divideby":
                spec["scale"] = 1.0 / float(v)
            elif k == "scale":
                spec["scale"] = float(v)
            elif k == "mean_value":
                # parse so `0, 0, 0` == `0,0,0`, and all-zero == OFF
                # == absent (make_device_augment's own rule)
                vals = tuple(float(t) for t in v.split(","))
                spec[k] = "" if not any(vals) else \
                    ",".join(f"{t:g}" for t in vals)
            elif k in spec:
                spec[k] = v
        return spec

    def _create_net(self) -> NetTrainer:
        """Build the trainer from the global section + the train data
        block (every task - the historic spec source), plus the pred
        block layered last UNDER task=pred/extract only (so the
        feeding iterator's image_mean/scale reaches the
        device_augment eval spec). The pred block must NOT feed under
        task=train - iterator-scoped keys like a pred batch_size
        would silently clobber the train configuration - and eval
        blocks never feed (an eval block without rand_crop must not
        erase the train block's crop)."""
        defcfg, train, evals, pred = self._split_blocks()
        feed = defcfg + (train or [])
        if self.task in ("pred", "pred_raw", "extract", "serve"):
            feed = feed + (pred or [])
        net = NetTrainer()
        for k, v in feed:
            net.set_param(k, v)
        self._check_daug_blocks(net, feed, defcfg, train, evals, pred)
        return net

    def _check_daug_blocks(self, net, feed, defcfg, train, evals, pred):
        """device_augment bakes ONE normalization spec into the jitted
        step, but every iterator block feeds it raw pixels. A block
        whose effective spec diverges from the trainer's would be
        silently normalized with the WRONG spec - fail loudly instead.
        Only blocks the CURRENT task instantiates are checked (a conf
        shared between train and pred must not be rejected for a
        divergence in a block the task never uses). `feed` is exactly
        what _create_net fed the trainer, so eff IS the compiled
        spec."""
        active = []
        if self.task in ("pred", "pred_raw", "extract", "serve"):
            if pred is not None:
                active.append(("pred", pred))
        else:
            if train is not None:
                active.append(("data", train))
            active.extend((name or "eval", keys) for name, keys in evals)
        eff = self._daug_spec(feed)
        want = "1" if net.device_augment else "0"
        for tag, keys in active:
            bs = self._daug_spec(defcfg + keys)
            flag = "1" if int(bs["device_augment"] or "0") else "0"
            if flag != want:
                raise ValueError(
                    f"device_augment mismatch: the trainer compiled "
                    f"with device_augment={want} but iterator block "
                    f"'{tag}' has device_augment={flag} - raw pixels "
                    "and the in-step augment must agree. Set "
                    "device_augment globally, not per block.")
            if not net.device_augment:
                continue
            for k in ("scale", "mirror", "crop_y_start", "crop_x_start",
                      "image_mean", "mean_value", "input_shape"):
                if bs[k] != eff[k]:
                    raise ValueError(
                        f"device_augment: block '{tag}' has {k}="
                        f"{bs[k]!r} but the trainer's compiled spec "
                        f"has {k}={eff[k]!r}; the in-step augment is "
                        "compiled once - per-block normalization "
                        "divergence cannot be honored (use the host "
                        "pipeline, device_augment=0, for that)")

    def init(self) -> None:
        # param_server=dist: join the multi-controller job up front so
        # every later path (model load, iterators, mesh) sees the global
        # device view (idempotent; trainer.init_model also calls it)
        from cxxnet_tpu.parallel import distributed
        distributed.init_from_config(self.cfg)
        if self.elastic and self.task in ("train", "finetune"):
            self._start_coordinator()
        if self.task == "train" and self.continue_training:
            if self._sync_latest_model():
                telemetry.stdout(f"Init: Continue training from round "
                                 f"{self.start_counter}")
                telemetry.event("checkpoint", op="resume",
                                round=self.start_counter)
                self._create_iterators()
                return
            # reference aborts here (cxxnet_main.cpp:109-113)
            raise FileNotFoundError(
                "Init: cannot find models for continue training; "
                "specify model_in instead")
        if self.name_model_in == "NULL":
            assert self.task == "train", \
                "must specify model_in if not training"
            self.net_trainer = self._create_net()
            self.net_trainer.init_model()
        elif self.task == "finetune":
            self._copy_model()
        else:
            self._load_model()
        self._create_iterators()

    def _start_coordinator(self) -> None:
        """Arm the elastic coordinator (parallel/coordinator.py):
        membership comes from the supervisor's generation.json when
        present (the record names this pod generation's members; this
        worker's member id arrives in CXN_MEMBER_ID), and degrades to
        rank-as-member for a pod launched without a supervisor."""
        import jax
        from cxxnet_tpu.parallel import distributed
        from cxxnet_tpu.parallel.coordinator import (ControlPlane,
                                                     Coordinator)
        coord_dir = self.coord_dir or os.path.join(
            self.name_model_dir, "coord")
        os.makedirs(coord_dir, exist_ok=True)
        generation, members = 0, list(range(jax.process_count()))
        if os.path.exists(os.path.join(coord_dir, "generation.json")):
            rec = distributed.read_membership(coord_dir)
            generation = int(rec.get("generation", 0))
            members = [int(m) for m in rec["members"]]
        member_env = os.environ.get("CXN_MEMBER_ID")
        if member_env is not None:
            member = int(member_env)
        else:
            member = members[jax.process_index()]
        plane = ControlPlane(coord_dir)
        self._coordinator = Coordinator(
            plane, member, members, generation=generation,
            barrier_secs=self.barrier_secs,
            lease_secs=self.leader_lease_secs)
        self._coordinator.start()
        telemetry.event("coord", op="start", member=member,
                        generation=generation, members=members)

    def _model_name(self, counter: int) -> str:
        return os.path.join(self.name_model_dir, f"{counter:04d}.model")

    def _model_counters(self) -> List[int]:
        """Sorted %04d.model counters present in model_dir (the pattern
        accepts 5+ digits: %04d renders them past round 9999)."""
        import re
        try:
            names = os.listdir(self.name_model_dir)
        except OSError:
            return []
        return sorted(int(m.group(1)) for m in
                      (re.fullmatch(r"(\d{4,})\.model", n) for n in names)
                      if m)

    def _sync_latest_model(self) -> bool:
        """Load the newest VALID checkpoint at or past start_counter,
        walking backward past corrupt/truncated files (each skip is
        logged). A crash mid-save or disk corruption must cost at most
        the lost rounds, never the whole run - and never silently
        resume from garbage (the reference loads whatever bytes are
        there, cxxnet_main.cpp:100-113). The scan is listdir-based, not
        an ascending existence probe, so keep_latest rotation having
        deleted the early checkpoints does not hide the survivors."""
        from cxxnet_tpu.nnet import checkpoint
        counters = [c for c in self._model_counters()
                    if c >= self.start_counter]
        while counters:
            c = counters.pop()
            path = self._model_name(c)
            t0 = time.perf_counter()
            err = checkpoint.validate_file(path)
            if err is None:
                try:
                    self.net_trainer = self._create_net()
                    with open(path, "rb") as fi:
                        self.net_trainer.load_model(fi)
                except (OSError, ValueError, KeyError,
                        struct.error) as e:
                    # validate_file can pass formats it cannot cheaply
                    # check (legacy binaries, whose loader raises
                    # struct.error/KeyError on garbage); a failed load
                    # walks back like any other invalid file
                    err = str(e)
                    self.net_trainer = None
            if err is not None:
                # crc-skip walk-back: countable, not just a stderr line
                telemetry.inc("checkpoint.walkback")
                telemetry.stderr(
                    f"Init: skipping invalid checkpoint {path}: {err}\n",
                    event_kind="checkpoint", op="skip_invalid",
                    path=path, error=err)
                continue
            secs = time.perf_counter() - t0
            telemetry.observe("checkpoint.load_s", secs)
            telemetry.event("checkpoint", op="load", path=path,
                            round=c, secs=secs)
            # the next save overwrites the first invalid/missing slot,
            # re-training the lost rounds
            self.start_counter = c + 1
            return True
        return False

    def _newest_model_counter(self) -> Optional[int]:
        """Largest %04d.model counter present in model_dir, if any."""
        hits = self._model_counters()
        return hits[-1] if hits else None

    def _load_model(self) -> None:
        base = os.path.basename(self.name_model_in)
        try:
            self.start_counter = int(base.split(".")[0]) + 1
        except ValueError:
            # default to one past the newest existing checkpoint so the
            # next save can never overwrite one (a stale start_counter
            # here used to clobber existing %04d.model files)
            newest = self._newest_model_counter()
            self.start_counter = (newest + 1 if newest is not None
                                  else self.start_counter + 1)
            telemetry.stdout(
                f"WARNING: cannot infer start_counter from model name; "
                f"using {self.start_counter} (one past the newest "
                f"checkpoint in {self.name_model_dir})")
        self.net_trainer = self._create_net()
        t0 = time.perf_counter()
        with open(self.name_model_in, "rb") as fi:
            self.net_trainer.load_model(fi)
        secs = time.perf_counter() - t0
        telemetry.observe("checkpoint.load_s", secs)
        telemetry.event("checkpoint", op="load", path=self.name_model_in,
                        secs=secs)

    def _copy_model(self) -> None:
        self.net_trainer = self._create_net()
        self.net_trainer.init_model()
        with open(self.name_model_in, "rb") as fi:
            self.net_trainer.copy_model_from(fi)

    def _save_model(self) -> None:
        # quirk parity: the modulo check uses the POST-incremented counter
        # (cxxnet_main.cpp:173-176), so with save_model=k the rounds saved
        # are k-1, 2k-1, ... — e.g. save_model=num_round=15 writes only
        # 0014.model. Kept so round numbering matches the reference.
        counter = self.start_counter
        self.start_counter += 1
        barrier = None
        if self._coordinator is not None:
            # elastic pod: EVERY round boundary is a barrier (absent
            # members must be convicted promptly, not only on save
            # rounds), and on save rounds only the elected leader
            # writes - ending the N-independent-writers race on the
            # shared %04d.model path
            barrier = self._pod_barrier(counter)
        if self.save_period == 0 or self.start_counter % self.save_period:
            return
        if barrier is not None and not barrier.is_leader:
            telemetry.event("checkpoint", op="skip_nonleader",
                            round=counter, leader=barrier.leader)
            return
        os.makedirs(self.name_model_dir, exist_ok=True)
        path = self._model_name(counter)
        t0 = time.perf_counter()
        # durable save: tmp + fsync + os.replace, so a kill mid-write
        # leaves at most a *.tmp - %04d.model is complete or absent
        with atomic_writer(path) as fo:
            self.net_trainer.save_model(fo)
        # end-to-end save cost incl. fsync + rename (serialization-only
        # time is checkpoint.write_s, kept by nnet/checkpoint.py)
        secs = time.perf_counter() - t0
        telemetry.inc("checkpoint.saves")
        telemetry.observe("checkpoint.save_s", secs)
        # progress beacon: a round spent fsyncing a huge checkpoint is
        # slow, not hung - the watchdog must not page on it
        telemetry.beacon("checkpoint.save")
        try:
            nbytes = os.path.getsize(path)
        except OSError:
            nbytes = -1
        telemetry.event("checkpoint", op="save", round=counter,
                        path=path, secs=secs, bytes=nbytes)
        if barrier is not None:
            # pod-wide publish manifest: the checkpoint the pod agrees
            # on, stamped with the monotonically increasing pod epoch
            # (what a restarted/reshaped generation resumes from)
            from cxxnet_tpu.parallel.coordinator import file_sha256
            self._coordinator.publish(barrier, counter, path,
                                      file_sha256(path), nbytes)
        self._rotate_models(counter)
        if self.name_publish:
            # serving publish hook: atomic copy to the swap_watch'd
            # path AFTER the round file is durable - a live Server
            # sees complete checkpoints appear, never partial ones
            from cxxnet_tpu.nnet import checkpoint
            checkpoint.publish_model(path, self.name_publish)

    def _pod_barrier(self, counter: int):
        """One coordinated checkpoint barrier; a conviction exits this
        worker with RESHAPE_EXIT_CODE so the supervisor rolls the pod
        back to the published checkpoint and rebuilds it around the
        missing member (docs/FAULT_TOLERANCE.md "Elastic pod")."""
        from cxxnet_tpu.parallel.coordinator import PodReshapeRequired
        from cxxnet_tpu.utils.fault import RESHAPE_EXIT_CODE
        try:
            return self._coordinator.barrier(counter)
        except PodReshapeRequired as e:
            telemetry.stderr(
                f"elastic: {e}; exiting for pod reshape\n",
                event_kind="coord", op="reshape_exit", round=counter,
                missing=e.missing, dead=e.dead)
            sys.stderr.flush()
            sys.exit(RESHAPE_EXIT_CODE)

    def _rotate_models(self, saved: int) -> None:
        """keep_latest=k: bound the checkpoint set to the k newest
        %04d.model files (rescue.model and foreign files untouched).
        Counters past the one just saved are left alone: a stale
        higher-counter file (e.g. corrupt debris a resume walked back
        over) must not push fresh valid checkpoints out of the keep
        window - it is skipped by resume and overwritten in place when
        the counter catches up."""
        if self.keep_latest <= 0:
            return
        live = [c for c in self._model_counters() if c <= saved]
        for c in live[:-self.keep_latest]:
            try:
                os.remove(self._model_name(c))
            except OSError:
                pass  # concurrent cleanup / permissions: rotation is
                # best-effort, the save itself already succeeded

    def _save_rescue(self) -> str:
        """Final rescue checkpoint on divergence abort: the last good
        (rolled-back) params, in a file resume will not probe."""
        os.makedirs(self.name_model_dir, exist_ok=True)
        path = os.path.join(self.name_model_dir, "rescue.model")
        with atomic_writer(path) as fo:
            self.net_trainer.save_model(fo)
        return path

    # ------------------------------------------------------------------
    def _create_iterators(self) -> None:
        defcfg, train, evals, pred = self._split_blocks()
        if self.task in ("pred", "pred_raw", "extract", "serve"):
            if pred is not None:
                self.itr_pred = create_iterator(pred)
        else:
            if train is not None:
                self.itr_train = create_iterator(train)
            for evname, itcfg in evals:
                self.itr_evals.append(create_iterator(itcfg))
                self.eval_names.append(evname)

        def init_iter(it):
            for k, v in defcfg:
                it.set_param(k, v)
            # multi-controller: each worker feeds the batch rows its
            # devices OWN under the mesh (auto-wired unless the config
            # sets dist_num_worker explicitly). Mesh-aware: on a pure
            # data mesh that is batch/nproc rows from a per-worker data
            # shard; on a mesh whose batch dim is replicated across
            # processes (e.g. a cross-host 'seq' axis - the batch
            # splits over the sequence dim instead), every worker must
            # feed the SAME full batch, so no data shard is applied.
            import jax
            if jax.process_count() > 1:
                lb = self.net_trainer._local_batch
                it.set_param("batch_size", str(lb))
                nshard = self.batch_size // lb
                if nshard > 1 and not any(
                        k == "dist_num_worker" for k, _ in self.cfg):
                    shard = self.net_trainer._local_row_start // lb
                    it.set_param("dist_num_worker", str(nshard))
                    it.set_param("dist_worker_rank", str(shard))
            it.init()

        for it in filter(None, [self.itr_train, self.itr_pred]):
            init_iter(it)
        for it in self.itr_evals:
            init_iter(it)

    # ------------------------------------------------------------------
    def task_train(self) -> None:
        # monotonic: elapsed reporting must survive NTP step/slew of
        # the wall clock (a backwards jump under time.time() printed
        # negative/garbage durations)
        start = time.monotonic()
        if self.continue_training == 0 and self.name_model_in == "NULL":
            self._save_model()
        else:
            line = "".join(self.net_trainer.evaluate(it, name)
                           for it, name in zip(self.itr_evals,
                                               self.eval_names))
            telemetry.stderr(line + "\n", event_kind="eval",
                            round=self.start_counter - 1,
                            values=_eval_values(line))
            sys.stderr.flush()

        if self.itr_train is None:
            return
        if self.test_io:
            telemetry.stdout("start I/O test")
        cc = self.max_round
        try:
            self._train_rounds(cc, start)
        except DivergenceError:
            # abort, but not empty-handed: the state is the last good
            # (rolled-back) params - worth a rescue checkpoint
            path = self._save_rescue()
            telemetry.inc("fault.divergence_abort")
            telemetry.stderr(
                f"divergence guard: training aborted; rescue checkpoint "
                f"saved to {path}\n",
                event_kind="fault", type="divergence_abort",
                rescue=path)
            raise
        final_profile = self.net_trainer.profile_summary()
        if final_profile:
            telemetry.stderr(final_profile + "\n")
            sys.stderr.flush()
        if not self.silent:
            telemetry.stdout(
                f"\nupdating end, {int(time.monotonic() - start)} "
                "sec in all")

    def _train_rounds(self, cc: int, start: float) -> None:
        while self.start_counter <= self.num_round and cc > 0:
            cc -= 1
            if not self.silent:
                telemetry.stdout(f"update round {self.start_counter - 1}")
            telemetry.event("round_start", round=self.start_counter)
            sample_counter = 0
            self.net_trainer.start_round(self.start_counter)
            itr = self.itr_train
            prefetched = self.test_io == 0 and self.prefetch_stage > 0
            # fused dispatch (docs/PERFORMANCE.md): K batches per
            # jitted scan; test_io keeps per-batch accounting (it
            # measures the pipeline, nothing dispatches)
            fused_k = (self.steps_per_dispatch if self.test_io == 0
                       else 1)
            if prefetched:
                # stage batch k+1 (pad+cast+H2D) on a worker thread
                # while step k runs (io/prefetch.py); chunk=K makes
                # the worker assemble fused chunks; test_io keeps the
                # raw iterator - it measures the pipeline, not staging
                itr = self.net_trainer.prefetch(
                    itr, self.prefetch_stage, chunk=fused_k)
            pending = []  # fused, non-prefetched: batches awaiting K

            def tick(n_micro):
                # per-TRAINED-microstep progress accounting: fused
                # paths tick only after their chunk dispatched, so the
                # progress line never claims samples a failed chunk
                # would leave untrained (and K=1 keeps the historic
                # per-batch print cadence byte-for-byte)
                nonlocal sample_counter
                for _ in range(n_micro):
                    sample_counter += 1
                    if (sample_counter % self.print_step == 0
                            and not self.silent):
                        elapsed = int(time.monotonic() - start)
                        telemetry.stdout(
                            f"round {self.start_counter - 1:8d}:"
                            f"[{sample_counter:8d}] {elapsed} sec "
                            "elapsed")

            try:
                itr.before_first()
                while itr.next():
                    v = itr.value()
                    n_micro = 1
                    if self.test_io == 0:
                        if fused_k > 1 and not prefetched:
                            pending.append(v)
                            n_micro = 0
                            if len(pending) >= fused_k:
                                n_micro = len(pending)
                                self.net_trainer.update_chunk(pending)
                                pending = []
                        else:
                            # a StagedChunk (prefetched fused mode)
                            # routes to update_chunk inside update()
                            if isinstance(v, StagedChunk):
                                n_micro = v.n_steps
                            self.net_trainer.update(v)
                    tick(n_micro)
                if pending:
                    # round-boundary flush: the pass ended mid-chunk -
                    # a SHORT fused chunk trains the tail batches this
                    # round instead of silently dropping them
                    n_micro = len(pending)
                    self.net_trainer.update_chunk(pending)
                    pending = []
                    tick(n_micro)
            finally:
                if prefetched:
                    # an update() error mid-round must not leak the
                    # worker + its staged device batches
                    itr.close()
            self.net_trainer.finish_round_profile()
            stats = self.net_trainer.round_stats()
            round_label = self.start_counter
            if self.test_on_server:
                # CheckWeight_ analog (async_updater-inl.hpp:144-153):
                # every round, verify that replicated weights really are
                # identical on every device/process; abort on divergence
                bad = self.net_trainer.check_weights()
                if bad:
                    raise RuntimeError(
                        "test_on_server: weight consistency check "
                        "failed:\n" + "\n".join(bad))
            if self.test_io == 0:
                line = f"[{self.start_counter}]"
                if self.eval_train:
                    line += self.net_trainer.eval_train_metric()
                for it, name in zip(self.itr_evals, self.eval_names):
                    line += self.net_trainer.evaluate(it, name)
                # one write, same bytes as the historic piecewise
                # writes; the mirrored event carries the parsed values
                telemetry.stderr(line + "\n", event_kind="eval",
                                 round=self.start_counter,
                                 values=_eval_values(line))
                sys.stderr.flush()
            self._save_model()
            if stats is not None:
                # per-round throughput/latency record: one `round`
                # event on the log stream and one registry snapshot on
                # the metrics stream (what tools/metrics_report.py
                # tabulates). Emitted AFTER _save_model so the round's
                # own checkpoint save cost lands in its row, not the
                # next round's (_save_model already bumped
                # start_counter - round_label pins the finished round).
                telemetry.event("round", round=round_label, **stats)
                telemetry.emit_metrics(kind="round", round=round_label,
                                       **stats)

    def _calibration_source(self):
        """(iterator, name) behind `pass_calibration_iter` - "pred"
        (default), "train", or an eval block's name."""
        name = self.pass_calibration_iter
        if name in ("", "pred"):
            return self.itr_pred, "pred"
        if name == "train":
            return self.itr_train, "train"
        for it, nm in zip(self.itr_evals, self.eval_names):
            if nm == name:
                return it, nm
        raise ValueError(
            f"pass_calibration_iter={name!r}: no such iterator "
            f"(have: train, pred"
            + ("".join(", " + n for n in self.eval_names)) + ")")

    def _calibrate_passes(self) -> bool:
        """Explicit fold calibration (docs/GRAPH_PASSES.md): pull
        `pass_calibration_batches` batches from the named calibration
        iterator and average the frozen moments over them. A no-op -
        returning False so callers keep the pinned lazy
        first-inference-batch path - when nothing needs calibration,
        or when neither multi-batch nor an explicit iterator was
        requested."""
        tr = self.net_trainer
        if not tr.passes_need_calibration():
            return False
        n = self.pass_calibration_batches
        if n <= 1 and not self.pass_calibration_iter:
            return False
        import numpy as np
        from cxxnet_tpu.io.data import DataBatch
        it, src = self._calibration_source()
        assert it is not None, \
            f"pass_calibration_iter={src!r}: iterator not configured"
        batches = []
        it.before_first()
        while len(batches) < n and it.next():
            b = it.value()
            # iterators may reuse their batch buffers across next():
            # snapshot the arrays for the multi-batch moment pool
            batches.append(DataBatch(
                data=(None if b.data is None else np.array(b.data)),
                label=np.array(b.label),
                inst_index=(None if b.inst_index is None
                            else np.array(b.inst_index)),
                num_batch_padd=b.num_batch_padd,
                extra_data=[np.array(e) for e in b.extra_data],
                sparse_row_ptr=(None if b.sparse_row_ptr is None
                                else np.array(b.sparse_row_ptr)),
                sparse_findex=(None if b.sparse_findex is None
                               else np.array(b.sparse_findex)),
                sparse_fvalue=(None if b.sparse_fvalue is None
                               else np.array(b.sparse_fvalue))))
        it.before_first()
        if not batches:
            return False
        self.net_trainer.calibrate_graph_passes(
            batches if len(batches) > 1 else batches[0])
        telemetry.stdout(
            f"graph_passes: calibrated on {len(batches)} batch(es) "
            f"from the {src} iterator")
        return True

    def task_predict(self) -> None:
        assert self.itr_pred is not None, \
            "must specify a predict iterator to generate predictions"
        self._calibrate_passes()
        telemetry.stdout("start predicting...")
        # tmp + os.replace: a crash mid-run cannot leave a truncated
        # prediction file behind (same protocol as checkpoint saves)
        with atomic_writer(self.name_pred, "w") as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value()
                pred = self.net_trainer.predict(batch)
                for v in pred:
                    fo.write(f"{v:g}\n")
        telemetry.stdout(
            f"finished prediction, write into {self.name_pred}")

    def task_predict_raw(self) -> None:
        """task=pred_raw: one line of raw top-node outputs (e.g. the
        full softmax probability row) per instance. The reference
        ACCEPTS this task when wiring iterators (cxxnet_main.cpp:242)
        but never dispatches it (:77-79), so its shipped
        kaggle_bowl/pred.conf silently did nothing; here it does what
        that conf intended."""
        assert self.itr_pred is not None, \
            "must specify a predict iterator to generate predictions"
        self._calibrate_passes()
        telemetry.stdout("start predicting...")
        with atomic_writer(self.name_pred, "w") as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value()
                # padding rows already trimmed (_forward_nodes keeps
                # mask.sum() rows, the reference's num_batch_padd trim)
                flat = self.net_trainer.predict_dist(batch)
                for row in flat:
                    fo.write(" ".join(f"{v:g}" for v in row) + "\n")
        telemetry.stdout(
            f"finished prediction, write into {self.name_pred}")

    def _serve_request_sizes(self):
        """Row count of each submitted request (task=serve load
        shape): serve_rows>0 = fixed; serve_rows=0 = a deterministic
        ragged cycle 1,2,3,5,7,... capped at the largest bucket, so a
        single pass exercises every bucket size (the serve-smoke CI
        job's mode)."""
        if self.serve_rows > 0:
            while True:
                yield self.serve_rows
        cycle = [1, 2, 3, 5, 7, 4, 6, 8]
        i = 0
        while True:
            yield cycle[i % len(cycle)]
            i += 1

    def task_serve(self) -> None:
        """task=serve: the continuous-batching server (docs/SERVING.md)
        warmed over its bucket executables, then the pred iterator
        replayed as a request stream - the CLI's serving surface and
        its own load generator. Output file matches task=pred line for
        line (the parity the serve-smoke CI job asserts)."""
        assert self.itr_pred is not None, \
            "must specify a predict iterator to drive task = serve"
        import numpy as np
        from cxxnet_tpu.serve import (
            QueueFullError, Server, predictions_from_rows)
        if (not self._calibrate_passes()
                and self.net_trainer.passes_need_calibration()):
            # fold_conv_bn needs statistics BEFORE the bucket
            # executables compile (they are frozen per Server): use
            # the first pred batch - the same source the predict
            # path calibrates from (docs/GRAPH_PASSES.md); the
            # explicit multi-batch/named-iterator path above takes
            # precedence when configured
            self.itr_pred.before_first()
            if self.itr_pred.next():
                self.net_trainer.calibrate_graph_passes(
                    self.itr_pred.value())
                telemetry.stdout(
                    "serve: calibrated graph passes on the first "
                    "pred batch")
        srv = Server(self.net_trainer)
        telemetry.stdout(
            f"serve: warming {len(srv.buckets)} bucket executables "
            f"{list(srv.buckets)}")
        srv.warmup()
        telemetry.stdout("serve: warmup done, start serving")
        import collections
        import signal
        import threading
        # graceful drain on SIGTERM (docs/SERVING.md "Connection
        # limits & drain"): the handler only flips an Event - the
        # serving loop notices it between submissions, stops feeding,
        # resolves everything already admitted, and exits 0 with the
        # output file complete for the rows served
        term = threading.Event()
        old_term = None
        try:
            old_term = signal.signal(
                signal.SIGTERM, lambda signum, frame: term.set())
        except ValueError:
            pass  # not the main thread (embedded run): no handler
        sizes = self._serve_request_sizes()
        t0 = time.monotonic()
        # bounded in-flight window: futures resolve in submission
        # order, so results drain to the output file DURING iteration
        # - task=pred streams in constant memory and task=serve must
        # too (an unbounded submit-then-drain would hold the whole
        # dataset's inputs and results in RAM)
        futures = collections.deque()
        max_inflight = 4 * srv.max_batch
        srv.start()
        try:
            with atomic_writer(self.name_pred, "w") as fo:
                def drain(down_to: int) -> None:
                    while len(futures) > down_to:
                        rows = futures.popleft().result()
                        for v in predictions_from_rows(rows):
                            fo.write(f"{v:g}\n")

                self.itr_pred.before_first()
                while not term.is_set() and self.itr_pred.next():
                    batch = self.itr_pred.value()
                    if batch.is_sparse():
                        c, y, x = self.net_trainer.net_cfg.input_shape
                        data = batch.to_dense(c * y * x).reshape(
                            batch.batch_size, c, y, x)
                    else:
                        data = np.asarray(batch.data)
                    valid = batch.batch_size - batch.num_batch_padd
                    data = data[:valid]
                    extras = [np.asarray(e)[:valid]
                              for e in batch.extra_data[
                                  :self.net_trainer.net_cfg
                                  .extra_data_num]]
                    lo = 0
                    while lo < valid and not term.is_set():
                        n = min(next(sizes), valid - lo)
                        try:
                            futures.append(srv.submit(
                                data[lo:lo + n],
                                [e[lo:lo + n] for e in extras]))
                        except QueueFullError as e:
                            # serve_queue_limit armed below the
                            # in-flight window: this driver is the
                            # well-behaved client - honor the advice,
                            # drain, resubmit (no row may drop; the
                            # output must stay line-for-line pred)
                            drain(max_inflight // 2)
                            time.sleep(min(e.retry_after_s, 0.5))
                            continue
                        lo += n
                        drain(max_inflight)
                # reached on completion AND on SIGTERM: every future
                # already admitted resolves into the output file -
                # zero drops of admitted work either way
                drain(0)
        finally:
            if old_term is not None:
                signal.signal(signal.SIGTERM, old_term)
            if term.is_set():
                telemetry.stdout(
                    "serve: SIGTERM - draining queued requests")
                stats = srv.drain()
            else:
                stats = srv.stop()
        dt = time.monotonic() - t0
        qps = stats["requests"] / dt if dt > 0 else 0.0
        telemetry.stdout(
            f"serve: {stats['requests']} requests ({stats['rows']} "
            f"rows) in {dt:.2f} sec, {qps:.1f} req/s, "
            f"p50 {stats['latency_p50_ms']} ms, "
            f"p99 {stats['latency_p99_ms']} ms, "
            f"{stats['padding_rows']} padding rows over "
            f"{stats['batches']} batches")
        telemetry.event("serve", op="summary", secs=dt, qps=qps, **{
            k: v for k, v in stats.items() if not isinstance(v, dict)})
        telemetry.emit_metrics(kind="serve")
        telemetry.stdout(
            f"finished serving, write into {self.name_pred}")

    def task_extract_feature(self) -> None:
        assert self.itr_pred is not None, \
            "must specify a predict iterator to generate predictions"
        assert self.extract_node_name, \
            "extract node name must be specified in task extract"
        self._calibrate_passes()
        telemetry.stdout("start predicting...")
        nrow = 0
        dshape = None
        mode = "w" if self.output_format else "wb"
        with atomic_writer(self.name_pred, mode) as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value()
                feat = self.net_trainer.extract_feature(
                    batch, self.extract_node_name)
                nrow += feat.shape[0]
                dshape = feat.shape[1:]
                flat = feat.reshape(feat.shape[0], -1)
                if self.output_format:
                    for row in flat:
                        fo.write(" ".join(f"{v:g}" for v in row) + "\n")
                else:
                    flat.astype("float32").tofile(fo)
            if dshape is None:
                # raising inside the atomic_writer discards the tmp, so
                # no empty artifact appears (and a pre-existing output
                # from an earlier run is left untouched)
                raise ValueError(
                    "task=extract: the pred iterator yielded no data "
                    "(empty list file or dataset smaller than one batch)")
        with atomic_writer(self.name_pred + ".meta", "w") as fm:
            fm.write(f"{nrow},{dshape[0]},{dshape[1]},{dshape[2]}\n")
        telemetry.stdout(
            f"finished prediction, write into {self.name_pred}")


def main(argv: Optional[List[str]] = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    return LearnTask().run(argv)


if __name__ == "__main__":
    sys.exit(main())
