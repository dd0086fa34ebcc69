"""Continuous-batching inference server (docs/SERVING.md).

The predict/extract tasks are batch-at-a-time, train-shaped code: one
caller, one fixed batch, one padded dispatch. Production serving is
the opposite shape - many concurrent callers submitting a few rows
each - and the TF-paper framing (PAPERS.md, arXiv:1605.08695) treats
it as the same dataflow system with a different driver. This module is
that driver:

- a **shared request queue**: `submit()` is thread-safe and returns a
  future; requests larger than the biggest bucket split internally and
  re-join on `result()`;
- **continuous/dynamic batching into padded buckets**: dispatchers
  coalesce queued requests up to `max_batch` rows and run the smallest
  power-of-two bucket that covers them, padding the tail. Every bucket
  size is a distinct program shape of ONE jitted inference executable
  (trainer's `infer_fn`), so the bucket set compiles once;
- **warmed executables**: `warmup()` runs every bucket once at
  startup. Steady state then performs ZERO recompiles - provable via
  the same `_cache_size` technique the jaxpr audit uses
  (`executable_cache_size()` == `len(buckets)` and stays flat);
- **replica fan-out**: `replicas` dispatcher threads drain the shared
  queue; each dispatch is the SPMD executable over the full mesh (on
  `mesh = data:N` the bucket's rows spread over the data axis), and
  jax's async dispatch lets replicas pipeline host staging against
  device compute. `zero_stage = 3` params are consumed directly at
  their stored (sharded) layout - the executable's in_shardings are
  the trainer's `pstore`, so no host-side gather ever runs;
- an **admission/flush policy**: a dispatcher waits up to
  `max_wait_ms` for the bucket to fill, then flushes what it has
  (fill-or-timeout), so p99 latency stays bounded under low load.

Telemetry (docs/OBSERVABILITY.md): `serve.latency_s` histogram
(p50/p99 through the registry), the `serve.queue_s` / `serve.device_s`
per-request breakdown (request tracing: queue = submit -> dispatch,
incl. the fill-or-timeout coalesce wait; device = dispatch -> result
readback), the `serve.request_rows` Prometheus
histogram over the bucket ladder, `serve.queue_depth` gauge,
`serve.requests`/`serve.rows`/`serve.batches`/`serve.padding_rows`/
`serve.errors` counters. These accumulate unconditionally (they are
the product surface, queried via `Server.stats()`), like the fault
counters - no per-row device sync is added beyond the result readback
serving inherently requires. With the observability plane armed every
dispatch additionally lands in the flight recorder (executable
fingerprint + bucket + trace id - telemetry/flight.py), each warmed
bucket registers on `/executables`, and resolved requests emit `trace`
events that `tools/trace_export.py` renders to Perfetto-loadable
Chrome trace JSON.

The production front (this PR's layer, docs/SERVING.md "Serving over
HTTP" + "Hot-swap runbook"):

- **HTTP request path**: `Server(http_port=N)` (CLI `serve_port=`)
  attaches a `/predict` POST endpoint to the same stdlib listener
  that serves `/metrics`/`/healthz` - rows in, predictions out, trace
  ids minted at ingress so the queue-vs-device decomposition covers
  the network hop;
- **backpressure + load shedding**: a hard `queue_limit` (rows) above
  which `submit()` raises a typed `QueueFullError` and `/predict`
  returns 429 with a `Retry-After` derived from the queue depth and
  the measured drain rate; shedding flips `/healthz` to 503 through
  the health source map (`serve_shed`) until the queue drains below
  half the limit for a hysteresis window, so an LB can rotate the
  replica out and back in;
- **per-request deadlines**: `deadline_ms` (server default or per
  request) expires queued requests BEFORE dispatch - a dead request
  never wastes a bucket slot - surfacing as `DeadlineExpiredError`
  in-process and 504 over HTTP;
- **zero-downtime hot-swap**: `swap_to(path)` (or the `swap_watch=`
  polling thread) validates an atomic checksummed checkpoint (crc32
  trailer), stages the new params to device OUTSIDE any lock, and
  switches between batches under `_swap_lock`; in-flight dispatches
  already bound the old params and finish on the old weights, no
  request drops. A torn/corrupt file is rejected (`swap.rejected`
  event) and the old weights keep serving;
- **canaried rollout with automatic rollback** (`swap_canary_frac=`,
  docs/SERVING.md "Canary runbook"): a validated new checkpoint is
  STAGED as a candidate params slot instead of promoted - a
  deterministic fraction of requests (hash of the trace id, so split
  parts stay coherent) binds the candidate while the rest keep the
  incumbent, both through the SAME warmed bucket executables (params
  are jit arguments; the canary is a second argument binding - zero
  recompiles, `executable_cache_size()` stays flat). A judge thread
  scores the candidate over `swap_canary_window` seconds
  (error/deadline rates vs incumbent + shadow pairs: the same live
  rows dispatched through both param sets, compared argmax/allclose)
  and either auto-promotes (`swap` op=promoted) or auto-rolls-back
  (`swap` op=rolled_back; the incumbent is bitwise-untouched and the
  watcher quarantines the file exactly like a torn checkpoint - the
  pre-attempt stat record means it is never retried until
  republished);
- **hardened ingress + graceful drain** (docs/SERVING.md "Connection
  limits & drain"): `serve_conn_timeout_ms`/`serve_max_conns`/
  `serve_max_body_bytes` plumb to the listener (telemetry/http.py) -
  per-connection read deadlines so a slow-loris client cannot pin a
  listener thread, an accept gate answering 503 + Retry-After past
  the connection cap (own `serve_conns` health source with the same
  hysteretic recovery as shedding), and a 413 for bloated bodies
  before a byte of them is read. `drain()` (SIGTERM in `task=serve`)
  stops admission, flips /healthz to a draining verdict, resolves
  everything queued with zero drops, then stops.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from cxxnet_tpu import telemetry
from cxxnet_tpu.telemetry import spans
from cxxnet_tpu.telemetry.flight import fingerprint as exec_fingerprint
from cxxnet_tpu.utils import fault

# Retry-After advice when the drain-rate EWMA has no samples yet (a
# cold or just-restarted Server has dispatched nothing): the
# documented default the 429 header carries instead of an estimate
# derived from uninitialized state (docs/SERVING.md)
RETRY_AFTER_COLD_S = 1.0


def _trace_side(trace: str, frac: float) -> int:
    """Deterministic canary routing (docs/SERVING.md "Canary
    runbook"): hash of the request trace id against the traffic
    fraction - 1 = candidate, 0 = incumbent. Keyed on the trace so
    every split part of an oversize request lands on the same weight
    generation, and a retried trace routes the same way."""
    return 1 if zlib.crc32(trace.encode()) % 10000 < frac * 10000 else 0


class QueueFullError(RuntimeError):
    """submit() rejected: the queue is at `queue_limit` rows (load
    shedding, docs/SERVING.md). Carries the advice an HTTP 429 turns
    into a Retry-After header: `retry_after_s` (queue depth over the
    measured drain rate) and the `queue_depth` at rejection."""

    def __init__(self, msg: str, retry_after_s: float,
                 queue_depth: int) -> None:
        super().__init__(msg)
        self.retry_after_s = retry_after_s
        self.queue_depth = queue_depth


class DeadlineExpiredError(RuntimeError):
    """The request's deadline passed while it was still queued; it was
    dropped before dispatch (a dead request must never spend a bucket
    slot). HTTP callers see 504."""


def bucket_sizes(max_batch: int, data_axis: int = 1) -> Tuple[int, ...]:
    """The padded-batch bucket set: powers of two up to `max_batch`
    that the mesh's data axis divides (a bucket's rows must split
    evenly over the axis), plus `max_batch` itself. At least one
    bucket must exist - a `max_batch` the data axis does not divide
    cannot be dispatched and is rejected here, at configure time."""
    if max_batch < 1:
        raise ValueError("serve_max_batch must be >= 1")
    if max_batch % max(data_axis, 1):
        raise ValueError(
            f"serve_max_batch={max_batch} must be a multiple of the "
            f"mesh's data-axis size ({data_axis}) - every bucket "
            "dispatches over that axis")
    out = set()
    b = 1
    while b <= max_batch:
        if b % data_axis == 0:
            out.add(b)
        b *= 2
    out.add(max_batch)
    return tuple(sorted(out))


def ladder_buckets(ladder: Sequence[int], max_batch: int,
                   data_axis: int = 1) -> Tuple[int, ...]:
    """An EXPLICIT bucket ladder (the autotuner's telemetry-shaped
    rungs, or `serve_bucket_ladder =` - docs/GRAPH_PASSES.md) folded
    into a valid bucket set: rungs outside [1, max_batch] or not
    divisible by the mesh's data axis are dropped (the
    inapplicable-tuned-value rule - a cache shaped on one mesh must
    not break another), and `max_batch` itself always closes the
    ladder. The max_batch/data-axis contract is bucket_sizes'."""
    if max_batch < 1:
        raise ValueError("serve_max_batch must be >= 1")
    if max_batch % max(data_axis, 1):
        raise ValueError(
            f"serve_max_batch={max_batch} must be a multiple of the "
            f"mesh's data-axis size ({data_axis}) - every bucket "
            "dispatches over that axis")
    axis = max(data_axis, 1)
    out = {int(b) for b in ladder
           if 1 <= int(b) <= max_batch and int(b) % axis == 0}
    out.add(max_batch)
    return tuple(sorted(out))


def ladder_from_histogram(hist, max_batch: int, data_axis: int = 1,
                          rungs: int = 4) -> Tuple[int, ...]:
    """Shape a bucket ladder from an observed request-size histogram
    ({size: count}, the Server's `request_sizes` stat): one rung at
    each 1/rungs quantile of the size distribution, rounded UP to the
    data axis, closed by `max_batch`. Sizes the traffic actually
    sends get tight buckets (less padding); sizes it never sends get
    no bucket (fewer warmed executables) - the TVM move of shaping
    the search space from the workload instead of a fixed
    power-of-two set. Falls back to bucket_sizes on an empty
    histogram."""
    sizes = sorted((int(s), int(c)) for s, c in dict(hist).items()
                   if int(c) > 0 and int(s) >= 1)
    if not sizes:
        return bucket_sizes(max_batch, data_axis)
    axis = max(data_axis, 1)
    total = sum(c for _, c in sizes)
    ladder = []
    for r in range(1, max(rungs, 1) + 1):
        target = r * total / max(rungs, 1)
        acc = 0
        for s, c in sizes:
            acc += c
            if acc >= target:
                ladder.append(-(-s // axis) * axis)  # ceil to axis
                break
    return ladder_buckets(ladder, max_batch, data_axis)


def predictions_from_rows(rows: np.ndarray) -> np.ndarray:
    """The TransformPred rule (trainer.predict) applied to raw final-
    node rows: single-column output passes through as scalars, wider
    output argmaxes - so a serve result file is comparable line-for-
    line with a `task = pred` file."""
    rows = np.asarray(rows)
    flat = rows.reshape(rows.shape[0], -1)
    if flat.shape[1] == 1:
        return flat[:, 0]
    return np.argmax(flat, axis=1).astype(np.float32)


class _Future:
    """Minimal one-shot result future (no concurrent.futures executor
    to tie its lifetime to)."""

    __slots__ = ("_ev", "_value", "_error", "trace")

    def __init__(self) -> None:
        self._ev = threading.Event()
        self._value = None
        self._error: Optional[BaseException] = None
        # the request trace id (minted at submit; the HTTP front
        # echoes it in the /predict response body)
        self.trace = ""

    def _set(self, value) -> None:
        self._value = value
        self._ev.set()

    def _set_error(self, err: BaseException) -> None:
        self._error = err
        self._ev.set()

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("serve request still pending")
        if self._error is not None:
            raise self._error
        return self._value


class _JoinedFuture:
    """A request that split into several work items: result() is the
    row-concatenation of the parts, in submission order."""

    __slots__ = ("_parts",)

    def __init__(self, parts: List[_Future]) -> None:
        self._parts = parts

    @property
    def trace(self) -> str:
        return self._parts[0].trace if self._parts else ""

    def done(self) -> bool:
        return all(p.done() for p in self._parts)

    def result(self, timeout: Optional[float] = None):
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        out = []
        for p in self._parts:
            left = (None if deadline is None
                    else max(0.0, deadline - time.monotonic()))
            out.append(p.result(left))
        return np.concatenate(out, axis=0)


class _Canary:
    """A staged candidate weight generation under judgment
    (docs/SERVING.md "Canary runbook"). Every mutable field moves
    under the owning Server's `_swap_lock`; the judge thread snapshots
    under the lock and dispatches shadow pairs OUTSIDE it (GL015)."""

    __slots__ = ("params", "path", "epoch", "frac", "t0", "n_req",
                 "n_err", "n_exp", "shadow", "shadow_done",
                 "provenance")

    def __init__(self, params, path: str, epoch: int,
                 frac: float) -> None:
        self.params = params
        self.path = path
        self.epoch = epoch
        self.frac = frac
        self.t0 = time.monotonic()
        # per-side accounting over the judging window, indexed
        # [incumbent, candidate]: dispatched requests, dispatch
        # errors, deadline expiries - the judge's rate comparison
        self.n_req = [0, 0]
        self.n_err = [0, 0]
        self.n_exp = [0, 0]
        # sampled live request rows pending a shadow comparison
        # ((data, extras) copies; capped small - a sample, not a tap)
        self.shadow: List[Tuple[np.ndarray, List[np.ndarray]]] = []
        self.shadow_done = 0
        # publish_model's sidecar metadata (src path etc.), riding
        # the promoted/rolled_back events for provenance
        self.provenance: Dict[str, Any] = {}


class _WorkItem:
    __slots__ = ("data", "extras", "n", "t_submit", "future",
                 "trace", "part", "nparts", "t_collect", "deadline",
                 "side")

    def __init__(self, data, extras, t_submit, trace="",
                 part=0, nparts=1, deadline=0.0) -> None:
        self.data = data
        self.extras = extras
        self.n = data.shape[0]
        self.t_submit = t_submit
        self.future = _Future()
        # absolute monotonic expiry (0 = none): checked at queue-pop
        # so an expired request drops BEFORE dispatch
        self.deadline = deadline
        # end-to-end request tracing (docs/OBSERVABILITY.md "Request
        # tracing"): the trace id minted at submit(), the part index
        # for oversize requests that split, and the coalesce time a
        # dispatcher stamps when it pops the item; the queue/device
        # latency cut itself is the DISPATCH stamp (_run_batch) -
        # the fill wait after the pop is still queue time
        self.trace = trace
        self.part = part
        self.nparts = nparts
        self.t_collect = 0.0
        # canary routing side (0 = incumbent, 1 = candidate), stamped
        # at queue-pop from the trace hash while a canary is active;
        # a batch only ever coalesces items of one side
        self.side = 0


class Server:
    """Continuous-batching server over a trainer's inference
    executable. The trainer must hold a model (init_model or
    load_model); its mesh, dtype and device_augment spec all apply
    unchanged - serving is the same compiled forward predict runs,
    driven by a queue instead of an iterator.

    start() spawns the dispatcher replicas (warmup() first unless you
    want the first requests to pay the compiles); submit() from any
    thread; stop() drains the queue, joins the replicas and returns
    stats(). Usable as a context manager."""

    def __init__(self, trainer, max_batch: int = 0,
                 max_wait_ms: Optional[float] = None,
                 replicas: Optional[int] = None,
                 node: int = -1,
                 metrics_port: Optional[int] = None,
                 metrics_host: str = "0.0.0.0",
                 ladder: Optional[Sequence[int]] = None,
                 http_port: Optional[int] = None,
                 queue_limit: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 swap_watch: Optional[str] = None,
                 swap_poll_ms: Optional[float] = None,
                 canary_frac: Optional[float] = None,
                 canary_window: Optional[float] = None,
                 conn_timeout_ms: Optional[float] = None,
                 max_conns: Optional[int] = None,
                 max_body_bytes: Optional[int] = None) -> None:
        import jax
        if trainer.state is None:
            raise RuntimeError(
                "Server needs an initialized trainer (init_model or "
                "load_model first)")
        if jax.process_count() > 1:
            raise RuntimeError(
                "serving a multi-controller job is not supported; run "
                "the server on a single-process mesh")
        self.trainer = trainer
        self.max_batch = int(max_batch or trainer.serve_max_batch
                             or trainer.batch_size)
        self.max_wait_ms = float(
            trainer.serve_max_wait_ms if max_wait_ms is None
            else max_wait_ms)
        self.replicas = int(trainer.serve_replicas if replicas is None
                            else replicas)
        if self.replicas < 1:
            raise ValueError("serve_replicas must be >= 1")
        self.node = (node if node >= 0
                     else trainer.net_cfg.num_nodes - 1)
        dsize = trainer.mesh.shape.get("data", 1)
        # explicit ladder > trainer's (tuned or serve_bucket_ladder =)
        # ladder > the power-of-two default - the same
        # explicit-keys-win chain the scalar serve knobs ride
        lad = (ladder if ladder is not None
               else getattr(trainer, "serve_ladder", None))
        self.buckets = (ladder_buckets(lad, self.max_batch, dsize)
                        if lad else
                        bucket_sizes(self.max_batch, dsize))
        if getattr(trainer, "passes_need_calibration",
                   lambda: False)():
            # a calibrating pass (fold_conv_bn / quantize_int8)
            # without stats: the infer executable built below is the
            # un-rewritten FLOAT graph (safe, just unoptimized) and
            # stays so for this Server's lifetime - warmup on zeros
            # must never become the calibration batch (zero-input
            # moments and activation ranges would be garbage).
            # task=serve calibrates from the first pred batch before
            # building the Server (main.py); programmatic users call
            # trainer.calibrate_graph_passes (or predict once) first.
            telemetry.stderr(
                "serve: graph passes (fold_conv_bn/quantize_int8) "
                "have no calibration stats; serving the unoptimized "
                "float graph (calibrate before Server creation to "
                "fold/quantize)\n",
                event_kind="serve", op="fold_uncalibrated")
        self._fn = trainer._infer_fn(self.node)
        c, y, x = trainer.net_cfg.input_shape
        self._input_dims = (c, y, x)
        self._extra_dims = [
            tuple(trainer.net.node_shapes[1 + i][1:])
            for i in range(trainer.net_cfg.extra_data_num)]
        # attachable live-exposition server (docs/OBSERVABILITY.md):
        # metrics_port=N serves /metrics + /healthz + /varz for the
        # Server's lifetime (0 = ephemeral bind, read .metrics_server
        # .port). None = off; programmatic twins of the CLI key, which
        # arms the process-wide plane in main.run instead.
        # http_port=N (CLI serve_port=) attaches the SAME listener
        # plus the /predict request path - one socket, both surfaces;
        # specifying both ports with different values is an error.
        if http_port is None:
            cfg_port = int(getattr(trainer, "serve_port", 0) or 0)
            if cfg_port > 0:
                http_port = cfg_port
        if (http_port is not None and metrics_port is not None
                and int(http_port) != int(metrics_port)):
            raise ValueError(
                "serve_port and metrics_port attach ONE listener; "
                f"set them equal or drop one (got {http_port} vs "
                f"{metrics_port})")
        self.http_port = http_port
        self.metrics_port = (metrics_port if metrics_port is not None
                             else http_port)
        self.metrics_host = metrics_host
        self.metrics_server = None
        if self.metrics_port is not None:
            # the attached exposition endpoint is a flight-recorder
            # consumer (it serves the /varz tail and /executables) -
            # arm the recorder for this Server's lifetime, the same
            # rule arm_observability applies to the process-wide
            # plane. Armed HERE (not in start()) so warmup()'s cost
            # enrichment sees it: warmup conventionally runs before
            # start(). stop() re-derives from the remaining consumers.
            telemetry.get().flight.enabled = True
        self._cond = threading.Condition()
        # admission state: the queue, its row count and the drain flag
        # move together under the condition (checked statically -
        # docs/STATIC_ANALYSIS.md GL016)
        self._queue: collections.deque = collections.deque()
        # guarded-by: self._cond
        self._queued_rows = 0
        self._threads: List[threading.Thread] = []
        # guarded-by: self._cond
        self._draining = False
        self._started = False
        self.warmup_s = 0.0
        # backpressure (docs/SERVING.md "Serving over HTTP"): hard
        # queue bound in ROWS (0 = unlimited), the default request
        # deadline, and the shed->healthy hysteresis window
        self.queue_limit = int(
            trainer.serve_queue_limit if queue_limit is None
            else queue_limit)
        self.deadline_ms = float(
            trainer.serve_deadline_ms if deadline_ms is None
            else deadline_ms)
        self.shed_clear_ms = float(
            getattr(trainer, "serve_shed_clear_ms", 1000.0))
        # guarded-by: self._cond
        self._last_shed_t = 0.0
        # whether this Server currently holds the `serve_shed` source
        # unhealthy (503 on /healthz); cleared with hysteresis once
        # the queue drains below queue_limit/2 for shed_clear_ms
        # guarded-by: self._cond
        self._shed_health = False
        # checkpoint hot-swap (docs/SERVING.md "Hot-swap runbook"):
        # _swap_lock orders the params/fn switch against dispatch
        # snapshots; ONLY attribute reads/writes happen under it -
        # staging (device_put) and warmup stay outside (GL015)
        self._swap_lock = threading.Lock()
        self.swap_watch = (swap_watch if swap_watch is not None
                           else getattr(trainer, "swap_watch", "")) or ""
        self.swap_poll_ms = float(
            getattr(trainer, "swap_poll_ms", 200.0)
            if swap_poll_ms is None else swap_poll_ms)
        self._swap_thread: Optional[threading.Thread] = None
        # watcher shutdown signal (checked each poll tick)
        self._swap_stop = threading.Event()
        # canaried rollout (docs/SERVING.md "Canary runbook"): with
        # canary_frac in (0, 1] a validated checkpoint stages as a
        # CANDIDATE slot instead of promoting, judged for
        # canary_window seconds. 0 = off: swap_to flips immediately,
        # no judge thread ever spawns (unarmed byte-parity)
        self.canary_frac = float(
            getattr(trainer, "swap_canary_frac", 0.0)
            if canary_frac is None else canary_frac)
        if not 0.0 <= self.canary_frac <= 1.0:
            raise ValueError("swap_canary_frac must be in [0, 1]")
        self.canary_window = float(
            getattr(trainer, "swap_canary_window", 10.0)
            if canary_window is None else canary_window)
        if self.canary_window <= 0:
            raise ValueError("swap_canary_window must be > 0")
        # the candidate under judgment (None = no canary in flight)
        # guarded-by: self._swap_lock
        self._canary: Optional[_Canary] = None
        self._canary_thread: Optional[threading.Thread] = None
        # judge shutdown signal: set by stop(), read each judge tick
        self._canary_stop = threading.Event()
        # connection-level ingress limits (enforced by the listener -
        # telemetry/http.py; configured here so the serve_* fallback
        # chain stays uniform). All 0 = off, the plain PR-16 listener.
        self.conn_timeout_ms = float(
            getattr(trainer, "serve_conn_timeout_ms", 0.0)
            if conn_timeout_ms is None else conn_timeout_ms)
        self.max_conns = int(
            getattr(trainer, "serve_max_conns", 0)
            if max_conns is None else max_conns)
        self.max_body_bytes = int(
            getattr(trainer, "serve_max_body_bytes", 0)
            if max_body_bytes is None else max_body_bytes)
        # last (mtime_ns, size) the watcher acted on - recorded even
        # for a REJECTED file so a torn checkpoint is skipped once,
        # not re-validated in a hot loop
        # guarded-by: self._swap_lock
        self._swap_seen: Optional[Tuple[int, int]] = None
        # product-surface accounting, independent of the process-wide
        # registry (a second Server in one process must not inherit
        # the first one's counts OR its latency window); the registry
        # mirrors everything for the metrics stream/report
        self._lock = threading.Lock()
        # guarded-by: self._lock
        self._n_requests = 0
        # guarded-by: self._lock
        self._n_rows = 0
        # guarded-by: self._lock
        self._n_batches = 0
        # guarded-by: self._lock
        self._n_padding = 0
        # guarded-by: self._lock
        self._n_errors = 0
        # guarded-by: self._lock
        self._n_shed = 0
        # guarded-by: self._lock
        self._n_shed_rows = 0
        # guarded-by: self._lock
        self._n_expired = 0
        # guarded-by: self._lock
        self._n_swaps = 0
        # guarded-by: self._lock
        self._n_swap_rejected = 0
        # guarded-by: self._lock
        self._n_canary_req = 0
        # guarded-by: self._lock
        self._n_canary_promoted = 0
        # guarded-by: self._lock
        self._n_canary_rolled_back = 0
        # measured drain rate (rows/s, EWMA over dispatched batches):
        # what Retry-After is derived from
        # guarded-by: self._lock
        self._drain_rate = 0.0
        # guarded-by: self._lock
        self._last_drain_t = 0.0
        # guarded-by: self._lock
        self._bucket_hits: Dict[int, int] = {b: 0 for b in self.buckets}
        # request-size histogram: the serve telemetry the autotuner's
        # ladder_from_histogram shapes the bucket ladder from
        # (docs/GRAPH_PASSES.md "per-layer autotuner"); counts per
        # submitted work-item row count
        # guarded-by: self._lock
        self._size_hist: Dict[int, int] = {}
        self._lat = telemetry.Histogram()
        # per-request queue-vs-device decomposition (request tracing):
        # queue = submit -> coalesce, device = coalesce -> result
        self._qlat = telemetry.Histogram()
        self._dlat = telemetry.Histogram()
        # request-size distribution as a proper Prometheus histogram
        # on /metrics (bounds = this Server's bucket ladder); the
        # dict-shaped stats()["request_sizes"] stays for the autotuner
        self._req_hist = telemetry.get().registry.bucket_histogram(
            "serve.request_rows", bounds=self.buckets)
        # request-trace ids minted at submit(); executable
        # fingerprints per warmed bucket (filled by warmup) feed the
        # flight recorder + /executables registry (telemetry/flight.py)
        self._trace_seq = itertools.count(1)
        self._exec_fp: Dict[int, str] = {}

    # -- lifecycle ---------------------------------------------------------
    def warmup(self) -> float:
        """Compile + run every bucket executable once (zeros input) so
        steady-state serving never compiles. Returns the wall seconds
        spent; also recorded as `serve.warmup_s`."""
        import jax
        t0 = time.perf_counter()
        params = self.trainer.state["params"]
        tel = telemetry.get()
        epoch = getattr(self.trainer, "_fold_epoch", 0)
        for b in self.buckets:
            data = np.zeros((b,) + self._input_dims, np.float32)
            extras = [np.zeros((b,) + d, np.float32)
                      for d in self._extra_dims]
            gdata, gextras = self.trainer.stage_infer_rows(data, extras)
            tb = time.perf_counter()
            jax.block_until_ready(self._fn(params, gdata, gextras))
            compile_s = time.perf_counter() - tb
            # executable registry (telemetry/flight.py): one entry per
            # warmed bucket program shape, stamped with its compile
            # wall-time (warmup's block IS the compile window). The
            # fingerprint is what flight entries and stall dumps name.
            fp = exec_fingerprint(
                "serve.infer", self.node, b, self._input_dims,
                epoch)
            self._exec_fp[b] = fp
            tel.executables.register(
                fp, name=f"serve.infer:b{b}", kind="serve",
                shape=str((b,) + self._input_dims),
                arg_bytes=int(data.nbytes
                              + sum(e.nbytes for e in extras)),
                device=jax.default_backend(), donated=0,
                compile_s=compile_s)
            if tel.flight.enabled:
                # armed plane: enrich with XLA cost analysis + output
                # footprint (one extra trace/lowering per bucket,
                # sanctioned here in the warmup window; the jit cache
                # the zero-recompile audit counts is untouched)
                tel.executables.enrich(fp, self._fn,
                                       (params, gdata, gextras))
        self.warmup_s = time.perf_counter() - t0
        telemetry.observe("serve.warmup_s", self.warmup_s)
        telemetry.event("serve", op="warmup", buckets=list(self.buckets),
                        secs=self.warmup_s)
        return self.warmup_s

    def executable_cache_size(self) -> Optional[int]:
        """Compiled-program count of the inference executable (the
        jaxpr audit's `_cache_size` technique): after warmup this
        equals len(buckets) and must stay flat under any steady-state
        request mix - the zero-recompile proof."""
        fn = getattr(self._fn, "_cache_size", None)
        return fn() if callable(fn) else None

    def start(self) -> "Server":
        if self._started:
            return self
        if self.metrics_port is not None and self.metrics_server is None:
            from cxxnet_tpu.telemetry.http import ObservabilityServer
            self.metrics_server = ObservabilityServer(
                telemetry.get(), int(self.metrics_port),
                host=self.metrics_host,
                predict_backend=(self if self.http_port is not None
                                 else None),
                conn_timeout_ms=self.conn_timeout_ms,
                max_conns=self.max_conns,
                max_body_bytes=self.max_body_bytes,
                conn_clear_ms=self.shed_clear_ms)
            self.metrics_server.start()
            telemetry.event("observability", op="http_start",
                            port=self.metrics_server.port,
                            host=self.metrics_host,
                            predict=self.http_port is not None)
        with self._cond:
            # published under the lock that guards it: a replica from
            # a previous start/stop cycle draining late must not read
            # a torn flag
            self._draining = False
        with self._lock:
            # a restarted Server serves a fresh traffic mix: the
            # previous run's drain-rate EWMA is stale advice, so
            # Retry-After reverts to the documented cold default
            # until a batch dispatches (RETRY_AFTER_COLD_S)
            self._drain_rate = 0.0
            self._last_drain_t = 0.0
        self._started = True
        for i in range(self.replicas):
            t = threading.Thread(target=self._replica_loop,
                                 name=f"serve-replica-{i}", daemon=True)
            self._threads.append(t)
            t.start()
        if self.swap_watch and self._swap_thread is None:
            # checkpoint watcher: the file's CURRENT state counts as
            # already-served (the Server was presumably built from
            # it); only a subsequent publish triggers a swap
            with self._swap_lock:
                self._swap_seen = self._swap_stat()
            self._swap_stop.clear()
            self._swap_thread = threading.Thread(
                target=self._swap_watch_loop,
                name="serve-swap-watch", daemon=True)
            self._swap_thread.start()
        return self

    def stop(self, drain: bool = True) -> Dict[str, Any]:
        """Stop the replicas - after draining the queue (default), or
        immediately failing queued requests (drain=False) - and return
        stats(). Idempotent."""
        if self._swap_thread is not None:
            self._swap_stop.set()
            self._swap_thread.join(timeout=10.0)
            self._swap_thread = None
        if self._canary_thread is not None:
            # an undecided canary fails SAFE at shutdown: the judge
            # sees the stop signal and rolls back to the incumbent
            # (promotion needs a full window's evidence)
            self._canary_stop.set()
            self._canary_thread.join(timeout=15.0)
            self._canary_thread = None
        with self._cond:
            self._draining = True
            if not drain:
                while self._queue:
                    it = self._queue.popleft()
                    self._queued_rows -= it.n
                    it.future._set_error(
                        RuntimeError("server stopped before dispatch"))
            self._cond.notify_all()
            shed_held = self._shed_health
            self._shed_health = False
        if shed_held:
            # a stopped server is not "overloaded"; release the 503
            # so a restart doesn't inherit a stale verdict
            telemetry.get().health.clear("serve_shed")
        for t in self._threads:
            t.join(timeout=60.0)
        self._threads = []
        self._started = False
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        if self.metrics_port is not None:
            # this Server's endpoint was a flight consumer; re-derive
            # the recorder's armed state from whatever remains (sinks,
            # the process-wide plane, an explicit flight_recorder=1)
            telemetry.get()._refresh_flight()
        telemetry.set_gauge("serve.queue_depth", 0.0)
        stats = self.stats()
        telemetry.event("serve", op="stop", **{
            k: v for k, v in stats.items() if not isinstance(v, dict)})
        return stats

    def drain(self) -> Dict[str, Any]:
        """Graceful shutdown (docs/SERVING.md "Connection limits &
        drain"; `task=serve` runs this on SIGTERM): stop admitting -
        new submits raise and /predict answers 503 - flip /healthz to
        a `serve_drain` 503 so the LB rotates this replica out,
        resolve EVERYTHING already queued (zero drops: the replicas
        keep dispatching until the queue is empty), then stop.
        Returns the final stats()."""
        with self._cond:
            depth = self._queued_rows
            self._draining = True
            self._cond.notify_all()
        telemetry.get().health.set_unhealthy(
            "serve_drain", "draining: shutdown in progress")
        telemetry.event("serve", op="drain_start", queue_rows=depth)
        try:
            stats = self.stop(drain=True)
        finally:
            # the listener is closed by stop(); clear the verdict so
            # a long-lived process (or a restarted Server) does not
            # inherit a stale draining 503
            telemetry.get().health.clear("serve_drain")
        telemetry.event("serve", op="drain_done", queue_rows=depth,
                        errors=stats.get("errors"))
        return stats

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # -- submission --------------------------------------------------------
    def submit(self, data: np.ndarray, extras: Sequence = (),
               deadline_ms: Optional[float] = None):
        """Enqueue one request: data is (n, c, y, x) rows or a single
        (c, y, x) instance; extras (if the net declares extra inputs)
        ride along row-aligned. Returns a future whose result() is the
        raw final-node rows, (n, width) - predictions_from_rows turns
        them into predict()-style labels. Thread-safe; requests wider
        than the largest bucket split transparently.

        `deadline_ms` overrides the server default (serve_deadline_ms;
        0 = none): a request still queued past its deadline is dropped
        BEFORE dispatch and its future raises DeadlineExpiredError.
        With `queue_limit` set, a submit that would push the queue
        past the limit raises QueueFullError instead of enqueueing
        (load shedding - the HTTP front maps it to 429+Retry-After)."""
        if not self._started:
            raise RuntimeError("Server not started (call start())")
        data = np.ascontiguousarray(data)
        if data.ndim == 3:
            data = data[None]
        if data.ndim != 4 or data.shape[1:] != self._input_dims:
            raise ValueError(
                f"serve request must be (n, {self._input_dims[0]}, "
                f"{self._input_dims[1]}, {self._input_dims[2]}) or a "
                f"single instance; got {data.shape}")
        if data.shape[0] < 1:
            raise ValueError("serve request needs at least one row")
        extras = [np.ascontiguousarray(e, dtype=np.float32)
                  for e in extras]
        if len(extras) != len(self._extra_dims):
            raise ValueError(
                f"net declares {len(self._extra_dims)} extra inputs "
                f"but the request carries {len(extras)}")
        for e in extras:
            if e.shape[0] != data.shape[0]:
                raise ValueError("extras must be row-aligned with data")
        t_submit = time.monotonic()
        # request trace id (docs/OBSERVABILITY.md "Request tracing"):
        # minted once per submit and shared by every split part, so an
        # oversize request renders as ONE span tree in the exported
        # Chrome trace; pid-scoped so multi-process traces merge
        trace = f"{os.getpid():x}-{next(self._trace_seq):06d}"
        eff_ms = (self.deadline_ms if deadline_ms is None
                  else float(deadline_ms))
        deadline = t_submit + eff_ms / 1e3 if eff_ms > 0 else 0.0
        nparts = -(-data.shape[0] // self.max_batch)
        items = []
        for part, lo in enumerate(
                range(0, data.shape[0], self.max_batch)):
            hi = lo + self.max_batch
            items.append(_WorkItem(
                data[lo:hi], [e[lo:hi] for e in extras], t_submit,
                trace=trace, part=part, nparts=nparts,
                deadline=deadline))
        items[0].future.trace = trace
        shed_depth = -1
        with self._cond:
            if self._draining:
                raise RuntimeError("server is stopping")
            if (self.queue_limit > 0 and
                    self._queued_rows + data.shape[0]
                    > self.queue_limit):
                # hard admission bound: reject, do NOT enqueue. The
                # shed verdict (503 on /healthz) holds until the
                # queue drains below half the limit for the
                # hysteresis window (_maybe_recover)
                shed_depth = self._queued_rows
                self._last_shed_t = t_submit
                flip = not self._shed_health
                self._shed_health = True
            else:
                for it in items:
                    self._queue.append(it)
                    self._queued_rows += it.n
                depth = self._queued_rows
                self._cond.notify_all()
        if shed_depth >= 0:
            retry_s = self._retry_after(shed_depth + data.shape[0])
            with self._lock:
                self._n_shed += 1
                self._n_shed_rows += data.shape[0]
            telemetry.inc("serve.shed_total")
            telemetry.inc("serve.shed_rows", data.shape[0])
            if flip:
                reason = (f"load shed: queue {shed_depth} rows + "
                          f"{data.shape[0]} > limit {self.queue_limit}")
                telemetry.get().health.set_unhealthy(
                    "serve_shed", reason)
                telemetry.event("serve", op="shed",
                                queue_depth=shed_depth,
                                limit=self.queue_limit)
            raise QueueFullError(
                f"serve queue full ({shed_depth} rows >= limit "
                f"{self.queue_limit}); retry in {retry_s:.2f}s",
                retry_after_s=retry_s, queue_depth=shed_depth)
        with self._lock:
            self._n_requests += 1
            self._n_rows += data.shape[0]
            for it in items:
                self._size_hist[it.n] = self._size_hist.get(it.n, 0) + 1
        for it in items:
            self._req_hist.observe(it.n)
        telemetry.inc("serve.requests")
        telemetry.inc("serve.rows", data.shape[0])
        telemetry.set_gauge("serve.queue_depth", depth)
        if len(items) == 1:
            return items[0].future
        return _JoinedFuture([it.future for it in items])

    # -- backpressure helpers ----------------------------------------------
    def _retry_after(self, backlog_rows: int) -> float:
        """Retry-After advice for a shed request: the time the current
        backlog takes to drain at the measured (EWMA) drain rate,
        clamped to [0.1s, 60s]. With no sample yet - a cold Server, or
        one just restarted (start() resets the EWMA) - the rate is
        unknown and the documented RETRY_AFTER_COLD_S default applies;
        a non-finite estimate falls back the same way rather than
        leaking garbage into the header."""
        with self._lock:
            rate = self._drain_rate
        if not (rate > 0.0) or not np.isfinite(rate):
            return RETRY_AFTER_COLD_S
        adv = backlog_rows / rate
        if not np.isfinite(adv):
            return RETRY_AFTER_COLD_S
        return min(60.0, max(0.1, adv))

    def _maybe_recover(self) -> None:
        """Shed->healthy hysteresis: clear the `serve_shed` health
        verdict once the queue has drained below HALF the limit AND
        no shed happened for shed_clear_ms - a single drained batch
        amid a storm must not flap /healthz."""
        now = time.monotonic()
        cleared = False
        with self._cond:
            if (self._shed_health
                    and self._queued_rows * 2 < max(self.queue_limit, 1)
                    and (now - self._last_shed_t)
                    >= self.shed_clear_ms / 1e3):
                self._shed_health = False
                cleared = True
        if cleared:
            telemetry.get().health.clear("serve_shed")
            telemetry.event("serve", op="shed_recovered",
                            limit=self.queue_limit)

    def _fail_expired(self, it: _WorkItem, now: float) -> None:
        """Resolve a deadline-expired item (called OUTSIDE _cond: the
        future Event set + registry counters need no queue state)."""
        with self._lock:
            self._n_expired += 1
        if self.canary_frac > 0:
            # judge evidence: attribute the expiry to the weight
            # generation that would have served this trace
            with self._swap_lock:
                can = self._canary
                if can is not None:
                    can.n_exp[_trace_side(it.trace, can.frac)] += 1
        telemetry.inc("serve.deadline_expired")
        waited_ms = (now - it.t_submit) * 1e3
        it.future._set_error(DeadlineExpiredError(
            f"request deadline expired after {waited_ms:.1f} ms in "
            "queue (dropped before dispatch)"))
        telemetry.event("serve", op="deadline_expired",
                        trace=it.trace, part=it.part, rows=it.n,
                        waited_ms=round(waited_ms, 3))

    # -- dispatchers -------------------------------------------------------
    def _collect(self) -> Optional[List[_WorkItem]]:
        """Admission policy: block for work, then coalesce queued
        items up to max_batch rows, waiting at most max_wait_ms past
        the FIRST item's submit time for the batch to fill
        (fill-or-timeout). Deadline-expired items are dropped here,
        before a bucket slot is spent on them. Returns None when
        stopping and drained; an empty list means "nothing live this
        round, loop again" (everything popped had expired)."""
        expired: List[_WorkItem] = []
        frac = 0.0
        if self.canary_frac > 0:
            # snapshot the active canary's traffic split BEFORE taking
            # _cond (no nested locks on the admission path); a canary
            # resolving mid-collect is benign - the batch's side tag
            # just routes to the incumbent at dispatch
            with self._swap_lock:
                if self._canary is not None:
                    frac = self._canary.frac
        items = self._collect_locked(expired, frac)
        if expired:
            now = time.monotonic()
            for it in expired:
                self._fail_expired(it, now)
        if items is not None:
            self._maybe_recover()
        return items

    def _collect_locked(
            self, expired: List[_WorkItem], frac: float = 0.0
    ) -> Optional[List[_WorkItem]]:
        with self._cond:
            first = None
            while first is None:
                if not self._queue:
                    if self._draining:
                        return None
                    if expired:
                        # resolve the drops promptly instead of
                        # blocking here with their futures pending
                        break
                    if (self._shed_health and self._queued_rows * 2
                            < max(self.queue_limit, 1)
                            and time.monotonic() - self._last_shed_t
                            >= self.shed_clear_ms / 1e3):
                        # storm over, traffic gone: surface so the
                        # caller can clear the shed 503 (recovery
                        # must not wait for the next request)
                        break
                    self._cond.wait(0.05)
                    continue
                # pop the next un-expired item; expired ones
                # accumulate for post-lock resolution
                now = time.monotonic()
                while self._queue:
                    it = self._queue.popleft()
                    self._queued_rows -= it.n
                    if it.deadline and now > it.deadline:
                        expired.append(it)
                        continue
                    first = it
                    break
            if first is None:
                telemetry.set_gauge("serve.queue_depth",
                                    self._queued_rows)
                return []
            # coalesce stamp: end of this item's queue phase (request
            # tracing's queue-vs-device cut)
            first.t_collect = time.monotonic()
            if frac > 0.0:
                first.side = _trace_side(first.trace, frac)
            items = [first]
            total = first.n
            deadline = first.t_submit + self.max_wait_ms / 1e3
            while total < self.max_batch:
                if self._queue:
                    head = self._queue[0]
                    if head.deadline and time.monotonic() > head.deadline:
                        self._queue.popleft()
                        self._queued_rows -= head.n
                        expired.append(head)
                        continue
                    if frac > 0.0:
                        head.side = _trace_side(head.trace, frac)
                        if head.side != first.side:
                            # a batch binds ONE weight generation:
                            # ship what we have, the head opens the
                            # other side's batch next round
                            break
                    if head.n <= self.max_batch - total:
                        it = self._queue.popleft()
                        self._queued_rows -= it.n
                        it.t_collect = time.monotonic()
                        items.append(it)
                        total += it.n
                        continue
                    break  # head doesn't fit: ship what we have
                wait = deadline - time.monotonic()
                if wait <= 0 or self._draining:
                    break
                self._cond.wait(min(wait, 0.05))
            telemetry.set_gauge("serve.queue_depth", self._queued_rows)
            return items

    def _run_batch(self, items: List[_WorkItem]) -> None:
        from jax.profiler import TraceAnnotation
        from cxxnet_tpu.parallel import distributed
        total = sum(it.n for it in items)
        bucket = next(b for b in self.buckets if b >= total)
        data = np.concatenate([it.data for it in items], axis=0)
        extras = [
            np.concatenate([it.extras[i] for it in items], axis=0)
            for i in range(len(self._extra_dims))]
        if bucket > total:
            pad = bucket - total
            data = np.concatenate(
                [data, np.zeros((pad,) + data.shape[1:], data.dtype)],
                axis=0)
            extras = [np.concatenate(
                [e, np.zeros((pad,) + e.shape[1:], e.dtype)], axis=0)
                for e in extras]
        tel = telemetry.get()
        fp = self._exec_fp.get(bucket, "")
        fl = None
        if tel.flight.enabled:
            # dispatch flight record: opened BEFORE staging (a hung
            # backend blocks inside device_put / the dispatch / the
            # readback below, leaving this entry in-flight with the
            # exact executable fingerprint + request trace on it)
            fl = tel.flight.start(
                "serve", fp=fp, bucket=bucket, nbytes=int(data.nbytes),
                trace=items[0].trace,
                fields={"rows": total, "requests": len(items)})
        t_dispatch = time.monotonic()
        try:
            # serve-side fault points (utils/fault.py, CXXNET_FAULT):
            # delay stalls the dispatch (deadline/backpressure tests),
            # error crashes it (the replica recovers, futures fail)
            fault.fault_point("serve_dispatch_delay")
            fault.fault_point("serve_dispatch_error")
            # hot-swap consistency: snapshot (fn, params) under the
            # swap lock so a batch binds ONE weight generation; the
            # dispatch itself runs outside the lock (GL015 - never
            # hold a lock across a jax boundary). An in-flight batch
            # that snapshotted before a swap finishes on old weights.
            # A canary batch (side=1) binds the staged candidate
            # params instead - same fn, same warmed executables, the
            # candidate is just a second argument binding.
            side = items[0].side
            routed = 0
            with self._swap_lock:
                fn = self._fn
                can = self._canary
                if can is not None and side == 1:
                    params = can.params
                    routed = len(items)
                else:
                    side = 0
                    params = self.trainer.state["params"]
                if can is not None:
                    can.n_req[side] += len(items)
                    if side == 0 and len(can.shadow) < 4:
                        # sample incumbent rows for the judge's shadow
                        # comparison (same rows through BOTH param
                        # sets, compared argmax/allclose)
                        can.shadow.append(
                            (items[0].data.copy(),
                             [e.copy() for e in items[0].extras]))
            if routed:
                with self._lock:
                    self._n_canary_req += routed
                telemetry.inc("serve.canary_requests", routed)
            with TraceAnnotation(spans.SERVE_BATCH, bucket=bucket):
                gdata, gextras = self.trainer.stage_infer_rows(data,
                                                               extras)
                out = fn(params, gdata, gextras)
                rows = distributed.fetch_local(out)
        except BaseException as e:
            # a FAILED dispatch must not read as a hung one: the
            # replica recovers and keeps serving, so close the flight
            # entry with the error instead of leaving it in-flight
            # forever (only a dispatch that never returns stays open)
            tel.flight.fail(fl, f"{type(e).__name__}: {e}")
            raise
        rows = rows.reshape(bucket, -1)
        t_done = time.monotonic()
        tel.flight.finish(fl)
        if fp:
            tel.executables.count_dispatch(fp, secs=t_done - t_dispatch)
        off = 0
        for it in items:
            it.future._set(rows[off:off + it.n])
            off += it.n
            self._lat.observe(t_done - it.t_submit)
            telemetry.observe("serve.latency_s", t_done - it.t_submit)
            # queue-vs-device breakdown per traced request part: the
            # cut is at DISPATCH, not at queue-pop - the fill-or-
            # timeout coalesce wait after the pop is host-side
            # admission latency and must not be billed to the device
            # (it would misdirect a p99 investigation toward the
            # accelerator); t_collect still rides the trace record so
            # the export can render the coalesce boundary
            queue_s = max(t_dispatch - it.t_submit, 0.0)
            device_s = max(t_done - t_dispatch, 0.0)
            self._qlat.observe(queue_s)
            self._dlat.observe(device_s)
            telemetry.observe("serve.queue_s", queue_s)
            telemetry.observe("serve.device_s", device_s)
            # one trace record per resolved part (no-op with no event
            # sink armed): the complete span set tools/trace_export.py
            # renders to Chrome trace-event JSON
            tel.event("trace", trace=it.trace, part=it.part,
                      parts=it.nparts, rows=it.n, bucket=bucket,
                      fp=fp, t_submit=round(it.t_submit, 6),
                      t_collect=round(it.t_collect, 6),
                      t_dispatch=round(t_dispatch, 6),
                      t_done=round(t_done, 6),
                      queue_ms=round(queue_s * 1e3, 3),
                      device_ms=round(device_s * 1e3, 3))
        with self._lock:
            self._n_batches += 1
            self._n_padding += bucket - total
            self._bucket_hits[bucket] += 1
            # drain-rate EWMA (rows/s across all replicas): Retry-After
            # advice for shed requests derives from it. Measured over
            # inter-completion gaps so replica overlap and admission
            # waits are priced in, not just device time.
            if self._last_drain_t > 0:
                gap = t_done - self._last_drain_t
                if gap > 1e-6:
                    inst = total / gap
                    self._drain_rate = (
                        inst if self._drain_rate <= 0
                        else 0.7 * self._drain_rate + 0.3 * inst)
            self._last_drain_t = t_done
        telemetry.inc("serve.batches")
        telemetry.inc("serve.padding_rows", bucket - total)
        # serving progress beacon: a wedged dispatch (hung backend)
        # stops marking and the watchdog dumps the stuck replica stack
        telemetry.beacon("serve.batch")

    def _replica_loop(self) -> None:
        while True:
            items = self._collect()
            if items is None:
                return
            if not items:
                # nothing live this round (expired drops resolved /
                # shed recovery surfaced) - nothing to dispatch
                continue
            try:
                self._run_batch(items)
            except BaseException as e:  # noqa: BLE001 - delivered via futures
                with self._lock:
                    self._n_errors += 1
                if self.canary_frac > 0:
                    # judge evidence: bill the failed dispatch to the
                    # weight generation the batch was bound to
                    with self._swap_lock:
                        can = self._canary
                        if can is not None:
                            can.n_err[items[0].side] += 1
                telemetry.inc("serve.errors")
                telemetry.stderr(
                    f"serve: dispatch failed: {type(e).__name__}: {e}\n",
                    event_kind="serve", op="error",
                    error=f"{type(e).__name__}: {e}")
                for it in items:
                    if not it.future.done():
                        it.future._set_error(e)

    # -- checkpoint hot-swap -----------------------------------------------
    def _swap_stat(self) -> Optional[Tuple[int, int]]:
        try:
            st = os.stat(self.swap_watch)
        except OSError:
            return None
        return (st.st_mtime_ns, st.st_size)

    def _swap_watch_loop(self) -> None:
        """Poll the published-checkpoint path every swap_poll_ms and
        swap on any (mtime, size) change. The stat is recorded before
        the attempt, so a rejected (torn) file is skipped ONCE and
        not re-validated in a hot loop; publishing a fixed file
        changes the stat again and retries."""
        poll_s = max(self.swap_poll_ms, 10.0) / 1e3
        while not self._swap_stop.wait(poll_s):
            cur = self._swap_stat()
            with self._swap_lock:
                if cur is None or cur == self._swap_seen:
                    continue
                self._swap_seen = cur
            try:
                self.swap_to(self.swap_watch)
            except BaseException as e:  # noqa: BLE001 - keep serving
                telemetry.stderr(
                    f"serve: swap attempt failed: "
                    f"{type(e).__name__}: {e}\n",
                    event_kind="swap", op="error",
                    error=f"{type(e).__name__}: {e}")

    def _params_mismatch(self, cur, new) -> Optional[str]:
        """A swap must be weight-compatible with the warmed
        executables: identical param tree (layer/param keys) and leaf
        shapes. Returns the first mismatch as a reason string."""
        for lk in cur:
            if lk not in new:
                return f"checkpoint missing layer {lk!r}"
            for pn in cur[lk]:
                if pn not in new[lk]:
                    return f"checkpoint missing param {lk}/{pn}"
                want = tuple(cur[lk][pn].shape)
                got = tuple(np.shape(new[lk][pn]))
                if want != got:
                    return (f"shape mismatch at {lk}/{pn}: "
                            f"checkpoint {got} vs serving {want}")
        extra = [f"{lk}/{pn}" for lk in new for pn in new[lk]
                 if lk not in cur or pn not in cur[lk]]
        if extra:
            return f"checkpoint has unknown params: {extra[:3]}"
        return None

    def swap_to(self, path: str) -> bool:
        """Zero-downtime weight swap from an atomic checksummed
        checkpoint (docs/SERVING.md "Hot-swap runbook"): validate the
        crc32 trailer, load, verify the param tree matches, stage the
        new params to device (all outside any lock), then switch
        between batches under _swap_lock. In-flight batches bound the
        old params at dispatch and finish on the old weights; no
        request is dropped. Returns True on an applied swap; a
        torn/corrupt/mismatched checkpoint emits `swap` op=rejected
        and the old weights keep serving (False)."""
        from cxxnet_tpu.nnet import checkpoint
        t0 = time.perf_counter()
        blob = None
        reason = checkpoint.validate_file(path)
        if reason is None:
            try:
                with open(path, "rb") as fi:
                    blob = checkpoint.load_model(fi)
            except (OSError, ValueError) as e:
                reason = f"{type(e).__name__}: {e}"
        if reason is None:
            reason = self._params_mismatch(
                self.trainer.state["params"], blob["params"])
        if reason is not None:
            with self._lock:
                self._n_swap_rejected += 1
            telemetry.inc("serve.swap_rejected")
            telemetry.stderr(
                f"serve: checkpoint swap rejected ({path}): "
                f"{reason}\n",
                event_kind="swap", op="rejected", path=path,
                reason=reason)
            return False
        if self.canary_frac > 0:
            calibrated = (self.trainer._fold_stats is not None
                          or self.trainer._quant_stats is not None)
            if calibrated:
                # frozen fold/quant calibration means applying this
                # checkpoint rewarms new executables - incumbent and
                # candidate could not share warmed buckets, so the
                # traffic split is impossible. Fall through to the
                # direct (non-canaried) swap and say so.
                telemetry.stderr(
                    f"serve: canary bypassed for {path}: calibrated "
                    f"passes force a rewarm, applying directly\n",
                    event_kind="swap", op="canary_bypassed", path=path)
            else:
                with self._swap_lock:
                    busy = self._canary is not None
                if busy:
                    with self._lock:
                        self._n_swap_rejected += 1
                    telemetry.inc("serve.swap_rejected")
                    telemetry.stderr(
                        f"serve: checkpoint swap rejected ({path}): "
                        f"canary already in progress\n",
                        event_kind="swap", op="rejected", path=path,
                        reason="canary already in progress")
                    return False
                staged = self._stage_params(blob)
                return self._start_canary(
                    staged, path,
                    int(blob.get("epoch", self.trainer.epoch)))
        # stage the new weights at the stored sharded layout BEFORE
        # taking the swap lock - device_put is a dispatch boundary and
        # must never run under a lock (GL015 / the runtime lock audit)
        staged = self._stage_params(blob)
        with self._swap_lock:
            self.trainer.state["params"] = staged
            self.trainer.epoch = int(blob.get("epoch",
                                              self.trainer.epoch))
            old_fold = self.trainer._fold_epoch
            # frozen fold/quant calibration described the OLD weights:
            # retire it (epoch bump + stale-executable eviction, the
            # PR 10/12 mechanism). On the no-passes path this is a
            # no-op and params stay plain jit ARGUMENTS - the swap is
            # a zero-recompile, bitwise switch.
            self.trainer._retire_calibration_state()
            rewarmed = self.trainer._fold_epoch != old_fold
            if rewarmed:
                self._fn = self.trainer._infer_fn(self.node)
        if rewarmed:
            # new fold epoch = new executables: re-warm every bucket
            # so steady state stays recompile-free and /executables
            # lists the new fingerprints (epoch is part of them)
            self.warmup()
        with self._lock:
            self._n_swaps += 1
        telemetry.inc("serve.swaps")
        telemetry.event("swap", op="applied", path=path,
                        epoch=self.trainer.epoch, rewarmed=rewarmed,
                        secs=round(time.perf_counter() - t0, 4))
        return True

    def _stage_params(self, blob: Dict[str, Any]) -> Dict[str, Any]:
        """Stage a validated checkpoint's params to device at the
        stored sharded layout (the same put_global_full landing
        set_weight uses). Runs OUTSIDE any lock - device_put is a
        dispatch boundary and must never run under a lock (GL015 /
        the runtime lock audit)."""
        from cxxnet_tpu.parallel import distributed
        cur = self.trainer.state["params"]
        pstore = self.trainer._params_store_shard
        return {
            lk: {pn: distributed.put_global_full(
                np.ascontiguousarray(blob["params"][lk][pn]),
                pstore[lk][pn])
                for pn in cur[lk]}
            for lk in cur}

    # -- canaried rollout --------------------------------------------------
    def _start_canary(self, staged, path: str, epoch: int) -> bool:
        """Install a validated, device-staged candidate as the canary
        (docs/SERVING.md "Canary runbook"): a swap_canary_frac slice
        of traffic (deterministic on the trace id, so oversize-split
        parts stay coherent) binds the candidate params at dispatch
        while the rest keeps the incumbent - through the SAME warmed
        bucket executables, zero recompiles. A judge thread scores
        the candidate over swap_canary_window seconds and either
        promotes it (swap op=promoted) or rolls it back
        (op=rolled_back, incumbent bitwise-untouched)."""
        from cxxnet_tpu.nnet import checkpoint
        can = _Canary(staged, path, epoch, self.canary_frac)
        can.provenance = checkpoint.read_publish_meta(path) or {}
        with self._swap_lock:
            if self._canary is not None:
                # raced with another swap_to: first canary wins, this
                # candidate is dropped (the watcher already recorded
                # the file's stat, so it is quarantined like a reject)
                return False
            self._canary = can
        # one judge per canary: the previous judge (if any) exited
        # when its canary resolved, so join is immediate
        if self._canary_thread is not None:
            self._canary_thread.join(timeout=15.0)
        self._canary_stop.clear()
        self._canary_thread = threading.Thread(
            target=self._canary_judge_loop, args=(can,),
            name="serve-canary-judge", daemon=True)
        self._canary_thread.start()
        telemetry.event(
            "swap", op="canary_started", path=path, epoch=epoch,
            frac=can.frac, window_s=self.canary_window,
            src=str(can.provenance.get("src", "")))
        return True

    def _canary_judge_loop(self, can: "_Canary") -> None:
        """Judge thread: periodically score the canary against the
        incumbent until the window closes, then promote or roll back.
        ANY judge failure rolls back - a broken judge must fail safe
        to the incumbent (the canary_judge_error fault point proves
        it)."""
        try:
            fault.fault_point("canary_judge_error")
            deadline = can.t0 + self.canary_window
            while True:
                wait_s = min(0.05, max(0.0, deadline - time.monotonic()))
                if self._canary_stop.wait(wait_s):
                    # server stopping before the window closed: the
                    # candidate was never promoted, drop it
                    self._canary_rollback(
                        can, "server stopping before verdict")
                    return
                verdict = self._canary_check(can)
                if verdict is not None:
                    self._canary_rollback(can, verdict)
                    return
                if time.monotonic() >= deadline:
                    break
            verdict = self._canary_check(can, final=True)
            if verdict is not None:
                self._canary_rollback(can, verdict)
            else:
                self._canary_promote(can)
        except BaseException as e:  # noqa: BLE001 - fail safe to incumbent
            self._canary_rollback(
                can, f"judge error: {type(e).__name__}: {e}")

    def _canary_check(self, can: "_Canary",
                      final: bool = False) -> Optional[str]:
        """One judge round. Returns a rollback reason, or None when
        the canary still looks healthy. Evidence: (a) shadow pairs -
        the same sampled rows dispatched through BOTH param sets and
        compared (candidate non-finite where the incumbent is finite,
        or argmax agreement below 0.5, is a fail); (b) error/deadline
        rates - candidate
        worse than incumbent with at least one bad event is a fail.
        On the final round with zero organic evidence, a synthetic
        zeros batch checks the candidate at least produces finite
        output."""
        with self._swap_lock:
            if self._canary is not can:
                return None
            fn = self._fn
            inc_params = self.trainer.state["params"]
            cand_params = can.params
            sample = can.shadow.pop() if can.shadow else None
            shadow_done = can.shadow_done
            n_req = list(can.n_req)
            bad = [can.n_err[0] + can.n_exp[0],
                   can.n_err[1] + can.n_exp[1]]
        if sample is not None:
            reason = self._shadow_divergence(
                fn, inc_params, cand_params, sample[0], sample[1])
            with self._swap_lock:
                can.shadow_done += 1
            if reason is not None:
                return reason
        elif final and shadow_done == 0:
            # no organic traffic reached the incumbent during the
            # window: synthesize a zeros batch so the candidate is at
            # least proven finite before promotion (argmax agreement
            # on synthetic rows is meaningless, so skip it)
            c, y, x = self._input_dims
            data = np.zeros((1, c, y, x), np.float32)
            extras = [np.zeros((1, d), np.float32)
                      for d in self._extra_dims]
            reason = self._shadow_divergence(
                fn, inc_params, cand_params, data, extras,
                check_agree=False)
            if reason is not None:
                return reason
        if bad[1] > 0:
            rate = [bad[s] / max(n_req[s], 1) for s in (0, 1)]
            if rate[1] > rate[0]:
                return (f"candidate error/deadline rate "
                        f"{rate[1]:.4f} > incumbent {rate[0]:.4f} "
                        f"({bad[1]}/{n_req[1]} vs "
                        f"{bad[0]}/{n_req[0]})")
        return None

    def _shadow_divergence(self, fn, inc_params, cand_params, data,
                           extras, check_agree: bool = True
                           ) -> Optional[str]:
        """Dispatch the same rows through incumbent and candidate
        params (same warmed bucket executables - the rows are padded
        to a covering bucket, so the executable cache stays flat) and
        compare. Returns a rollback reason or None."""
        from cxxnet_tpu.parallel import distributed
        n = int(data.shape[0])
        bucket = next((b for b in self.buckets if b >= n),
                      self.buckets[-1])
        if n > bucket:
            data, extras = data[:bucket], [e[:bucket] for e in extras]
            n = bucket
        if bucket > n:
            pad = bucket - n
            data = np.concatenate(
                [data, np.zeros((pad,) + data.shape[1:], data.dtype)],
                axis=0)
            extras = [np.concatenate(
                [e, np.zeros((pad,) + e.shape[1:], e.dtype)], axis=0)
                for e in extras]
        gdata, gextras = self.trainer.stage_infer_rows(data, extras)
        out_inc = distributed.fetch_local(
            fn(inc_params, gdata, gextras)).reshape(bucket, -1)[:n]
        out_cand = distributed.fetch_local(
            fn(cand_params, gdata, gextras)).reshape(bucket, -1)[:n]
        if fault.fault_point("canary_divergence") == "corrupt":
            # sabotage: poison the candidate's answers so the
            # divergence check trips (rollback-path drills)
            out_cand = out_cand + np.nan
        # the judge scores RELATIVE health: a candidate is only
        # penalized for non-finite outputs at positions where the
        # incumbent was finite (an incumbent that already emits NaN -
        # e.g. a diverged trainer - must not veto its own checkpoint)
        cand_bad = ~np.isfinite(out_cand)
        if bool(np.any(cand_bad & np.isfinite(out_inc))):
            return ("candidate produced non-finite outputs where "
                    "the incumbent was finite")
        agree = None
        if check_agree:
            agree = float(np.mean(
                predictions_from_rows(out_cand)
                == predictions_from_rows(out_inc)))
        telemetry.event(
            "swap", op="canary_shadow", rows=n,
            agree=(None if agree is None else round(agree, 4)),
            allclose=bool(np.allclose(out_cand, out_inc,
                                      rtol=1e-3, atol=1e-5)))
        if agree is not None and agree < 0.5:
            return (f"candidate argmax agreement {agree:.2f} < 0.5 "
                    f"on {n} shadow rows")
        return None

    def _canary_promote(self, can: "_Canary") -> None:
        """The window closed clean: the candidate becomes the
        incumbent between batches (same flip as a direct swap -
        in-flight batches bound their params at dispatch)."""
        with self._swap_lock:
            if self._canary is not can:
                return
            self.trainer.state["params"] = can.params
            self.trainer.epoch = can.epoch
            self._canary = None
        with self._lock:
            self._n_swaps += 1
            self._n_canary_promoted += 1
        telemetry.inc("serve.swaps")
        telemetry.inc("serve.canary_promoted")
        telemetry.event(
            "swap", op="promoted", path=can.path, epoch=can.epoch,
            canary_requests=can.n_req[1], shadow_pairs=can.shadow_done,
            window_s=self.canary_window,
            src=str(can.provenance.get("src", "")))

    def _canary_rollback(self, can: "_Canary", reason: str) -> None:
        """Drop the candidate; the incumbent was never touched, so
        rollback is just detaching the canary slot. The watcher
        recorded the file's stat before the attempt, so the bad
        checkpoint is quarantined (skipped once) exactly like a torn
        file - republishing retries."""
        with self._swap_lock:
            if self._canary is not can:
                return
            self._canary = None
        with self._lock:
            self._n_canary_rolled_back += 1
        telemetry.inc("serve.canary_rolled_back")
        telemetry.stderr(
            f"serve: canary rolled back ({can.path}): {reason}\n",
            event_kind="swap", op="rolled_back", path=can.path,
            reason=reason, canary_requests=can.n_req[1],
            shadow_pairs=can.shadow_done,
            src=str(can.provenance.get("src", "")))

    # -- HTTP request path -------------------------------------------------
    def handle_predict(self, body: bytes):
        """The /predict POST backend (telemetry/http.py routes here
        when this Server attached with http_port/serve_port): JSON
        {"data": rows, "extras": [...], "deadline_ms": N, "raw": bool}
        in; {"predictions": [...], "rows": n, "trace": id} out. data
        is (n,c,y,x) nested, flat (n, c*y*x), or one instance. Maps
        QueueFullError -> 429 + Retry-After, deadline expiry/timeout
        -> 504, validation -> 400, dispatch failure -> 500. Returns
        (status, extra_headers, body_bytes)."""
        import json

        def err(code: int, msg: str, **extra):
            payload = {"error": msg}
            payload.update(extra)
            return code, {}, json.dumps(payload).encode()

        t0 = time.monotonic()
        try:
            req = json.loads(body.decode("utf-8")) if body else {}
        except (ValueError, UnicodeDecodeError):
            return err(400, "request body must be a JSON object")
        if not isinstance(req, dict) or "data" not in req:
            return err(400, 'request JSON needs a "data" field '
                            '(rows to predict)')
        try:
            data = np.asarray(req["data"], dtype=np.float32)
        except (ValueError, TypeError):
            return err(400, '"data" must be a numeric array')
        c, y, x = self._input_dims
        width = c * y * x
        if data.ndim == 1 and data.size == width:
            data = data.reshape(1, c, y, x)
        elif data.ndim == 2 and data.shape[-1] == width:
            data = data.reshape(-1, c, y, x)
        deadline_ms = req.get("deadline_ms")
        try:
            extras = [np.asarray(e, dtype=np.float32)
                      for e in req.get("extras", ())]
            fut = self.submit(data, extras, deadline_ms=deadline_ms)
        except QueueFullError as e:
            # ceil seconds for the header (int per RFC 9110), exact
            # advice in the body; [1, 60] keeps a confused client
            # from either hammering or giving up
            secs = max(1, min(60, int(-(-e.retry_after_s // 1))))
            return (429, {"Retry-After": str(secs)},
                    json.dumps({
                        "error": "queue full (load shed)",
                        "retry_after_s": round(e.retry_after_s, 3),
                        "queue_depth": e.queue_depth}).encode())
        except (ValueError, TypeError) as e:
            return err(400, str(e))
        except RuntimeError as e:
            return err(503, str(e))
        eff_ms = (self.deadline_ms if deadline_ms is None
                  else float(deadline_ms))
        timeout = eff_ms / 1e3 + 5.0 if eff_ms > 0 else 300.0
        try:
            rows = fut.result(timeout=timeout)
        except DeadlineExpiredError as e:
            return err(504, str(e), trace=fut.trace)
        except TimeoutError:
            return err(504, "timed out waiting for the result",
                       trace=fut.trace)
        except BaseException as e:  # noqa: BLE001 - dispatch error -> 500
            return err(500, f"{type(e).__name__}: {e}",
                       trace=fut.trace)
        rows = np.asarray(rows)
        out = {
            "predictions": [float(v)
                            for v in predictions_from_rows(rows)],
            "rows": int(rows.shape[0]),
            "trace": fut.trace,
            "latency_ms": round((time.monotonic() - t0) * 1e3, 3),
        }
        if req.get("raw"):
            # raw final-node rows: what the bitwise swap proofs and
            # the smoke's cold-restart comparison consume
            out["outputs"] = rows.reshape(rows.shape[0], -1).tolist()
        return 200, {}, json.dumps(out).encode()

    # -- reporting ---------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Product-surface summary: request/row/batch/padding counts,
        per-bucket dispatch counts, and latency p50/p99 (ms) from the
        registry histogram."""
        with self._lock:
            out: Dict[str, Any] = {
                "requests": self._n_requests,
                "rows": self._n_rows,
                "batches": self._n_batches,
                "padding_rows": self._n_padding,
                "errors": self._n_errors,
                "shed_requests": self._n_shed,
                "shed_rows": self._n_shed_rows,
                "deadline_expired": self._n_expired,
                "swaps": self._n_swaps,
                "swap_rejected": self._n_swap_rejected,
                "canary_requests": self._n_canary_req,
                "canary_promoted": self._n_canary_promoted,
                "canary_rolled_back": self._n_canary_rolled_back,
                "drain_rows_per_s": round(self._drain_rate, 2),
                "buckets": {b: n for b, n in self._bucket_hits.items()},
                "request_sizes": dict(self._size_hist),
            }
        with self._swap_lock:
            out["canary_active"] = self._canary is not None
        if self.metrics_server is not None:
            ingress = getattr(self.metrics_server, "ingress_stats",
                              None)
            if ingress is not None:
                out.update(ingress())
        out["queue_limit"] = self.queue_limit
        out["warmup_s"] = round(self.warmup_s, 4)
        for hist, stem in ((self._lat, "latency"),
                           (self._qlat, "queue"),
                           (self._dlat, "device")):
            for q in (50, 99):
                v = hist.percentile(q)
                out[f"{stem}_p{q}_ms"] = (round(v * 1e3, 3)
                                          if v == v else None)
        return out
