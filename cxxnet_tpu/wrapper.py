"""numpy-facing wrapper API.

API parity with wrapper/cxxnet.py:64-312 (`Net`, `DataIter`, `train()`):
the reference reaches the C++ core over a ctypes C ABI
(wrapper/cxxnet_wrapper.cpp); here the same surface binds directly to the
in-process trainer - same call signatures and semantics, numpy in/out.
"""

from __future__ import annotations

from typing import Optional, Union

import numpy as np

from cxxnet_tpu.io import create_iterator
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config_string


class DataIter:
    """Config-built data iterator (CXNIOCreateFromConfig semantics)."""

    def __init__(self, cfg: str):
        self._it = create_iterator(parse_config_string(cfg))
        self._it.init()
        self.head = True
        self.tail = False

    def next(self) -> bool:
        ret = self._it.next()
        self.head = False
        self.tail = not ret
        return ret

    def before_first(self) -> None:
        self._it.before_first()
        self.head = True
        self.tail = False

    def check_valid(self) -> None:
        if self.head:
            raise RuntimeError(
                "iterator at head state, call next to get to valid state")
        if self.tail:
            raise RuntimeError("iterator reaches end")

    def get_data(self) -> np.ndarray:
        self.check_valid()
        return self._it.value().data

    def get_label(self) -> np.ndarray:
        self.check_valid()
        return self._it.value().label

    @property
    def value(self) -> DataBatch:
        self.check_valid()
        return self._it.value()


def _batch_from_numpy(data: np.ndarray,
                      label: Optional[np.ndarray]) -> DataBatch:
    if data.ndim != 4:
        raise ValueError(
            "need 4 dimensional tensor (batch, channel, height, width)")
    if label is None:
        label = np.zeros((data.shape[0], 1), dtype=np.float32)
    label = np.asarray(label, dtype=np.float32)
    if label.ndim == 1:
        label = label.reshape(-1, 1)
    if label.shape[0] != data.shape[0]:
        raise ValueError("data size mismatch")
    return DataBatch(data=np.asarray(data, dtype=np.float32), label=label)


class Net:
    """Neural net object (CXNNetCreate semantics)."""

    def __init__(self, dev: str = "cpu", cfg: str = ""):
        self._net = NetTrainer(dev=dev, cfg=cfg)

    def set_param(self, name, value) -> None:
        self._net.set_param(str(name), str(value))

    def init_model(self) -> None:
        self._net.init_model()

    def load_model(self, fname: str) -> None:
        with open(fname, "rb") as f:
            self._net.load_model(f)

    def save_model(self, fname: str) -> None:
        with open(fname, "wb") as f:
            self._net.save_model(f)

    def start_round(self, round_counter: int) -> None:
        self._net.start_round(round_counter)

    def update(self, data: Union[DataIter, np.ndarray],
               label: Optional[np.ndarray] = None) -> None:
        if isinstance(data, DataIter):
            data.check_valid()
            self._net.update(data.value)
        elif isinstance(data, np.ndarray):
            if label is None:
                raise ValueError("need label to use update")
            self._net.update(_batch_from_numpy(data, label))
        else:
            raise TypeError(f"update does not support type {type(data)}")

    def evaluate(self, data: DataIter, name: str) -> str:
        if not isinstance(data, DataIter):
            raise TypeError("evaluate expects a DataIter")
        return self._net.evaluate(data._it, name)

    def predict(self, data: Union[DataIter, np.ndarray]) -> np.ndarray:
        if isinstance(data, DataIter):
            data.check_valid()
            return self._net.predict(data.value)
        return self._net.predict(_batch_from_numpy(data, None))

    def predict_dist(self,
                     data: Union[DataIter, np.ndarray]) -> np.ndarray:
        if isinstance(data, DataIter):
            data.check_valid()
            return self._net.predict_dist(data.value)
        return self._net.predict_dist(_batch_from_numpy(data, None))

    def extract(self, data: Union[DataIter, np.ndarray],
                node_name: str) -> np.ndarray:
        if isinstance(data, DataIter):
            data.check_valid()
            return self._net.extract_feature(data.value, node_name)
        return self._net.extract_feature(_batch_from_numpy(data, None),
                                         node_name)

    def calibrate_passes(self, data: np.ndarray,
                         label: Optional[np.ndarray] = None) -> bool:
        """Capture fold_conv_bn calibration statistics from one numpy
        batch (graph_passes - docs/GRAPH_PASSES.md). predict/extract
        self-calibrate on their first batch; call this before
        serve_start so the serving executables compile FOLDED (an
        uncalibrated Server serves the unfolded graph and warns).
        Returns True when stats were captured."""
        return self._net.calibrate_graph_passes(
            _batch_from_numpy(np.asarray(data, dtype=np.float32),
                              label))

    # -- serving (docs/SERVING.md) -------------------------------------
    def serve_start(self, max_batch: int = 0,
                    max_wait_ms: Optional[float] = None,
                    replicas: Optional[int] = None,
                    http_port: Optional[int] = None,
                    queue_limit: Optional[int] = None,
                    deadline_ms: Optional[float] = None,
                    swap_watch: Optional[str] = None) -> None:
        """Start the continuous-batching server over this net's
        inference executable: bucket executables compiled + warmed
        here, dispatcher replicas spawned. Unset arguments fall back
        to the net's serve_* config keys (serve_max_batch /
        serve_max_wait_ms / serve_replicas / serve_port /
        serve_queue_limit / serve_deadline_ms / swap_watch -
        docs/SERVING.md). http_port attaches the /predict HTTP
        request path (0 = ephemeral; read the bound port off
        `net._server.metrics_server.port`); queue_limit arms load
        shedding (QueueFullError / HTTP 429); swap_watch arms the
        zero-downtime checkpoint hot-swap poller."""
        if getattr(self, "_server", None) is not None:
            raise RuntimeError("server already started")
        from cxxnet_tpu.serve import Server
        srv = Server(self._net, max_batch=max_batch,
                     max_wait_ms=max_wait_ms, replicas=replicas,
                     http_port=http_port, queue_limit=queue_limit,
                     deadline_ms=deadline_ms, swap_watch=swap_watch)
        # attach only once running: a warmup failure (compile error,
        # OOM) must leave serve_start retryable, not wedge the Net
        # behind "server already started"
        srv.warmup()
        srv.start()
        self._server = srv

    def serve_submit(self, data: np.ndarray,
                     block: bool = True):
        """Submit numpy rows ((n, c, y, x) or one (c, y, x) instance)
        to the running server. block=True (default) returns the raw
        final-node rows, (n, width) - the predict_dist surface;
        block=False returns a future whose result() yields them
        (concurrent submitters are what continuous batching
        coalesces). cxxnet_tpu.serve.predictions_from_rows converts
        rows to predict()-style labels."""
        if getattr(self, "_server", None) is None:
            raise RuntimeError("call serve_start first")
        fut = self._server.submit(np.asarray(data, dtype=np.float32))
        return fut.result() if block else fut

    def serve_swap(self, path: str) -> bool:
        """Hot-swap the running server's weights from an on-disk
        checkpoint (docs/SERVING.md "Hot-swap runbook"): validated,
        staged and switched between batches with zero dropped
        requests. Returns False (and keeps the old weights serving)
        when the file is torn/corrupt/shape-mismatched."""
        if getattr(self, "_server", None) is None:
            raise RuntimeError("call serve_start first")
        return self._server.swap_to(path)

    def serve_stop(self) -> dict:
        """Drain + stop the server; returns its stats() summary
        (request/batch/padding counts, latency p50/p99 ms)."""
        if getattr(self, "_server", None) is None:
            raise RuntimeError("no server running")
        srv, self._server = self._server, None
        return srv.stop()

    def serve_drain(self) -> dict:
        """Graceful shutdown (docs/SERVING.md "Connection limits &
        drain"): reject new submissions, flip /healthz to draining,
        resolve every queued request, then stop. Returns stats()."""
        if getattr(self, "_server", None) is None:
            raise RuntimeError("no server running")
        srv, self._server = self._server, None
        return srv.drain()

    def has_layer(self, layer_name: str) -> bool:
        return layer_name in self._net.net_cfg.layer_name_map

    def get_weight(self, layer_name: str, tag: str) -> np.ndarray:
        w, _ = self._net.get_weight(layer_name, tag)
        return w

    def set_weight(self, weight: np.ndarray, layer_name: str,
                   tag: str) -> None:
        self._net.set_weight(np.asarray(weight, dtype=np.float32),
                             layer_name, tag)


# train()'s device-resident cutoff: datasets under this many bytes are
# staged once (module-level so tests can force either path)
_STAGE_BYTES_LIMIT = 256 * 2 ** 20


def train(cfg: str, data, label, num_round: int,
          param, eval_data=None, batch_size: int = 128,
          dev: str = "cpu") -> Net:
    """Convenience trainer over numpy arrays (cxxnet.py:301-312).

    eval_data: optional (data, label) pair; CLASSIFICATION error is
    computed after every round (batch_size chunks) and printed to
    stderr like the CLI round loop - regression nets should evaluate
    manually. The final partial batch of each round trains too (padded
    internally)."""
    import jax
    from cxxnet_tpu import telemetry
    net = Net(dev=dev, cfg=cfg)
    net.set_param("batch_size", batch_size)
    for k, v in (param.items() if isinstance(param, dict) else param):
        net.set_param(k, v)
    net.init_model()
    n = data.shape[0]
    # small datasets train device-resident: stage every batch's device
    # buffers ONCE (trainer.stage_batch, trajectory bit-identical to
    # streaming - tests/test_trainer.py) instead of re-padding/casting/
    # staging the same slices every round. Gated by a memory bound so a
    # large numpy dataset streams exactly as before instead of pinning
    # itself into device memory.
    staged = None
    # bound the STAGED footprint (f32, padded to full batches), not the
    # source nbytes: a uint8 source stages at 4x its own size
    c, hh, ww = net._net.net_cfg.input_shape
    n_batches = (n + batch_size - 1) // batch_size
    staged_bytes = n_batches * batch_size * c * hh * ww * 4
    if staged_bytes < _STAGE_BYTES_LIMIT:
        try:
            staged = [net._net.stage_batch(_batch_from_numpy(
                data[i:i + batch_size], label[i:i + batch_size]))
                for i in range(0, n, batch_size)]
            if net._net.steps_per_dispatch > 1:
                # fused dispatch (docs/PERFORMANCE.md): stack the
                # device-resident batches into K-step chunks ONCE;
                # each round then costs one dispatch per chunk
                # (update() routes StagedChunk to update_chunk)
                k = net._net.steps_per_dispatch
                staged = [net._net.stage_chunk(staged[i:i + k])
                          for i in range(0, len(staged), k)]
        except jax.errors.JaxRuntimeError as e:
            # the one failure staging may absorb: the dataset passed
            # the host-side bound but does not fit device memory next
            # to the model - say so and stream. Anything else (a bad
            # shape, a dead backend) is a real error and propagates
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            telemetry.stderr(
                f"train: staging {staged_bytes} bytes on the device "
                "ran out of memory; streaming the dataset instead\n",
                event_kind="config", type="stage_oom",
                bytes=staged_bytes)
            staged = None
    pf = None
    if staged is None:
        # large datasets stream - through the H2D staging prefetcher
        # (io/prefetch.py): batch k+1 padded/cast/device_put on a
        # worker thread while step k runs, same batches in the same
        # order as the direct slice loop
        class _Slices:
            def before_first(self):
                self.i = -batch_size

            def next(self):
                self.i += batch_size
                return self.i < n

            def value(self):
                i = self.i
                return _batch_from_numpy(data[i:i + batch_size],
                                         label[i:i + batch_size])

        # chunk=K assembles fused-dispatch chunks on the worker when
        # steps_per_dispatch is configured (1 = unchanged streaming)
        pf = net._net.prefetch(_Slices(), depth=1,
                               chunk=net._net.steps_per_dispatch)
    try:
        for r in range(num_round):
            net.start_round(r)
            if staged is not None:
                for s in staged:
                    net._net.update(s)
            else:
                pf.before_first()
                while pf.next():
                    net._net.update(pf.value())
            if eval_data is not None:
                ed, el = eval_data
                preds = [net.predict(ed[i:i + batch_size])
                         for i in range(0, ed.shape[0], batch_size)]
                pred = np.concatenate(preds)
                err = float((pred != np.asarray(el).reshape(-1)).mean())
                telemetry.stderr(f"[{r}]\teval-error:{err:g}\n",
                                 event_kind="eval", round=r,
                                 values={"eval-error": err})
    finally:
        if pf is not None:
            pf.close()  # a mid-round error must not leak the worker
    return net
