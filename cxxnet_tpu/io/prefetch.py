"""Double-buffered host->device staging (the ThreadBuffer at the H2D edge).

The reference hides disk/decode latency behind compute with a generic
two-semaphore double buffer (utils/thread_buffer.h:22-202) and a
batch-level ThreadBufferIterator (iter_batch_proc-inl.hpp:136-224).
On TPU the analogous stall is not the disk but the HOST->DEVICE edge:
the per-step pad + cast + device_put of batch k+1 serializes after the
(asynchronously dispatched) step k unless it runs on its own thread.

StagedPrefetcher wraps any DataIter and runs the trainer's FULL
staging pipeline (trainer.stage_batch: pad, host cast, device_put
under the step's in_shardings) on a worker thread, `depth` batches
ahead. value() yields StagedBatch objects, which trainer.update()
consumes with zero per-step host work - so staging of batch k+1
overlaps both the host dispatch and the device compute of batch k.
Trajectory-identical to streaming the DataBatches directly (staging is
the same code either way; RNG folds on the step counter, not on wall
time).
"""

from __future__ import annotations

import queue
import sys
import threading
import time

from cxxnet_tpu import telemetry
from cxxnet_tpu.io.thread_util import drain_and_join
from cxxnet_tpu.telemetry import spans

_END = object()


class StagedPrefetcher:
    """DataIter-protocol wrapper: before_first()/next()/value(), where
    value() returns the staged (device-resident) batch. stage_fn is
    typically trainer.stage_batch; source is any DataIter yielding
    DataBatches. Up to depth+1 staged batches are resident at once
    (depth queued plus the one the worker holds while the queue is
    full), each pinning its device buffers in HBM until consumed -
    budget HBM headroom for depth+1, not depth.

    Fused dispatch (steps_per_dispatch=K, docs/PERFORMANCE.md):
    chunk=K makes the worker assemble K staged batches into one
    StagedChunk via chunk_fn (trainer.stage_chunk) per queue item -
    the last item of a pass may be a SHORT chunk (the round-boundary
    flush). HBM budget then scales to K*(depth+1) batches resident."""

    def __init__(self, stage_fn, source, depth: int = 1,
                 chunk: int = 1, chunk_fn=None):
        self.stage_fn = stage_fn
        self.source = source
        self.depth = max(1, int(depth))
        self.chunk = max(1, int(chunk))
        if self.chunk > 1 and chunk_fn is None:
            raise ValueError("chunk > 1 requires chunk_fn")
        self.chunk_fn = chunk_fn
        self._q = None
        self._thread = None
        self._stop = threading.Event()
        self._cur = None
        self._exhausted = False
        self._closed = False
        self._pending_error = None
        # telemetry armed? cached per pass (before_first) - the
        # disabled next() path must cost one attribute check, not a
        # singleton lookup per batch
        self._tel = False

    # -- DataIter protocol -------------------------------------------------
    def before_first(self) -> None:
        self._shutdown()
        # restarting the pass abandons any undelivered worker error
        # (the rewind re-reads the same data; a persistent fault will
        # re-raise on this pass)
        self._pending_error = None
        self.source.before_first()
        self._tel = telemetry.enabled()
        self._q = queue.Queue(maxsize=self.depth)
        self._stop.clear()
        self._exhausted = False
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="staged-prefetch", daemon=True)
        self._thread.start()

    # graftlint: hot-path (per-batch consumer path: no host syncs here)
    def next(self) -> bool:
        if self._closed:
            # close() is terminal for the current pass: a stray next()
            # from a consumer's cleanup path must not silently rewind
            # the source and resurrect a worker nothing will close
            return False
        if self._q is None:
            self.before_first()
        if self._exhausted:
            # the worker put ONE _END and exited; a blocking get here
            # would hang forever
            return False
        t0 = time.perf_counter() if self._tel else 0.0
        stalled = False
        try:
            # common path: the worker is ahead and the queue is
            # non-empty - ONE non-blocking get, zero timeout wakeups
            # (the old 0.2 s get-loop woke 5x/sec for the whole stall
            # on data-bound runs)
            item = self._q.get_nowait()
        except queue.Empty:
            # the staging worker is behind the consumer: block on the
            # queue. The first get keeps the historic 0.2 s bar so the
            # io.prefetch.stalls metric retains its meaning (a wait
            # the consumer actually felt, not an instantaneously-empty
            # queue); later gets stretch to 2 s - the timeout then
            # exists ONLY as the dead-worker sweep (a healthy worker
            # always delivers a batch, _END, or its exception)
            # the wait the step felt, on a running profiler trace's own
            # clock (telemetry/spans.py); only this branch pays for it
            from jax.profiler import TraceAnnotation
            with TraceAnnotation(spans.IO_WAIT):
                timeout = 0.2
                while True:
                    try:
                        item = self._q.get(timeout=timeout)
                        break
                    except queue.Empty:
                        stalled = True
                        timeout = 2.0
                        if (self._thread is not None
                                and self._thread.is_alive()):
                            continue
                        # worker died without delivering a batch, _END, or
                        # an exception (e.g. killed interpreter-side): one
                        # last race-free sweep, then fail instead of
                        # hanging forever
                        try:
                            item = self._q.get_nowait()
                            break
                        except queue.Empty:
                            self._exhausted = True
                            raise RuntimeError(
                                "staged-prefetch worker died without "
                                "delivering a batch or an error; the data "
                                "pipeline is gone (see stderr for the "
                                "worker's traceback)")
        if item is _END:
            self._exhausted = True
            return False
        if isinstance(item, BaseException):
            # the worker exits after putting its exception; a caller
            # that catches it and calls next() again must get False,
            # not a hang on a dead producer's queue
            self._exhausted = True
            telemetry.inc("io.prefetch.worker_errors")
            raise item
        self._cur = item
        if self._tel:
            telemetry.inc("io.prefetch.batches")
            telemetry.set_gauge("io.prefetch.depth", self._q.qsize())
            wait = time.perf_counter() - t0
            telemetry.observe("io.prefetch.wait_s", wait)
            if stalled:
                telemetry.inc("io.prefetch.stalls")
        return True

    def value(self):
        return self._cur

    def close(self) -> None:
        """Stop the worker and drop queued staged batches. REQUIRED
        when abandoning a pass mid-stream (consumer error): the worker
        otherwise spins in _put holding staged batches - pinned device
        memory - alive for the life of the process (the running
        thread's self-reference also defeats GC). Terminal for the
        pass: next() returns False until before_first() reopens.
        Idempotent.

        A worker exception still queued (the consumer stopped before
        next() could deliver it) is raised here rather than swallowed -
        unless close() is itself running from an exception handler, in
        which case the in-flight error wins and the worker's is noted
        on stderr."""
        self._shutdown()
        self._closed = True
        err, self._pending_error = self._pending_error, None
        if err is not None:
            if sys.exc_info()[1] is None:
                raise err
            telemetry.stderr(
                f"staged-prefetch: worker error superseded by the "
                f"consumer's: {type(err).__name__}: {err}\n",
                event_kind="io", type="prefetch_worker_error_superseded",
                error=f"{type(err).__name__}: {err}")

    # -- worker ------------------------------------------------------------
    def _put(self, item) -> bool:
        """Bounded put that stays responsive to _shutdown (a plain
        blocking put would deadlock against a consumer that stopped
        consuming)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        try:
            pending = []
            while not self._stop.is_set() and self.source.next():
                staged = self.stage_fn(self.source.value())
                if self.chunk <= 1:
                    if not self._put(staged):
                        return
                    continue
                pending.append(staged)
                if len(pending) >= self.chunk:
                    # release the per-batch staged singles BEFORE the
                    # (possibly long) blocking put: holding them
                    # through a full-queue wait would pin K extra
                    # batches of HBM beyond the documented
                    # K*(depth+1) budget
                    item = self.chunk_fn(pending)
                    pending = []
                    if not self._put(item):
                        return
            if pending and not self._stop.is_set():
                # round-boundary flush: the pass ended mid-chunk; a
                # SHORT chunk ships the tail so every delivered batch
                # trains this round (dropping it would silently starve
                # the trailing batches of every epoch)
                item = self.chunk_fn(pending)
                pending = []
                if not self._put(item):
                    return
            self._put(_END)
        except BaseException as e:  # noqa: BLE001 - re-raised in next()
            self._put(e)

    def _shutdown(self) -> None:
        if self._thread is None:
            return
        # bounded drain-while-join (thread_util discipline shared with
        # the rest of io/): a worker stuck outside q.put fails loudly
        # after the timeout instead of hanging the trainer; drained
        # worker exceptions are kept, not discarded
        def keep_error(item):
            if (isinstance(item, BaseException)
                    and self._pending_error is None):
                self._pending_error = item

        drain_and_join(self._q, self._thread, self._stop,
                       on_item=keep_error)
        self._q = None
        self._thread = None
