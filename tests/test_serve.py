"""Continuous-batching serving layer (serve/server.py, docs/SERVING.md).

Acceptance story: in-process tests assert tight-tolerance parity with
the batch-at-a-time predict path plus exact padding / admission /
compile-count semantics (XLA:CPU compiles a contraction per program
shape, and a bucket and the full predict batch are different shapes,
so answers may drift ~1 ULP between them), and the ragged-stream-vs-
unbatched-predict matrix runs in subprocesses on the virtual 8-device
platform: BITWISE on one device, a few ULP of float32 with equal
argmax on every row on the sharded legs (`mesh = data:4`, and
`zero_stage = 3` sharded params). Padding-row isolation (pad contents
must never leak into real rows) is bitwise IN-process:
both sides run the identical bucket executable.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from cxxnet_tpu import telemetry
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.serve import (
    Server, bucket_sizes, predictions_from_rows)
from cxxnet_tpu.utils.config import parse_config_string

MLP_CFG = """
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
silent = 1
seed = 7
"""

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# parity legs run on the virtual 8-device platform
PARITY_ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    XLA_FLAGS="--xla_force_host_platform_device_count=8")


def make_trainer(extra=""):
    t = NetTrainer()
    for k, v in parse_config_string(MLP_CFG + extra):
        t.set_param(k, v)
    t.init_model()
    return t


def req(rng, n):
    return rng.rand(n, 1, 1, 36).astype(np.float32)


def dist_ref(tr, data):
    """Unbatched reference: predict_dist on the rows as one batch."""
    return tr.predict_dist(DataBatch(
        data=data,
        label=np.zeros((data.shape[0], 1), np.float32)))


@pytest.fixture(scope="module")
def trainer():
    return make_trainer()


# ---------------------------------------------------------------------------
# bucket rules
# ---------------------------------------------------------------------------
def test_bucket_sizes_rules():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(1) == (1,)
    # non-power-of-two max joins the power-of-two ladder
    assert bucket_sizes(24, 4) == (4, 8, 16, 24)
    # a data axis prunes buckets it cannot divide
    assert bucket_sizes(32, 8) == (8, 16, 32)
    with pytest.raises(ValueError):
        bucket_sizes(0)
    with pytest.raises(ValueError):
        bucket_sizes(6, 4)  # 6 rows cannot split over 4 devices


def test_serve_rejects_uninitialized_trainer():
    t = NetTrainer()
    for k, v in parse_config_string(MLP_CFG):
        t.set_param(k, v)
    with pytest.raises(RuntimeError):
        Server(t)


# ---------------------------------------------------------------------------
# parity + padding isolation
# ---------------------------------------------------------------------------
def test_ragged_stream_matches_predict(trainer):
    """A ragged request stream through the server equals per-request
    predict_dist (tight tolerance in-process; the single-device leg of
    the subprocess matrix below is bitwise)."""
    rng = np.random.RandomState(3)
    sizes = [1, 3, 8, 2, 5, 7, 4, 6, 1, 2] * 2
    datas = [req(rng, s) for s in sizes]
    srv = Server(trainer, max_batch=8, max_wait_ms=2.0, replicas=2)
    srv.warmup()
    srv.start()
    futs = [srv.submit(d) for d in datas]
    outs = [f.result(timeout=120) for f in futs]
    stats = srv.stop()
    assert stats["errors"] == 0
    assert stats["rows"] == sum(sizes)
    for d, o in zip(datas, outs):
        assert o.shape == (d.shape[0], 3)
        np.testing.assert_allclose(o, dist_ref(trainer, d),
                                   rtol=5e-6, atol=1e-7)


def test_padding_rows_never_leak(trainer):
    """Bitwise, same bucket executable: real rows' outputs must be
    IDENTICAL whether the padding tail is zeros or garbage - padded
    rows provably never leak into real rows."""
    from cxxnet_tpu.parallel import distributed
    rng = np.random.RandomState(11)
    rows = req(rng, 3)
    outs = []
    for pad_fill in (0.0, 1e3):
        pad = np.full((5, 1, 1, 36), pad_fill, np.float32)
        gdata, gextras = trainer.stage_infer_rows(
            np.concatenate([rows, pad], axis=0))
        out = distributed.fetch_local(
            trainer.infer_rows(gdata, gextras))
        outs.append(np.asarray(out)[:3])
    assert np.array_equal(outs[0], outs[1]), \
        "padding contents leaked into real rows"


def test_request_position_in_batch_is_bitwise_irrelevant(trainer):
    """Same bucket executable: a request's rows produce the same bits
    at any row offset (what lets the dispatcher coalesce arbitrary
    request mixes without changing anyone's answer)."""
    from cxxnet_tpu.parallel import distributed
    rng = np.random.RandomState(12)
    rows = req(rng, 2)
    other = req(rng, 6)

    def run(data):
        gdata, ge = trainer.stage_infer_rows(data)
        return np.asarray(distributed.fetch_local(
            trainer.infer_rows(gdata, ge)))

    head = run(np.concatenate([rows, other], axis=0))[:2]
    tail = run(np.concatenate([other, rows], axis=0))[6:]
    assert np.array_equal(head, tail)


def test_oversize_request_splits(trainer):
    rng = np.random.RandomState(5)
    data = req(rng, 20)
    with Server(trainer, max_batch=8, max_wait_ms=1.0) as srv:
        out = srv.submit(data).result(timeout=120)
    np.testing.assert_allclose(out, dist_ref(trainer, data),
                               rtol=5e-6, atol=1e-7)


def test_predictions_from_rows_matches_predict(trainer):
    rng = np.random.RandomState(6)
    data = req(rng, 8)
    ref = trainer.predict(DataBatch(
        data=data, label=np.zeros((8, 1), np.float32)))
    with Server(trainer, max_batch=8) as srv:
        rows = srv.submit(data).result(timeout=120)
    assert np.array_equal(predictions_from_rows(rows), ref)


# ---------------------------------------------------------------------------
# warmup + zero steady-state recompiles
# ---------------------------------------------------------------------------
def test_zero_recompiles_steady_state():
    """Warmup compiles exactly one executable per bucket; a mixed
    request storm afterwards adds none (`_cache_size`, the jaxpr-audit
    technique - the audit itself re-asserts this in CI)."""
    tr = make_trainer()  # fresh: predict must not pre-fill the cache
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=2)
    srv.warmup()
    assert srv.executable_cache_size() == len(srv.buckets) == 4
    srv.start()
    rng = np.random.RandomState(9)
    futs = [srv.submit(req(rng, 1 + int(rng.randint(8))))
            for _ in range(40)]
    for f in futs:
        f.result(timeout=120)
    stats = srv.stop()
    assert stats["errors"] == 0
    assert srv.executable_cache_size() == len(srv.buckets)


# ---------------------------------------------------------------------------
# admission / flush policy
# ---------------------------------------------------------------------------
def test_low_load_flushes_on_timeout(trainer):
    """A lone small request must not wait for its bucket to fill:
    fill-or-timeout dispatches it after serve_max_wait_ms."""
    srv = Server(trainer, max_batch=8, max_wait_ms=30.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(4)
    t0 = time.monotonic()
    out = srv.submit(req(rng, 3)).result(timeout=30)
    wall = time.monotonic() - t0
    stats = srv.stop()
    assert out.shape == (3, 3)
    assert wall < 10.0  # flushed at ~30 ms, not never
    assert stats["batches"] == 1
    assert stats["buckets"][4] == 1  # smallest covering bucket
    assert stats["padding_rows"] == 1


def test_full_bucket_dispatches_without_waiting(trainer):
    """Once max_batch rows are queued the dispatcher ships them
    immediately - a huge max_wait_ms must not delay a FULL bucket."""
    srv = Server(trainer, max_batch=8, max_wait_ms=60_000.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(8)
    t0 = time.monotonic()
    out = srv.submit(req(rng, 8)).result(timeout=30)
    wall = time.monotonic() - t0
    stats = srv.stop()
    assert out.shape == (8, 3)
    assert wall < 10.0  # did NOT sit out the 60 s admission window
    assert stats["padding_rows"] == 0


def test_concurrent_submitters_coalesce(trainer):
    """The continuous-batching case: many threads submitting small
    requests; everyone gets their own correct rows back."""
    srv = Server(trainer, max_batch=8, max_wait_ms=5.0, replicas=2)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(10)
    datas = [req(rng, 1 + (i % 4)) for i in range(24)]
    outs = [None] * len(datas)
    errs = []

    def client(i):
        try:
            outs[i] = srv.submit(datas[i]).result(timeout=120)
        except Exception as e:  # noqa: BLE001 - re-raised below
            errs.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(datas))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    stats = srv.stop()
    assert not errs
    assert stats["errors"] == 0
    for d, o in zip(datas, outs):
        np.testing.assert_allclose(o, dist_ref(trainer, d),
                                   rtol=5e-6, atol=1e-7)


def test_submit_validation(trainer):
    srv = Server(trainer, max_batch=4)
    with pytest.raises(RuntimeError):  # not started
        srv.submit(np.zeros((1, 1, 1, 36), np.float32))
    srv.warmup()
    srv.start()
    with pytest.raises(ValueError):  # wrong instance shape
        srv.submit(np.zeros((1, 2, 2, 2), np.float32))
    with pytest.raises(ValueError):  # empty
        srv.submit(np.zeros((0, 1, 1, 36), np.float32))
    with pytest.raises(ValueError):  # undeclared extras
        srv.submit(np.zeros((1, 1, 1, 36), np.float32),
                   extras=[np.zeros((1, 2))])
    srv.stop()
    with pytest.raises(RuntimeError):  # stopped
        srv.submit(np.zeros((1, 1, 1, 36), np.float32))


# ---------------------------------------------------------------------------
# telemetry surface
# ---------------------------------------------------------------------------
def test_latency_and_queue_depth_through_registry(trainer):
    """p50/p99 latency and queue depth are visible through the
    process-wide telemetry registry (docs/OBSERVABILITY.md), and
    Server.stats() reports them in ms."""
    telemetry.reset_for_tests()
    srv = Server(trainer, max_batch=8, max_wait_ms=2.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(2)
    futs = [srv.submit(req(rng, 1 + (i % 3))) for i in range(12)]
    for f in futs:
        f.result(timeout=120)
    stats = srv.stop()
    snap = telemetry.get().registry.snapshot()
    lat = snap["serve.latency_s"]
    assert lat["count"] == 12
    assert lat["p50"] is not None and lat["p99"] is not None
    assert snap["serve.queue_depth"] == 0.0
    assert snap["serve.requests"] == 12
    assert snap["serve.batches"] == stats["batches"]
    assert stats["latency_p50_ms"] > 0
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"]


# ---------------------------------------------------------------------------
# wrapper surface
# ---------------------------------------------------------------------------
def test_wrapper_serve_api():
    from cxxnet_tpu import wrapper
    cfg = MLP_CFG.replace("batch_size = 32", "batch_size = 16")
    net = wrapper.Net(dev="cpu", cfg=cfg)
    net.init_model()
    net.serve_start(max_batch=4, max_wait_ms=2.0)
    with pytest.raises(RuntimeError):
        net.serve_start()  # already running
    rng = np.random.RandomState(1)
    one = rng.rand(1, 1, 36).astype(np.float32)  # single instance
    rows = net.serve_submit(one)
    assert rows.shape == (1, 3)
    np.testing.assert_allclose(
        rows, net.predict_dist(one[None]), rtol=5e-6, atol=1e-7)
    fut = net.serve_submit(rng.rand(3, 1, 1, 36).astype(np.float32),
                           block=False)
    assert fut.result(timeout=120).shape == (3, 3)
    stats = net.serve_stop()
    assert stats["requests"] == 2
    assert "latency_p99_ms" in stats
    with pytest.raises(RuntimeError):
        net.serve_stop()  # no server anymore
    with pytest.raises(RuntimeError):
        net.serve_submit(one)


# ---------------------------------------------------------------------------
# config schema: serve_* keys auto-registered, did-you-mean works
# ---------------------------------------------------------------------------
def test_serve_keys_registered_in_schema():
    from cxxnet_tpu.analysis import schema
    reg = schema.get_registry()
    for key in ("serve_max_batch", "serve_max_wait_ms",
                "serve_replicas", "serve_rows"):
        assert reg.recognizes(key), key
    assert schema.suggest("serve_max_batchh") == "serve_max_batch"


def test_cli_rejects_typoed_serve_key():
    from cxxnet_tpu.analysis.schema import validate_pairs
    from cxxnet_tpu.utils.config import ConfigError
    with pytest.raises(ConfigError) as ei:
        validate_pairs([("serve_max_batchh", "8")], source="x.conf")
    assert "serve_max_batch" in str(ei.value)  # did-you-mean


# ---------------------------------------------------------------------------
# CLI surface: task = serve drains the pred iterator through the
# server and writes a task=pred-compatible prediction file
# ---------------------------------------------------------------------------
CLI_CONF = """
data = train
iter = mnist
    path_img = "{d}/train-img.gz"
    path_label = "{d}/train-lbl.gz"
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
num_round = 1
max_round = 1
eta = 0.3
metric = error
silent = 1
"""


def test_cli_serve_task(tmp_path):
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.telemetry.sink import read_jsonl
    from cxxnet_tpu.tools.telemetry_smoke import write_synth_mnist
    d = str(tmp_path)
    write_synth_mnist(d, 96, 0, "train")
    write_synth_mnist(d, 64, 1, "test")
    conf = os.path.join(d, "serve_cli.conf")
    with open(conf, "w") as f:
        f.write(CLI_CONF.format(d=d))
    mdir = os.path.join(d, "models")
    assert LearnTask().run([conf, f"model_dir={mdir}"]) == 0
    model = os.path.join(mdir, "0001.model")
    assert os.path.exists(model)
    # direct predict reference
    assert LearnTask().run(
        [conf, "task=pred", f"model_in={model}",
         f"pred={d}/pred_direct.txt"]) == 0
    # the serve task, ragged request mode, with the metrics stream on
    metrics = os.path.join(d, "serve_metrics.jsonl")
    assert LearnTask().run(
        [conf, "task=serve", f"model_in={model}",
         f"pred={d}/pred_serve.txt", "serve_rows=0",
         "serve_max_batch=8", f"metrics_file={metrics}"]) == 0
    with open(os.path.join(d, "pred_direct.txt")) as f:
        direct = f.read().splitlines()
    with open(os.path.join(d, "pred_serve.txt")) as f:
        served = f.read().splitlines()
    assert len(direct) == len(served) == 64
    assert direct == served
    # latency histogram + queue-depth gauge reached the metrics stream
    recs = [r for r in read_jsonl(metrics) if r.get("kind") == "serve"]
    assert recs, "no serve metrics record"
    m = recs[-1]["metrics"]
    assert m["serve.latency_s"]["count"] > 0
    assert m["serve.latency_s"]["p99"] is not None
    assert "serve.queue_depth" in m
    assert m["serve.padding_rows"] > 0  # ragged mode really padded


def test_cli_overrides_after_pred_are_not_swallowed(tmp_path):
    """A command-line `pred=file` used to OPEN an unterminated pred
    iterator block, silently eating every override after it (found
    because `serve_max_batch=8` after `pred=` configured nothing):
    CLI pairs must never act as block markers - they rename the
    output and land in defcfg."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.utils.config import parse_config_file
    conf = tmp_path / "c.conf"
    conf.write_text(CLI_CONF.format(d=str(tmp_path)))
    task = LearnTask()
    for n, v in parse_config_file(str(conf)):
        task.set_param(n, v)
    task._n_file_pairs = len(task.cfg)
    for arg in (f"pred={tmp_path}/renamed.txt", "serve_max_batch=8"):
        n, v = arg.split("=", 1)
        task.set_param(n, v)
    defcfg, train, evals, pred = task._split_blocks()
    assert ("serve_max_batch", "8") in defcfg
    assert task.name_pred == f"{tmp_path}/renamed.txt"
    assert pred is not None  # the FILE's pred block survives intact
    assert ("serve_max_batch", "8") not in pred


def test_cli_serve_requires_pred_iterator(tmp_path):
    from cxxnet_tpu.main import LearnTask
    task = LearnTask()
    task.itr_pred = None
    with pytest.raises(AssertionError):
        task.task_serve()


# ---------------------------------------------------------------------------
# parity matrix: ragged serve == unbatched predict (subprocess) -
# bitwise on one device, a few ULP on the data-parallel mesh and on
# ZeRO-3 sharded params consumed directly
# ---------------------------------------------------------------------------
_PARITY_SCRIPT = r"""
import sys
import numpy as np
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.serve import Server
from cxxnet_tpu.utils.config import parse_config_string

CFG = '''%s'''
EXTRA = sys.argv[1] if len(sys.argv) > 1 else ""
tr = NetTrainer()
for k, v in parse_config_string(CFG + EXTRA.replace(";", "\n")):
    tr.set_param(k, v)
tr.init_model()
# one real update so the served params are trained state, not init
rs = np.random.RandomState(0)
tr.update(DataBatch(
    data=rs.rand(32, 1, 1, 36).astype(np.float32),
    label=rs.randint(0, 3, size=(32, 1)).astype(np.float32)))
if "zero_stage = 3" in EXTRA.replace(";", "\n"):
    # the stage-3 contract: params live SHARDED between steps and the
    # serve executable consumes them directly (no host gather)
    leaf = tr.state["params"]["fc1"]["wmat"]
    assert not leaf.sharding.is_fully_replicated, leaf.sharding
rng = np.random.RandomState(3)
sizes = [1, 3, 8, 2, 5, 7, 4, 6] * 2
datas = [rng.rand(s, 1, 1, 36).astype(np.float32) for s in sizes]
srv = Server(tr, max_batch=8, max_wait_ms=2.0, replicas=2)
srv.warmup()
n_warm = srv.executable_cache_size()
srv.start()
outs = [f.result(timeout=120)
        for f in [srv.submit(d) for d in datas]]
stats = srv.stop()
assert stats["errors"] == 0, stats
assert srv.executable_cache_size() == n_warm, "steady-state recompile"
dsize = tr.mesh.shape.get("data", 1)
ATOL = 4 * float(np.finfo(np.float32).eps)  # outputs are softmax rows
for d, o in zip(datas, outs):
    ref = tr.predict_dist(DataBatch(
        data=d, label=np.zeros((d.shape[0], 1), np.float32)))
    bucket = next(b for b in srv.buckets if b >= d.shape[0])
    if dsize == 1:
        # one device: the bucket and the unbatched predict compile
        # the same contraction, and every bucket answers bitwise
        assert np.array_equal(o, ref), (
            "bitwise mismatch for a %%d-row request (bucket %%d): "
            "max|d|=%%g" %% (d.shape[0], bucket, np.abs(o - ref).max()))
    else:
        # sharded legs: XLA:CPU's only runtime compiles a contraction
        # per program shape (a gemv at 1 row/device, gemms of other
        # tilings above it), so a bucket's per-device slice and the
        # reference's differ by an ULP or so - a backend codegen
        # artifact, not a serving-layer property
        # (test_padding_rows_never_leak proves the layer itself adds
        # zero numeric difference). docs/SERVING.md "Numerics fine
        # print": a few ULP of float32 and the same class on every row
        assert np.allclose(o, ref, rtol=0, atol=ATOL), (
            "mismatch for a %%d-row request (bucket %%d): max|d|=%%g"
            %% (d.shape[0], bucket, np.abs(o - ref).max()))
        assert np.array_equal(np.argmax(o, 1), np.argmax(ref, 1))
print("SERVE_PARITY=OK buckets=%%s bitwise=%%s"
      %% (list(srv.buckets), dsize == 1))
""" % MLP_CFG


@pytest.mark.parametrize("extra", [
    "",                                  # single device
    "mesh = data:4",                     # data-parallel fan-out
    "mesh = data:4;zero_stage = 3",      # sharded params, no gather
], ids=["plain", "data4", "zero3"])
def test_bitwise_serve_equals_unbatched_predict(extra):
    r = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT, extra],
        env=PARITY_ENV, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SERVE_PARITY=OK" in r.stdout


# ---------------------------------------------------------------------------
# production front: backpressure, deadlines, /predict, hot-swap
# (docs/SERVING.md "Serving over HTTP" / "Hot-swap runbook")
# ---------------------------------------------------------------------------
def _post_predict(port, payload, timeout=30):
    import json
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def _stall_dispatch(n, secs):
    """Arm n consecutive serve-side dispatch delays (fault registry)."""
    from cxxnet_tpu.utils import fault
    fault.clear()
    for i in range(n):
        fault.inject("serve_dispatch_delay", "delay", str(secs),
                     at=i + 1)


def test_queue_limit_rejects_with_typed_error():
    """Past queue_limit rows, submit() raises QueueFullError carrying
    Retry-After advice - it never enqueues (hard admission bound)."""
    from cxxnet_tpu.serve import QueueFullError
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1,
                 queue_limit=16)
    srv.warmup()
    _stall_dispatch(64, 0.1)
    srv.start()
    rng = np.random.RandomState(5)
    futs, errs = [], []
    try:
        for _ in range(30):
            try:
                futs.append(srv.submit(req(rng, 4)))
            except QueueFullError as e:
                errs.append(e)
        assert errs, "queue never filled past the limit"
        e = errs[0]
        assert e.retry_after_s > 0
        assert e.queue_depth <= 16
        for f in futs:
            f.result(timeout=60)
    finally:
        fault.clear()
        stats = srv.stop()
    # every accepted request resolved; every shed one was counted
    assert stats["errors"] == 0
    assert stats["shed_requests"] == len(errs)
    assert stats["shed_rows"] == 4 * len(errs)
    reg = telemetry.get().registry
    assert reg.counter("serve.shed_total").value == len(errs)
    assert reg.counter("serve.shed_rows").value == 4 * len(errs)


def test_shed_flips_healthz_503_then_recovers():
    """Shedding marks the `serve_shed` health source unhealthy (503
    on /healthz); once the queue drains below half the limit for the
    hysteresis window, it recovers to 200 without a restart."""
    from cxxnet_tpu.serve import QueueFullError
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=2,
                 queue_limit=8)
    srv.shed_clear_ms = 200.0
    srv.warmup()
    _stall_dispatch(32, 0.1)
    srv.start()
    rng = np.random.RandomState(6)
    futs, shed = [], 0
    try:
        for _ in range(30):
            try:
                futs.append(srv.submit(req(rng, 4)))
            except QueueFullError:
                shed += 1
        assert shed > 0
        ok, reasons = telemetry.get().health.status()
        assert not ok and "serve_shed" in reasons, reasons
        for f in futs:
            f.result(timeout=60)
    finally:
        fault.clear()
    # recovery is the replicas' job (hysteresis window), no new
    # submits needed
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if telemetry.get().health.ok:
            break
        time.sleep(0.05)
    assert telemetry.get().health.ok, "shed verdict never cleared"
    srv.stop()


def test_deadline_expires_before_dispatch():
    """A request whose deadline lapses in the queue resolves with
    DeadlineExpiredError and never spends a bucket slot: no dispatch,
    no error counted - dropped at collect time."""
    from cxxnet_tpu.serve import DeadlineExpiredError
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv.warmup()
    _stall_dispatch(4, 0.4)
    srv.start()
    rng = np.random.RandomState(7)
    try:
        blocker = srv.submit(req(rng, 8))   # pins the only replica
        doomed = srv.submit(req(rng, 2), deadline_ms=50)
        with pytest.raises(DeadlineExpiredError):
            doomed.result(timeout=30)
        blocker.result(timeout=30)
    finally:
        fault.clear()
        stats = srv.stop()
    assert stats["deadline_expired"] == 1
    assert stats["errors"] == 0
    assert telemetry.get().registry.counter(
        "serve.deadline_expired").value == 1
    # the expired request's rows were never dispatched
    assert stats["rows"] - 2 == sum(
        b * n for b, n in stats["buckets"].items()) - stats[
            "padding_rows"]


def test_http_predict_roundtrip_and_errors(trainer):
    """The /predict POST path: 200 with predictions matching the
    in-process surface, 400 on malformed input, echoing the ingress-
    minted trace id."""
    telemetry.reset_for_tests()
    srv = Server(trainer, max_batch=8, max_wait_ms=1.0, replicas=1,
                 http_port=0)
    srv.warmup()
    srv.start()
    try:
        port = srv.metrics_server.port
        rng = np.random.RandomState(8)
        data = req(rng, 3)
        code, _, out = _post_predict(
            port, {"data": data.reshape(3, -1).tolist(), "raw": True})
        assert code == 200
        assert out["rows"] == 3 and out["trace"]
        ref = srv.submit(data).result(timeout=30)
        assert np.array_equal(
            np.asarray(out["outputs"], np.float32), ref)
        assert out["predictions"] == [
            float(v) for v in predictions_from_rows(ref)]
        # the ingress trace id resolves through the queue/bucket
        # machinery like any in-process submit
        assert "-" in out["trace"]
        code, _, out = _post_predict(port, {"data": "nonsense"})
        assert code == 400 and "error" in out
        code, _, out = _post_predict(port, {})
        assert code == 400
    finally:
        srv.stop()


def test_http_storm_gets_429_with_sane_retry_after(trainer):
    """Past queue_limit the HTTP caller gets 429 + Retry-After (int
    seconds in [1, 60], exact advice in the body) while accepted
    requests still resolve - explicit shedding, not queue collapse."""
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    srv = Server(trainer, max_batch=8, max_wait_ms=1.0, replicas=1,
                 http_port=0, queue_limit=4)
    srv.warmup()
    # 0.3s per dispatch: any two requests overlapping a dispatch
    # window exceed the 4-row limit, so the storm MUST shed
    _stall_dispatch(64, 0.3)
    srv.start()
    try:
        port = srv.metrics_server.port
        rng = np.random.RandomState(9)
        payload = {"data": req(rng, 4).reshape(4, -1).tolist()}
        results = []
        lock = threading.Lock()

        def hammer():
            for _ in range(6):
                code, headers, out = _post_predict(port, payload,
                                                   timeout=120)
                with lock:
                    results.append((code, headers, out))

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        codes = [c for c, _, _ in results]
        assert 200 in codes and 429 in codes, codes
        for code, headers, out in results:
            if code != 429:
                continue
            retry = int(headers["Retry-After"])
            assert 1 <= retry <= 60
            assert out["retry_after_s"] > 0
            assert out["queue_depth"] <= 4
    finally:
        fault.clear()
        stats = srv.stop()
    assert stats["errors"] == 0
    assert stats["shed_requests"] == sum(
        1 for c in codes if c == 429)


def test_http_deadline_maps_504(trainer):
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    srv = Server(trainer, max_batch=8, max_wait_ms=1.0, replicas=1,
                 http_port=0)
    srv.warmup()
    _stall_dispatch(4, 0.4)
    srv.start()
    try:
        port = srv.metrics_server.port
        rng = np.random.RandomState(10)
        blocker = srv.submit(req(rng, 8))
        code, _, out = _post_predict(
            port, {"data": req(rng, 2).reshape(2, -1).tolist(),
                   "deadline_ms": 50})
        assert code == 504 and "error" in out
        blocker.result(timeout=30)
    finally:
        fault.clear()
        srv.stop()


def _save_checkpoint(tr, path):
    with open(path, "wb") as fo:
        tr.save_model(fo)


def test_hot_swap_mid_storm_zero_drops_bitwise_switch(tmp_path):
    """A swap under live traffic drops nothing: every future resolves
    error-free, pre-swap answers match the old weights, and post-swap
    answers are BITWISE the new checkpoint's (params are executable
    arguments - same program, zero recompiles)."""
    telemetry.reset_for_tests()
    tr_old = make_trainer()
    tr_new = make_trainer("seed = 99\n")
    ck = str(tmp_path / "new.model")
    _save_checkpoint(tr_new, ck)
    srv = Server(tr_old, max_batch=8, max_wait_ms=1.0, replicas=2)
    srv.warmup()
    n_warm = srv.executable_cache_size()
    srv.start()
    rng = np.random.RandomState(11)
    probe = req(rng, 5)
    try:
        old_ref = srv.submit(probe).result(timeout=60)
        futs = [srv.submit(req(rng, s))
                for s in ([1, 3, 8, 2, 5, 7] * 4)]
        assert srv.swap_to(ck) is True
        for f in futs:
            f.result(timeout=120)  # in-flight + queued all resolve
        new_out = srv.submit(probe).result(timeout=60)
        stats = srv.stats()
        assert stats["errors"] == 0
        assert stats["swaps"] == 1
        assert srv.executable_cache_size() == n_warm, \
            "swap must not recompile (params are arguments)"
    finally:
        srv.stop()
    # cold reference: a fresh server over the new checkpoint's weights
    srv2 = Server(tr_new, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv2.warmup()
    srv2.start()
    try:
        cold_ref = srv2.submit(probe).result(timeout=60)
    finally:
        srv2.stop()
    assert not np.array_equal(old_ref, new_out), \
        "swap visibly changed the weights"
    assert np.array_equal(new_out, cold_ref), \
        "post-swap serving must be bitwise the new checkpoint"
    assert telemetry.get().registry.counter(
        "serve.swaps").value == 1


def test_torn_checkpoint_rejected_keeps_serving(tmp_path):
    """A torn (truncated, trailer-less) checkpoint is rejected with a
    swap.rejected verdict; the old weights keep serving unchanged."""
    telemetry.reset_for_tests()
    tr = make_trainer()
    tr_new = make_trainer("seed = 99\n")
    good = str(tmp_path / "good.model")
    torn = str(tmp_path / "torn.model")
    _save_checkpoint(tr_new, good)
    blob = open(good, "rb").read()
    with open(torn, "wb") as fo:
        fo.write(blob[:len(blob) // 2])
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(12)
    probe = req(rng, 4)
    try:
        before = srv.submit(probe).result(timeout=60)
        assert srv.swap_to(torn) is False
        after = srv.submit(probe).result(timeout=60)
        stats = srv.stats()
    finally:
        srv.stop()
    assert np.array_equal(before, after), \
        "rejected swap must not perturb serving"
    assert stats["swaps"] == 0
    assert stats["swap_rejected"] == 1
    assert stats["errors"] == 0
    assert telemetry.get().registry.counter(
        "serve.swap_rejected").value == 1


def test_swap_watcher_picks_up_published_checkpoint(tmp_path):
    """The swap_watch poller: an atomic publish_model to the watched
    path triggers a live swap; a torn publish (fault-injected) is
    rejected once and serving continues on the last good weights."""
    from cxxnet_tpu.nnet import checkpoint
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    fault.clear()
    tr = make_trainer()
    tr_new = make_trainer("seed = 99\n")
    saved = str(tmp_path / "0001.model")
    watch = str(tmp_path / "publish.model")
    _save_checkpoint(tr_new, saved)
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1,
                 swap_watch=watch, swap_poll_ms=20.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(13)
    probe = req(rng, 4)
    try:
        old = srv.submit(probe).result(timeout=60)
        checkpoint.publish_model(saved, watch)
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if srv.stats()["swaps"] >= 1:
                break
            time.sleep(0.05)
        assert srv.stats()["swaps"] == 1, "watcher never swapped"
        new = srv.submit(probe).result(timeout=60)
        assert not np.array_equal(old, new)
        # torn publish leg: the watcher validates and rejects, the
        # new weights keep serving
        fault.inject("swap_torn_checkpoint", "corrupt")
        checkpoint.publish_model(saved, watch)
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if srv.stats()["swap_rejected"] >= 1:
                break
            time.sleep(0.05)
        assert srv.stats()["swap_rejected"] == 1, \
            "torn publish never rejected"
        still = srv.submit(probe).result(timeout=60)
        assert np.array_equal(new, still)
        stats = srv.stats()
        assert stats["errors"] == 0 and stats["swaps"] == 1
    finally:
        fault.clear()
        srv.stop()


def test_serve_front_keys_registered_in_schema():
    from cxxnet_tpu.analysis import schema
    reg = schema.get_registry()
    for key in ("serve_port", "serve_queue_limit",
                "serve_deadline_ms", "serve_shed_clear_ms",
                "swap_watch", "swap_poll_ms", "publish_model"):
        assert reg.recognizes(key), key
    assert schema.suggest("serve_queue_limitt") == "serve_queue_limit"
    assert schema.suggest("swap_watchh") == "swap_watch"


def test_no_http_thread_unless_armed(trainer):
    """Byte-parity guard: a Server without serve_port/metrics_port
    spawns no HTTP listener thread and imports no HTTP plane."""
    srv = Server(trainer, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv.warmup()
    srv.start()
    try:
        assert srv.metrics_server is None
        assert not [t for t in threading.enumerate()
                    if t.name == "telemetry-http"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# canaried rollout + automatic rollback
# (docs/SERVING.md "Canary runbook")
# ---------------------------------------------------------------------------
def _perturbed_trainer():
    """A realistic swap candidate: the incumbent's weights nudged by
    0.1% - bitwise-different params whose argmax agrees on nearly
    every row, the shape two consecutive checkpoints of one training
    run have. (Two unrelated random inits agree only ~1/3 of the time
    on 3-class argmax, and the judge rolls them back - correctly.)"""
    t = make_trainer()
    w, _ = t.get_weight("fc1", "wmat")
    t.set_weight(w * 1.001, "fc1", "wmat")
    return t


def test_canary_promotes_healthy_candidate_mid_storm(tmp_path):
    """swap_to() under a canary config stages the candidate, routes a
    deterministic traffic fraction at it through the SAME warmed
    bucket executables (zero recompiles), and auto-promotes after the
    window: post-promote answers are bitwise the candidate's, nothing
    drops, the incumbent's last pre-swap answers are unchanged."""
    telemetry.reset_for_tests()
    tr = make_trainer()
    tr_new = _perturbed_trainer()
    ck = str(tmp_path / "cand.model")
    _save_checkpoint(tr_new, ck)
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=2,
                 canary_frac=0.5, canary_window=1.0)
    srv.warmup()
    n_warm = srv.executable_cache_size()
    srv.start()
    rng = np.random.RandomState(21)
    probe = req(rng, 5)
    try:
        old_ref = srv.submit(probe).result(timeout=60)
        assert srv.swap_to(ck) is True
        assert srv.stats()["canary_active"] is True
        futs = []
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            futs.append(srv.submit(req(rng, int(rng.randint(1, 9)))))
            if srv.stats()["canary_promoted"]:
                break
            time.sleep(0.005)
        for f in futs:
            f.result(timeout=120)
        stats = srv.stats()
        assert stats["canary_promoted"] == 1, "judge never promoted"
        assert stats["canary_rolled_back"] == 0
        assert stats["swaps"] == 1
        assert stats["canary_requests"] > 0, \
            "no traffic ever routed to the candidate side"
        assert stats["errors"] == 0
        assert srv.executable_cache_size() == n_warm, \
            "canary must not recompile (params are arguments)"
        new_out = srv.submit(probe).result(timeout=60)
    finally:
        srv.stop()
    # cold reference: a fresh server over the candidate's weights
    srv2 = Server(tr_new, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv2.warmup()
    srv2.start()
    try:
        cold_ref = srv2.submit(probe).result(timeout=60)
    finally:
        srv2.stop()
    assert not np.array_equal(old_ref, new_out), \
        "promote visibly changed the weights"
    assert np.array_equal(new_out, cold_ref), \
        "post-promote serving must be bitwise the candidate"
    reg = telemetry.get().registry
    assert reg.counter("serve.canary_promoted").value == 1
    assert reg.counter("serve.canary_requests").value > 0


def test_canary_rolls_back_on_divergence(tmp_path):
    """A candidate whose shadow outputs diverge (canary_divergence
    fault NaN-poisons them) is rolled back: swaps stays 0, the
    incumbent keeps serving bitwise-identical answers, and no request
    errors - rollback is invisible to clients."""
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    tr = make_trainer()
    tr_new = _perturbed_trainer()
    ck = str(tmp_path / "cand.model")
    _save_checkpoint(tr_new, ck)
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=2,
                 canary_frac=0.25, canary_window=1.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(22)
    probe = req(rng, 4)
    try:
        before = srv.submit(probe).result(timeout=60)
        fault.clear()
        for i in range(50):
            fault.inject("canary_divergence", "corrupt", at=i + 1)
        assert srv.swap_to(ck) is True
        futs = []
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            futs.append(srv.submit(req(rng, 3)))
            if srv.stats()["canary_rolled_back"]:
                break
            time.sleep(0.005)
        for f in futs:
            f.result(timeout=120)
        stats = srv.stats()
        assert stats["canary_rolled_back"] == 1, \
            "poisoned candidate never rolled back"
        assert stats["swaps"] == 0
        assert stats["canary_promoted"] == 0
        assert stats["errors"] == 0
        after = srv.submit(probe).result(timeout=60)
        assert np.array_equal(before, after), \
            "rollback must leave the incumbent bitwise untouched"
    finally:
        fault.clear()
        srv.stop()
    assert telemetry.get().registry.counter(
        "serve.canary_rolled_back").value == 1


def test_canary_judge_crash_fails_safe(tmp_path):
    """A judge that dies (canary_judge_error fault) must never leave
    the canary half-routed forever: the candidate is rolled back and
    the incumbent keeps serving unchanged."""
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    tr = make_trainer()
    tr_new = _perturbed_trainer()
    ck = str(tmp_path / "cand.model")
    _save_checkpoint(tr_new, ck)
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1,
                 canary_frac=0.5, canary_window=30.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(23)
    probe = req(rng, 4)
    try:
        before = srv.submit(probe).result(timeout=60)
        fault.clear()
        fault.inject("canary_judge_error", "crash")
        assert srv.swap_to(ck) is True
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if srv.stats()["canary_rolled_back"]:
                break
            time.sleep(0.02)
        stats = srv.stats()
        assert stats["canary_rolled_back"] == 1, \
            "judge crash never resolved to a rollback"
        assert stats["swaps"] == 0
        assert stats["canary_active"] is False
        after = srv.submit(probe).result(timeout=60)
        assert np.array_equal(before, after)
    finally:
        fault.clear()
        srv.stop()


def test_unarmed_swap_is_direct_no_judge_thread(tmp_path):
    """Byte-parity guard: without canary_frac, swap_to() flips
    immediately (PR 16 semantics) and no judge thread exists."""
    telemetry.reset_for_tests()
    tr = make_trainer()
    tr_new = _perturbed_trainer()
    ck = str(tmp_path / "cand.model")
    _save_checkpoint(tr_new, ck)
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv.warmup()
    srv.start()
    try:
        assert srv.swap_to(ck) is True
        stats = srv.stats()
        assert stats["swaps"] == 1
        assert stats["canary_active"] is False
        assert stats["canary_requests"] == 0
        assert not [t for t in threading.enumerate()
                    if t.name == "serve-canary-judge"]
    finally:
        srv.stop()


def test_publish_meta_sidecar_roundtrip(tmp_path):
    """publish_model writes a provenance sidecar BEFORE the model
    copy; read_publish_meta returns it, and None when absent."""
    from cxxnet_tpu.nnet import checkpoint
    tr = make_trainer()
    src = str(tmp_path / "a.model")
    _save_checkpoint(tr, src)
    pub = str(tmp_path / "latest.model")
    checkpoint.publish_model(src, pub)
    meta = checkpoint.read_publish_meta(pub)
    assert meta is not None
    assert meta["src"] == os.path.abspath(src)
    assert meta["torn"] is False
    assert meta["bytes"] == os.path.getsize(src)
    assert checkpoint.read_publish_meta(
        str(tmp_path / "missing.model")) is None


# ---------------------------------------------------------------------------
# hardened ingress: Retry-After clamp, slow-loris, body cap, accept
# gate, graceful drain (docs/SERVING.md "Connection limits & drain")
# ---------------------------------------------------------------------------
def _read_until_eof(sock, timeout=10.0):
    sock.settimeout(timeout)
    buf = b""
    try:
        while True:
            chunk = sock.recv(4096)
            if not chunk:
                break
            buf += chunk
    except OSError:
        pass
    return buf


def test_retry_after_cold_clamp_pinned():
    """A 429 shed before the drain-rate EWMA has a single sample must
    advise the documented cold-start clamp - never garbage derived
    from a rate of zero."""
    from cxxnet_tpu.serve import QueueFullError
    from cxxnet_tpu.serve.server import RETRY_AFTER_COLD_S
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1,
                 queue_limit=8)
    srv.warmup()
    _stall_dispatch(64, 0.3)
    srv.start()
    rng = np.random.RandomState(24)
    futs, errs = [], []
    try:
        for _ in range(30):
            try:
                futs.append(srv.submit(req(rng, 4)))
            except QueueFullError as e:
                errs.append(e)
        assert errs, "queue never filled past the limit"
        # the first shed lands before any batch completed (0.3 s
        # stall): no drain-rate sample exists yet
        assert errs[0].retry_after_s == RETRY_AFTER_COLD_S
        for f in futs:
            f.result(timeout=60)
    finally:
        fault.clear()
        srv.stop()


def test_slow_loris_cut_while_service_continues():
    """Two live loris sockets - one stalled mid-headers, one stalled
    mid-body - are cut at serve_conn_timeout_ms while a concurrent
    well-behaved request completes normally."""
    import socket
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1,
                 http_port=0, conn_timeout_ms=400.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(25)
    try:
        port = srv.metrics_server.port
        s1 = socket.create_connection(("127.0.0.1", port), timeout=10)
        s1.sendall(b"POST /predict HTTP/1.0\r\nContent-")  # headers stall
        s2 = socket.create_connection(("127.0.0.1", port), timeout=10)
        s2.sendall(b"POST /predict HTTP/1.0\r\n"
                   b"Content-Length: 1000\r\n\r\nxx")  # body stall
        t0 = time.monotonic()
        code, _, out = _post_predict(
            port, {"data": req(rng, 2).reshape(2, -1).tolist()})
        assert code == 200 and out["rows"] == 2
        body_resp = _read_until_eof(s2)
        t_body = time.monotonic() - t0
        _read_until_eof(s1)
        t_hdr = time.monotonic() - t0
        s1.close()
        s2.close()
        # both cut near the deadline, far before the 10 s eof budget
        assert t_body < 8.0 and t_hdr < 8.0
        # the body-phase victim gets a clean 408 before the cut
        assert b"408" in body_resp.split(b"\r\n")[0], body_resp[:80]
        stats = srv.stats()
        assert stats["conn_timeouts"] >= 2
        assert stats["errors"] == 0
    finally:
        srv.stop()
    assert telemetry.get().registry.counter(
        "serve.conn_timeouts").value >= 2


def test_oversized_body_413_then_serves_normally():
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1,
                 http_port=0, max_body_bytes=512)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(26)
    try:
        port = srv.metrics_server.port
        code, _, out = _post_predict(
            port, {"data": req(rng, 16).reshape(16, -1).tolist()})
        assert code == 413
        assert out["max_body_bytes"] == 512
        # a small request on a fresh connection still serves
        code, _, out = _post_predict(
            port, {"data": [[0.0] * 36]})
        assert code == 200 and out["rows"] == 1
        assert srv.stats()["conn_oversized"] == 1
    finally:
        srv.stop()


def test_accept_gate_503_with_retry_after_then_recovers():
    """Past serve_max_conns the accept gate answers a raw 503 with
    Retry-After WITHOUT spawning a handler thread, flips its own
    health source, and recovers hysteretically once connections
    drop - driven by real /healthz polling (each GET is itself a
    connection exercising the gate)."""
    import socket
    import urllib.error
    import urllib.request
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1,
                 http_port=0, max_conns=1)
    srv.shed_clear_ms = 200.0
    srv.warmup()
    srv.start()
    try:
        port = srv.metrics_server.port
        hold = socket.create_connection(
            ("127.0.0.1", port), timeout=10)
        hold.sendall(b"GET /healthz HTTP/1.0\r\nX-Hold")  # occupy slot
        time.sleep(0.3)
        rej = socket.create_connection(
            ("127.0.0.1", port), timeout=10)
        rej.sendall(b"GET /healthz HTTP/1.0\r\n\r\n")
        buf = _read_until_eof(rej)
        rej.close()
        assert b"503" in buf.split(b"\r\n")[0], buf[:80]
        assert b"Retry-After: 1" in buf, buf[:200]
        ok, reasons = telemetry.get().health.status()
        assert not ok and "serve_conns" in reasons, reasons
        hold.close()
        recovered = False
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            try:
                r = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5)
                if r.status == 200:
                    recovered = True
                    break
            except (urllib.error.HTTPError, OSError):
                pass
            time.sleep(0.1)
        assert recovered, "conn gate never recovered"
        assert srv.stats()["conn_rejected"] >= 1
    finally:
        srv.stop()
    assert telemetry.get().registry.counter(
        "serve.conn_rejected").value >= 1


def test_drain_resolves_every_queued_future():
    """drain() flips the serve_drain health source, rejects new
    submits with a typed error, and resolves EVERY already-admitted
    future before returning - zero drops of accepted work."""
    from cxxnet_tpu.utils import fault
    telemetry.reset_for_tests()
    tr = make_trainer()
    srv = Server(tr, max_batch=8, max_wait_ms=1.0, replicas=1)
    srv.warmup()
    _stall_dispatch(16, 0.2)
    srv.start()
    rng = np.random.RandomState(27)
    futs = [srv.submit(req(rng, 2)) for _ in range(10)]
    state = {}
    th = threading.Thread(
        target=lambda: state.update(stats=srv.drain()))
    th.start()
    try:
        seen = False
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not seen:
            ok, reasons = telemetry.get().health.status()
            seen = "serve_drain" in reasons
            time.sleep(0.01)
        assert seen, "drain never flipped the health source"
        with pytest.raises(RuntimeError):
            srv.submit(req(rng, 1))
    finally:
        th.join(timeout=120)
        fault.clear()
    for f in futs:
        assert f.result(timeout=1).shape == (2, 3)
    assert state["stats"]["errors"] == 0
    assert telemetry.get().health.ok, \
        "serve_drain verdict must clear once drained"


def test_cli_serve_sigterm_drains(tmp_path, capsys):
    """SIGTERM during task=serve stops admission, drains every
    admitted request to the output file, and exits 0 - the k8s
    preStop / rolling-restart contract."""
    import signal
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.tools.telemetry_smoke import write_synth_mnist
    from cxxnet_tpu.utils import fault
    d = str(tmp_path)
    write_synth_mnist(d, 96, 0, "train")
    write_synth_mnist(d, 128, 1, "test")
    conf = os.path.join(d, "serve_term.conf")
    with open(conf, "w") as f:
        f.write(CLI_CONF.format(d=d))
    mdir = os.path.join(d, "models")
    assert LearnTask().run([conf, f"model_dir={mdir}"]) == 0
    model = os.path.join(mdir, "0001.model")
    # safety net: a no-op handler is what task_serve restores, so a
    # straggler SIGTERM after the task exits cannot kill pytest
    old = signal.signal(signal.SIGTERM, lambda s, f: None)
    killer_stop = threading.Event()
    # the registry is process-global: measure against a baseline, or
    # requests counted by EARLIER tests fire the kill before the
    # drain handler is even installed
    n0 = telemetry.get().registry.counter("serve.requests").value

    def killer():
        # fire once real requests are flowing (not during warmup)
        while not killer_stop.is_set():
            n = telemetry.get().registry.counter(
                "serve.requests").value
            if n - n0 >= 8:
                os.kill(os.getpid(), signal.SIGTERM)
                return
            time.sleep(0.01)

    _stall_dispatch(2000, 0.05)
    th = threading.Thread(target=killer, daemon=True)
    th.start()
    try:
        rc = LearnTask().run(
            [conf, "task=serve", f"model_in={model}",
             f"pred={d}/pred_term.txt", "serve_rows=1",
             "serve_max_batch=8"])
    finally:
        killer_stop.set()
        th.join(timeout=10)
        fault.clear()
        signal.signal(signal.SIGTERM, old)
    assert rc == 0
    assert "SIGTERM - draining" in capsys.readouterr().out
    with open(os.path.join(d, "pred_term.txt")) as f:
        lines = f.read().splitlines()
    # partial but nonempty: admission stopped mid-stream, every
    # admitted row drained
    assert 0 < len(lines) < 128
    for ln in lines:
        float(ln)


def test_canary_ingress_keys_registered_in_schema():
    from cxxnet_tpu.analysis import schema
    reg = schema.get_registry()
    for key in ("swap_canary_frac", "swap_canary_window",
                "serve_conn_timeout_ms", "serve_max_conns",
                "serve_max_body_bytes"):
        assert reg.recognizes(key), key
    assert schema.suggest("swap_canary_fracc") == "swap_canary_frac"
    assert schema.suggest("serve_max_connss") == "serve_max_conns"
