"""Fused multi-step dispatch (steps_per_dispatch=K): trajectory
equality is the acceptance proof (docs/PERFORMANCE.md).

A fused chunk must reproduce K streamed updates - same RNG stream
(folded on device from the same (seed, step_counter) pairs), same
divergence-guard decisions, same on-device train-metric accumulator.

Two rigor levels, split by what XLA:CPU promises: it compiles a
contraction per PROGRAM SHAPE, so the per-step executable and the
fused scan of the same math may differ ~1 ULP - backend noise, not a
property of the dispatch path. So the in-process tests assert
trajectory equality to tight tolerance plus EXACT guard/metric/counter
semantics, and the bitwise proof runs in subprocesses of their own on
this MLP, where both executables compile the same contractions. The
CI fused-smoke job (tools/fused_smoke.py) runs the same way.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.io.prefetch import StagedPrefetcher
from cxxnet_tpu.nnet.trainer import NetTrainer, StagedChunk
from cxxnet_tpu.utils.config import parse_config_string

MLP_CFG = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1:ac1] = tanh
layer[ac1->fc2] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.5
momentum = 0.9
wd = 0.0
metric = error
eval_train = 1
silent = 1
"""


def make_trainer(extra=""):
    t = NetTrainer()
    for k, v in parse_config_string(MLP_CFG + extra):
        t.set_param(k, v)
    t.init_model()
    return t


def synth_batches(n_batches=8, batch_size=16, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(8)
    out = []
    for _ in range(n_batches):
        x = rng.randn(batch_size, 8).astype(np.float32)
        y = (x @ w > 0).astype(np.float32)
        out.append(DataBatch(data=x.reshape(batch_size, 1, 1, 8),
                             label=y.reshape(batch_size, 1)))
    return out


class ListIter:
    def __init__(self, batches):
        self.batches = batches
        self.i = -1

    def before_first(self):
        self.i = -1

    def next(self):
        self.i += 1
        return self.i < len(self.batches)

    def value(self):
        return self.batches[self.i]


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the env of the bitwise subprocesses (see module docstring): the
# suite's device count
PARITY_ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    XLA_FLAGS="--xla_force_host_platform_device_count=8")


def params_of(t):
    return jax.tree.leaves(jax.tree.map(np.asarray, t.state["params"]))


def assert_traj_close(a, b, msg=""):
    """In-process equality bar: identical dtypes/shapes, values equal
    to well under any training-visible scale (the residual is
    XLA:CPU's per-program-shape contraction noise; the bitwise bar
    lives in the subprocess tests)."""
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_allclose(x, y, rtol=5e-6, atol=1e-7,
                                   err_msg=msg)


def run_streamed(batches, extra=""):
    t = make_trainer(extra)
    for b in batches:
        t.update(b)
    return t


def run_fused(batches, k, extra=""):
    t = make_trainer(extra + f"steps_per_dispatch = {k}\n")
    for i in range(0, len(batches), k):
        t.update_chunk(batches[i:i + k])
    return t


@pytest.mark.parametrize("k", [1, 2, 4])
def test_fused_trajectory_matches_streamed(k):
    batches = synth_batches(8)
    ta = run_streamed(batches)
    tb = run_fused(batches, k)
    assert_traj_close(params_of(ta), params_of(tb), f"K={k}")
    # identical train-metric accumulator -> identical metric STRING
    assert ta.eval_train_metric() == tb.eval_train_metric()
    assert ta.epoch == tb.epoch
    assert ta._step_counter == tb._step_counter


@pytest.mark.parametrize("k", [2, 4])
def test_fused_update_period_crosses_chunks(k):
    """Grad accumulation (update_period>1) folds into the scan: the
    carried accumulator crosses chunk boundaries exactly as it crosses
    streamed steps."""
    batches = synth_batches(8)
    ta = run_streamed(batches, "update_period = 2\n")
    tb = run_fused(batches, k, "update_period = 2\n")
    assert_traj_close(params_of(ta), params_of(tb), f"up=2 K={k}")
    assert ta.epoch == tb.epoch == 4
    assert ta.eval_train_metric() == tb.eval_train_metric()


def test_fused_short_final_chunk():
    """7 updates at K=4 -> a full chunk + a short (3-step) round-end
    chunk; the scan reads its length from the stacked axis."""
    batches = synth_batches(7)
    ta = run_streamed(batches)
    tb = run_fused(batches, 4)
    assert_traj_close(params_of(ta), params_of(tb), "short tail")
    assert tb._step_counter == 7
    assert ta.eval_train_metric() == tb.eval_train_metric()


def test_fused_nan_guard_drops_exact_microstep(capfd):
    """check_nan=1 with a NaN batch mid-chunk: the in-jit rollback
    drops EXACTLY that microstep; counters, consecutive accounting and
    the guard's stderr line match streaming."""
    batches = synth_batches(8)
    bad = DataBatch(
        data=np.full((16, 1, 1, 8), np.nan, np.float32),
        label=batches[5].label)
    seq = batches[:5] + [bad] + batches[6:]
    ta = run_streamed(seq, "check_nan = 1\n")
    err_streamed = capfd.readouterr().err
    tb = run_fused(seq, 4, "check_nan = 1\n")
    err_fused = capfd.readouterr().err
    assert_traj_close(params_of(ta), params_of(tb), "nan mid-chunk")
    assert ta.bad_rounds == tb.bad_rounds == 1
    assert ta._skipped_steps == tb._skipped_steps == 1
    assert ta.epoch == tb.epoch == 7
    assert "at update 5" in err_streamed
    assert err_fused == err_streamed
    assert ta.eval_train_metric() == tb.eval_train_metric()


def test_fused_divergence_abort_raises():
    """max_bad_rounds consecutive NaN microsteps inside chunks still
    raise DivergenceError (detection may land at the chunk boundary,
    the rollback semantics are per microstep)."""
    from cxxnet_tpu.utils.fault import DivergenceError
    batches = synth_batches(8)
    bad = DataBatch(
        data=np.full((16, 1, 1, 8), np.nan, np.float32),
        label=batches[0].label)
    seq = batches[:2] + [bad, bad, bad] + batches[5:]
    t = make_trainer("check_nan = 1\nsteps_per_dispatch = 4\n")
    with pytest.raises(DivergenceError):
        for i in range(0, len(seq), 4):
            t.update_chunk(seq[i:i + 4])
    assert t.bad_rounds == 3


def test_fused_accepts_staged_batches_and_chunks():
    """stage_chunk accepts StagedBatch/DataBatch mixed; update()
    routes a StagedChunk to update_chunk."""
    batches = synth_batches(4)
    ta = run_streamed(batches)
    tb = make_trainer()
    staged = [tb.stage_batch(b) for b in batches[:2]] + batches[2:]
    chunk = tb.stage_chunk(staged)
    assert isinstance(chunk, StagedChunk)
    assert chunk.n_steps == 4
    assert chunk.n_examples == (16, 16, 16, 16)
    tb.update(chunk)
    assert_traj_close(params_of(ta), params_of(tb), "mixed staging")


def test_fused_empty_chunk_rejected():
    t = make_trainer()
    with pytest.raises(ValueError):
        t.stage_chunk([])
    with pytest.raises(ValueError):
        t.set_param("steps_per_dispatch", "0")


def test_prefetcher_assembles_chunks_with_partial_tail():
    """chunk=K on the staging prefetcher: the worker ships StagedChunk
    items, flushing a SHORT chunk at the end of the pass, and the
    trajectory equals streaming."""
    batches = synth_batches(7)
    ta = run_streamed(batches)
    tb = make_trainer("steps_per_dispatch = 3\n")
    pf = tb.prefetch(ListIter(batches), depth=2, chunk=3)
    sizes = []
    pf.before_first()
    while pf.next():
        sizes.append(pf.value().n_steps)
        tb.update(pf.value())
    pf.close()
    assert sizes == [3, 3, 1]
    assert_traj_close(params_of(ta), params_of(tb), "prefetched chunks")
    assert ta.eval_train_metric() == tb.eval_train_metric()


def test_prefetcher_chunk_requires_chunk_fn():
    with pytest.raises(ValueError):
        StagedPrefetcher(lambda b: b, ListIter([]), chunk=2)


def test_prefetcher_chunk_restart_and_close():
    """before_first() restarts a chunked pass cleanly; close() mid-pass
    does not hang or leak."""
    t = make_trainer()
    pf = t.prefetch(ListIter(synth_batches(6)), depth=1, chunk=2)
    pf.before_first()
    assert pf.next() and pf.value().n_steps == 2
    pf.before_first()  # restart mid-pass
    n = 0
    while pf.next():
        n += pf.value().n_steps
    assert n == 6
    pf.close()
    assert not pf.next()


BITWISE_MATRIX_SCRIPT = r"""
# Bitwise trajectory-equality matrix (see test module docstring).
# Raises on the first mismatch.
import numpy as np, jax
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config_string

CFG = '''
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1:ac1] = tanh
layer[ac1->fc2] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.5
momentum = 0.9
wd = 0.0
metric = error
eval_train = 1
silent = 1
'''

def mk(extra=""):
    t = NetTrainer()
    for k, v in parse_config_string(CFG + extra):
        t.set_param(k, v)
    t.init_model()
    return t

rng = np.random.RandomState(0)
w = rng.randn(8)
batches = []
for _ in range(7):
    x = rng.randn(16, 8).astype(np.float32)
    batches.append(DataBatch(
        data=x.reshape(16, 1, 1, 8),
        label=(x @ w > 0).astype(np.float32).reshape(16, 1)))

def leaves(t):
    return jax.tree.leaves(jax.tree.map(np.asarray, t.state["params"]))

def check(pa, pb, tag):
    for a, b in zip(pa, pb):
        assert a.dtype == b.dtype and np.array_equal(a, b), (
            tag, float(np.abs(a.astype(np.float64)
                              - b.astype(np.float64)).max()))

class ListIter:
    def __init__(self, bs): self.bs, self.i = bs, -1
    def before_first(self): self.i = -1
    def next(self):
        self.i += 1
        return self.i < len(self.bs)
    def value(self): return self.bs[self.i]

for extra, tag in (("", "plain"), ("update_period = 2\n", "up2")):
    ta = mk(extra)
    for b in batches:
        ta.update(b)
    pa = leaves(ta)
    ma = ta.eval_train_metric()
    for K in (1, 2, 4):  # 7 batches -> short final chunk every time
        tb = mk(extra + f"steps_per_dispatch = {K}\n")
        for i in range(0, 7, K):
            tb.update_chunk(batches[i:i + K])
        check(pa, leaves(tb), f"{tag} K={K}")
        assert tb.eval_train_metric() == ma, (tag, K)

# NaN mid-chunk under the divergence guard
bad = DataBatch(data=np.full((16, 1, 1, 8), np.nan, np.float32),
                label=batches[5].label)
seq = batches[:5] + [bad] + batches[6:]
ta = mk("check_nan = 1\n")
for b in seq:
    ta.update(b)
tb = mk("check_nan = 1\nsteps_per_dispatch = 4\n")
for i in range(0, 7, 4):
    tb.update_chunk(seq[i:i + 4])
check(leaves(ta), leaves(tb), "nan")
assert ta.bad_rounds == tb.bad_rounds == 1

# prefetcher-assembled chunks (worker staging + partial tail)
ta = mk()
for b in batches:
    ta.update(b)
tb = mk("steps_per_dispatch = 3\n")
pf = tb.prefetch(ListIter(batches), depth=2, chunk=3)
pf.before_first()
sizes = []
while pf.next():
    sizes.append(pf.value().n_steps)
    tb.update(pf.value())
pf.close()
assert sizes == [3, 3, 1], sizes
check(leaves(ta), leaves(tb), "prefetched")
print("BITWISE-OK")
"""


def test_fused_trajectory_bitwise_exact():
    """THE acceptance proof: the fused trajectory is bit-for-bit the
    streamed one - K in {1,2,4}, grad accumulation, NaN-guard
    mid-chunk, short final chunks, and worker-assembled (prefetched)
    chunks."""
    r = subprocess.run(
        [sys.executable, "-c", BITWISE_MATRIX_SCRIPT], env=PARITY_ENV,
        cwd=REPO, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"\nstdout:{r.stdout}\nstderr:{r.stderr}"
    assert "BITWISE-OK" in r.stdout


def test_cli_fused_vs_streamed_checkpoint_identical(tmp_path):
    """The CI smoke assertion: a K=4 CLI run's final checkpoint is
    byte-identical to the K=1 run's, and the per-round eval lines
    match (subprocesses under the deterministic-codegen env)."""
    from test_cli import write_conf, write_synth_mnist
    tr = write_synth_mnist(tmp_path, n=256, seed=0, prefix="train")
    te = write_synth_mnist(tmp_path, n=64, seed=1, prefix="test")
    conf = write_conf(tmp_path, *tr, *te, extra="num_round = 3\n")

    def run(k, tag):
        mdir = tmp_path / f"models_{tag}"
        r = subprocess.run(
            [sys.executable, "-m", "cxxnet_tpu.main", conf,
             f"model_dir={mdir}", f"steps_per_dispatch={k}"],
            env=PARITY_ENV, cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert r.returncode == 0, r.stderr
        with open(mdir / "0003.model", "rb") as f:
            blob = f.read()
        evals = [l for l in r.stderr.splitlines() if l.startswith("[")]
        return blob, evals

    blob1, evals1 = run(1, "k1")
    blob4, evals4 = run(4, "k4")
    assert blob1 == blob4
    assert evals1 == evals4 and len(evals1) == 3


def test_wrapper_honors_steps_per_dispatch():
    """The numpy-wrapper train() wires steps_per_dispatch through both
    its paths (device-resident chunk stacking and the chunked
    prefetcher) - the knob must not be CLI-only."""
    from cxxnet_tpu import wrapper
    cfg = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:a1] = tanh
layer[a1->fc2] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
eta = 0.5
metric = error
"""
    rng = np.random.RandomState(0)
    w = rng.randn(8)
    x = rng.randn(96, 8).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)

    def preds(net):
        return np.concatenate(
            [net.predict(x[i:i + 16].reshape(-1, 1, 1, 8))
             for i in range(0, 96, 16)])

    n1 = wrapper.train(cfg, x.reshape(-1, 1, 1, 8), y, 3,
                       {"silent": "1"}, batch_size=16)
    n2 = wrapper.train(cfg, x.reshape(-1, 1, 1, 8), y, 3,
                       {"silent": "1", "steps_per_dispatch": "3"},
                       batch_size=16)
    assert np.array_equal(preds(n1), preds(n2))
    old = wrapper._STAGE_BYTES_LIMIT
    wrapper._STAGE_BYTES_LIMIT = 0  # force the streaming/prefetch path
    try:
        n3 = wrapper.train(cfg, x.reshape(-1, 1, 1, 8), y, 3,
                           {"silent": "1", "steps_per_dispatch": "3"},
                           batch_size=16)
    finally:
        wrapper._STAGE_BYTES_LIMIT = old
    assert np.array_equal(preds(n1), preds(n3))


def test_eval_inflight_config():
    """eval_inflight=N bounds the eval loop's in-flight staging; any
    value (including 0 = never sync) yields the same metrics."""
    batches = synth_batches(6)
    ta = run_streamed(batches)
    base = ta.evaluate(ListIter(batches), "eval")
    for v in ("1", "2", "0"):
        ta.set_param("eval_inflight", v)
        assert ta.evaluate(ListIter(batches), "eval") == base
    with pytest.raises(ValueError):
        ta.set_param("eval_inflight", "-1")


def test_profiler_add_chunk_per_step_stats():
    from cxxnet_tpu.utils.profiler import StepProfiler
    p = StepProfiler()
    p.round_start()
    p.add_chunk(0.4, 4, 64)
    p.add_chunk(0.1, 1, 16)
    st = p.stats()
    assert st["steps"] == 5
    assert st["examples"] == 80
    assert abs(st["step_total_s"] - 0.5) < 1e-9
    assert abs(st["step_p50_ms"] - 100.0) < 1e-6


def test_fused_telemetry_chunk_span(tmp_path):
    """Fused updates emit one train.chunk span per dispatch carrying
    the per-microstep loss vector; the step-time histogram keeps
    per-STEP scale (K amortized observations per chunk)."""
    from cxxnet_tpu import telemetry
    from cxxnet_tpu.telemetry.sink import read_jsonl
    log = str(tmp_path / "ev.jsonl")
    tel = telemetry.get()
    tel.configure(log_file=log)
    try:
        # deltas, not absolutes: the registry is process-global and
        # other tests in the session may already have fed it
        img0 = tel.registry.counter("train.images").value
        cnt0 = tel.registry.histogram("train.step_s").count
        batches = synth_batches(4)
        t = make_trainer("steps_per_dispatch = 4\n")
        t.update_chunk(batches)
        assert tel.registry.counter("train.images").value - img0 == 64
        assert tel.registry.histogram("train.step_s").count - cnt0 == 4
    finally:
        tel.close()
    chunks = [e for e in read_jsonl(log)
              if e.get("name") == "train.chunk"]
    assert len(chunks) == 1
    assert chunks[0]["steps"] == 4
    assert len(chunks[0]["loss"]) == 4
    assert chunks[0]["examples"] == 64
