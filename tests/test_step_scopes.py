"""The step names itself: layer / direction / updater scopes in the traced
program (nnet/network.py, nnet/trainer.py) and host spans on the
profiler's own clock (telemetry/spans.py). One file, so that xdist gives
the profiler sessions one worker."""

import contextlib
import glob
import os
import re
import threading

import numpy as np
import pytest

import jax

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.network import layer_scope
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.telemetry import spans
from cxxnet_tpu.utils.config import parse_config_string

_CONF = """
netconfig=start
layer[0->1] = conv:conv1
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 3
  stride = 2
layer[3->4] = lrn
  local_size = 5
  alpha = 0.001
  beta = 0.75
  knorm = 1
layer[4->5] = batch_norm:bn1
layer[5->6] = flatten
layer[6->7] = fullc:fc1
  nhidden = 10
layer[7->7] = softmax
netconfig=end
input_shape = 3,12,12
batch_size = 8
dev = cpu
eta = 0.1
momentum = 0.9
wd = 0.0001
random_type = gaussian
silent = 1
"""


def _trainer():
    t = NetTrainer()
    for k, v in parse_config_string(_CONF):
        t.set_param(k, v)
    t.init_model()
    return t


def _batch():
    rng = np.random.default_rng(0)
    return DataBatch(
        data=rng.normal(size=(8, 3, 12, 12)).astype(np.float32),
        label=rng.integers(0, 10, size=(8, 1)).astype(np.float32))


@pytest.fixture(scope="module")
def trainer():
    return _trainer()


@pytest.fixture(scope="module")
def staged(trainer):
    return trainer.stage_batch(_batch())


def test_every_layer_is_named_in_both_directions(trainer, staged):
    compiled = trainer.step_hlo(staged)
    names = set(re.findall(r'op_name="([^"]*)"', compiled))
    for marker in ("/jvp(conv.conv1)/", "/transpose(jvp(max_pooling.",
                   "/update/conv1/", "/update/fc1/", "/update/bn1/"):
        assert any(marker in n for n in names), marker
    # before XLA drops what it can (a flatten is a bitcast), every
    # layer of the conf stands in the traced step forward and backward
    traced = trainer._train_step.lower(
        trainer.state, staged.data, staged.extras, staged.labels,
        staged.mask, jax.random.PRNGKey(0)).as_text(debug_info=True)
    cfg = trainer.net_cfg
    scopes = [layer_scope(cfg, i) for i in range(len(cfg.layers))]
    assert scopes == ["conv.conv1", "relu.layer_1", "max_pooling.layer_2",
                      "lrn.layer_3", "batch_norm.bn1", "flatten.layer_5",
                      "fullc.fc1", "softmax.layer_7"]
    for scope in scopes:
        assert f"/jvp({scope})/" in traced, scope
        assert f"/transpose(jvp({scope}))/" in traced, scope


def _instructions(hlo_text):
    """The module's computations without their metadata: the tables of
    files and stack frames ahead of the first computation go, and every
    `metadata={...}`."""
    body = hlo_text[hlo_text.index("\n%"):]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", body)


def test_scopes_change_no_instruction(trainer, staged, monkeypatch):
    with_scopes = trainer.step_hlo(staged)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _trainer()
    without = bare.step_hlo(bare.stage_batch(_batch()))
    assert "jvp(conv.conv1)" in with_scopes
    assert "conv.conv1" not in without and "/update/" not in without
    assert _instructions(with_scopes) == _instructions(without)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            out.extend((line.name, ev.name, ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats))
                       for ev in line.events
                       if ev.name.split(".")[0] in ("train", "eval", "io",
                                                    "serve"))
    return out


@contextlib.contextmanager
def _traced(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_host_spans_nest_under_the_step_span(trainer, tmp_path):
    batch = _batch()
    trainer.update(batch)                  # nothing compiles in the trace
    jax.block_until_ready(trainer.state)
    first = trainer._step_counter

    # no trace: the same calls leave no file and no thread behind
    threads = {t.ident for t in threading.enumerate()}
    for _ in range(3):
        trainer.update(batch)
    jax.block_until_ready(trainer.state)
    assert {t.ident for t in threading.enumerate()} == threads
    assert list(tmp_path.iterdir()) == []

    with _traced(tmp_path):
        for _ in range(3):
            trainer.update(batch)
        jax.block_until_ready(trainer.state)
    got = _host_spans(str(tmp_path))
    steps = [s for s in got if s[1] == spans.TRAIN]
    assert [s[4]["step_num"] for s in steps] == [first + 3 + i
                                                  for i in range(3)]
    for name in (spans.TRAIN_STAGE, spans.TRAIN_KEY, spans.TRAIN_CALL):
        inner = [s for s in got if s[1] == name]
        assert len(inner) == 3, name
        for step, span in zip(steps, inner):
            assert span[0] == step[0]              # the same thread
            assert step[2] <= span[2] and span[3] <= step[3]
    # check_nan is off in this conf: no guard read-back, no span for it
    assert not [s for s in got if s[1] == spans.TRAIN_GUARD]


_SHARED = """
netconfig=start
layer[0->a] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[0->b] = share[fc1]
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


def test_a_shared_layer_is_two_names():
    t = NetTrainer()
    for k, v in parse_config_string(_SHARED):
        t.set_param(k, v)
    t.init_model()
    cfg = t.net_cfg
    assert [layer_scope(cfg, i) for i in range(2)] == ["fullc.fc1",
                                                       "fullc.layer_1"]
    rng = np.random.default_rng(0)
    staged = t.stage_batch(DataBatch(
        data=rng.normal(size=(8, 1, 1, 12)).astype(np.float32),
        label=rng.integers(0, 3, size=(8, 1)).astype(np.float32)))
    names = set(re.findall(r'op_name="([^"]*)"', t.step_hlo(staged)))
    for marker in ("jvp(fullc.fc1)", "jvp(fullc.layer_1)",
                   "transpose(jvp(fullc.layer_1))", "/update/fc1/"):
        assert any(marker in n for n in names), marker
    # one set of weights, one update
    assert not any("/update/layer_1/" in n for n in names)


def test_the_flight_entry_carries_the_span_s_step(trainer):
    from cxxnet_tpu import telemetry
    tel = telemetry.get()
    tel.flight.arm()
    try:
        step = trainer._step_counter
        trainer.update(_batch())
        entry = tel.flight.tail(1)[-1]
    finally:
        telemetry.reset_for_tests()
    assert (entry["kind"], entry["step"]) == (spans.TRAIN, step)


class _SlowIter:
    """Three batches, each 50 ms late: the consumer always waits."""

    def __init__(self):
        self.i = -1

    def before_first(self):
        self.i = -1

    def next(self):
        import time
        time.sleep(0.05)
        self.i += 1
        return self.i < 3

    def value(self):
        return self.i


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_the_prefetcher_s_wait_is_a_span(tmp_path):
    from cxxnet_tpu.io.prefetch import StagedPrefetcher
    pf = StagedPrefetcher(lambda b: b, _SlowIter(), depth=1)
    with _traced(tmp_path):
        pf.before_first()
        got = []
        while pf.next():
            got.append(pf.value())
    pf.close()
    assert got == [0, 1, 2]
    waits = [s for s in _host_spans(str(tmp_path)) if s[1] == spans.IO_WAIT]
    # every get found the queue empty, the end of the pass too
    assert 3 <= len(waits) <= 4
    assert all(s[3] - s[2] > 10e6 for s in waits[:3])      # ns


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_served_batch_is_a_span_with_its_bucket(trainer, tmp_path):
    from cxxnet_tpu.serve.server import Server
    srv = Server(trainer, max_batch=4, max_wait_ms=1.0)
    srv.warmup()
    srv.start()
    try:
        with _traced(tmp_path):
            rows = np.zeros((3, 3, 12, 12), np.float32)
            out = srv.submit(rows).result(timeout=120)
    finally:
        srv.stop()
    assert out.shape[0] == 3
    batches = [s for s in _host_spans(str(tmp_path))
               if s[1] == spans.SERVE_BATCH]
    assert [s[4]["bucket"] for s in batches] == [4]
