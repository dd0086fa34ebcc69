"""Set-up measured where it happens: the `setup.*` spans of
NetTrainer.init_model() and of each train-step shape's first dispatch,
JAX's build counters inside them (telemetry/jax_watch.py), and the
`/executables` entries they stamp. A tiny net on the CPU."""

import numpy as np
import pytest

from cxxnet_tpu import telemetry
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.telemetry import jax_watch, spans
from cxxnet_tpu.telemetry.registry import MetricsRegistry
from cxxnet_tpu.utils.config import parse_config_string

_CONF = """
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 16
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,12
batch_size = 8
dev = cpu
eta = 0.1
silent = 1
"""

KEYS = {"name", "parent", "start", "end", "trace_s", "lower_s",
        "compile_s", "cache_load_s", "traces", "cache_hits",
        "cache_misses"}


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return DataBatch(
        data=rng.normal(size=(8, 1, 1, 12)).astype(np.float32),
        label=rng.integers(0, 4, size=(8, 1)).astype(np.float32))


@pytest.fixture(scope="module")
def run():
    """init_model(), two update() calls, then one update_chunk() of
    two batches: the records after each stage and the registry."""
    # an executable's fingerprint is its site and shape: another net's
    # train_step@b8 from an earlier test must not hold the entry
    telemetry.reset_for_tests()
    t = NetTrainer()
    for k, v in parse_config_string(_CONF):
        t.set_param(k, v)
    t.init_model()
    t.update(_batch(0))
    after_first = t.fetch_setup()
    t.update(_batch(1))
    after_second = t.fetch_setup()
    t.update_chunk([_batch(2), _batch(3)])
    entries = {e["name"]: e for e in telemetry.executables().snapshot()}
    return t, after_first, after_second, t.fetch_setup(), entries


def test_init_model_is_one_span_with_three_children_in_order(run):
    _, _, recs, _, _ = run
    assert [(r["name"], r["parent"]) for r in recs] == [
        (spans.SETUP_INIT, None),
        (spans.SETUP_BUILD, spans.SETUP_INIT),
        (spans.SETUP_PARAMS, spans.SETUP_INIT),
        (spans.SETUP_STATE, spans.SETUP_INIT),
        (spans.SETUP_STEP, None)]
    init, build, params, state, step = recs
    assert init["start"] <= build["start"] < build["end"] <= params["start"]
    assert params["end"] <= state["start"] < state["end"] <= init["end"]
    assert init["end"] <= step["start"] < step["end"]
    # a child's counters are inside its parent's
    for field in ("traces", "trace_s", "lower_s", "compile_s"):
        assert sum(r[field] for r in (build, params, state)) <= init[field]


def test_one_step_span_a_shape_and_none_for_a_later_call(run):
    _, first, second, _, _ = run
    assert first == second      # the second update() records nothing
    assert [r["name"] for r in first].count(spans.SETUP_STEP) == 1


def test_a_cold_jit_traces_and_the_phases_fit_the_span(run):
    _, _, recs, _, _ = run
    step = recs[-1]
    assert step["traces"] > 0 and step["trace_s"] > 0
    assert step["lower_s"] > 0 and step["compile_s"] > 0
    # phases are counted without what ran inside them: never more
    # than the span's own wall time
    phases = (step["trace_s"] + step["lower_s"] + step["compile_s"]
              + step["cache_load_s"])
    assert phases <= step["end"] - step["start"]


def test_fetch_setup_has_the_documented_keys_and_is_a_copy(run):
    t, _, _, recs, _ = run
    assert all(set(r) == KEYS for r in recs)
    recs[0]["name"] = "changed"
    assert t.fetch_setup()[0]["name"] == spans.SETUP_INIT


def test_the_chunk_shape_is_a_step_span_of_its_own(run):
    _, _, _, recs, _ = run
    steps = [r for r in recs if r["name"] == spans.SETUP_STEP]
    assert len(steps) == 2 and steps[1]["traces"] > 0


def test_executables_carry_the_compile_seconds_of_their_step_span(run):
    _, _, _, recs, entries = run
    steps = [r for r in recs if r["name"] == spans.SETUP_STEP]
    for name, rec in zip(("train_step@b8", "train_chunk@K2b8"), steps):
        assert entries[name]["compile_s"] == pytest.approx(
            rec["compile_s"] + rec["cache_load_s"], abs=1e-6)
        assert entries[name]["compile_s"] > 0


def test_nested_phases_are_counted_once():
    """A trace inside a trace, and a cache read inside a compile, as JAX
    reports them: a start mark, then the duration at the end."""
    reg = MetricsRegistry()
    lis = jax_watch._Listener(lambda: reg)
    lis.started(jax_watch.TRACE_EVENT, 0.0)
    lis.started(jax_watch.TRACE_EVENT, 0.0)
    lis.ended(jax_watch.TRACE_EVENT, 0.2)
    lis.ended(jax_watch.TRACE_EVENT, 0.5)
    lis.started(jax_watch.COMPILE_EVENT, 0.0)
    lis.ended(jax_watch.CACHE_LOAD_EVENT, 0.3)
    lis.counted(jax_watch.CACHE_HIT_EVENT)
    lis.ended(jax_watch.COMPILE_EVENT, 0.4)
    lis.ended("/jax/some/other_duration", 9.0)
    got = jax_watch.totals(reg)
    assert got["trace_s"] == pytest.approx(0.5) and got["traces"] == 2
    assert got["compile_s"] == pytest.approx(0.1)
    assert got["cache_load_s"] == pytest.approx(0.3)
    assert (got["cache_hits"], got["cache_misses"], got["lower_s"]) == (1, 0, 0)
