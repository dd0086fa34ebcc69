"""Telemetry subsystem tests: metrics registry math, span nesting,
JSONL sink round-trip, StepProfiler percentiles/trace_round, retry
routing, and the end-to-end acceptance run (train with sinks armed ->
valid streams -> metrics_report renders)."""

import json
import math
import threading
import time

import numpy as np
import pytest

from cxxnet_tpu import telemetry
from cxxnet_tpu.telemetry import Telemetry
from cxxnet_tpu.telemetry.registry import (
    Counter, Gauge, Histogram, MetricsRegistry)
from cxxnet_tpu.telemetry.sink import format_record, read_jsonl
from cxxnet_tpu.utils.profiler import StepProfiler


@pytest.fixture(autouse=True)
def _clean_singleton():
    """Every test starts and ends with the process-wide telemetry in
    the disabled state with an empty registry."""
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_counter_gauge_basics():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge()
    g.set(3)
    g.set(1.5)
    assert g.value == 1.5


def test_histogram_percentile_math():
    h = Histogram()
    for v in range(1, 101):  # 1..100
        h.observe(float(v))
    assert h.count == 100
    assert h.sum == pytest.approx(5050.0)
    assert h.min == 1.0 and h.max == 100.0
    # numpy's linear-interpolation percentiles are the reference
    vals = np.arange(1, 101, dtype=np.float64)
    assert h.percentile(50) == pytest.approx(np.percentile(vals, 50))
    assert h.percentile(99) == pytest.approx(np.percentile(vals, 99))
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(np.percentile(vals, 50))
    assert snap["p99"] == pytest.approx(np.percentile(vals, 99))
    assert snap["mean"] == pytest.approx(50.5)


def test_histogram_empty_and_single():
    h = Histogram()
    assert math.isnan(h.percentile(50))
    assert h.snapshot()["p50"] is None
    h.observe(2.0)
    assert h.percentile(50) == 2.0
    assert h.percentile(99) == 2.0


def test_histogram_window_bounds_memory():
    h = Histogram(window=8)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100          # exact over the full stream
    assert h.max == 99.0
    assert h.percentile(0) >= 92.0  # window keeps only the newest 8


def test_registry_idempotent_and_type_checked():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    with pytest.raises(TypeError):
        r.gauge("a")
    r.counter("a").inc(2)
    r.gauge("b").set(1.0)
    r.histogram("c").observe(0.5)
    snap = r.snapshot()
    assert snap["a"] == 2 and snap["b"] == 1.0
    assert snap["c"]["count"] == 1


def test_registry_thread_safety():
    r = MetricsRegistry()

    def work():
        for _ in range(1000):
            r.counter("n").inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.counter("n").value == 8000


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_unarmed_keeps_its_record_and_emits_no_event():
    tel = Telemetry()
    assert not tel.enabled
    with tel.span("a") as outer:
        with tel.span("b") as inner:
            assert inner["end"] is None                 # still open
    assert (outer["name"], outer["parent"]) == ("a", None)
    assert (inner["name"], inner["parent"]) == ("b", "a")
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]
    # JAX's build counters ride on the record as deltas
    assert {"trace_s", "lower_s", "compile_s", "cache_load_s", "traces",
            "cache_hits", "cache_misses"} <= set(outer)
    assert tel.registry.get("a").count == 1          # histogram observed
    assert tel.registry.get("a/b").count == 1
    assert tel.recent_spans() == []                  # no event unarmed


def test_span_nesting_records_paths(tmp_path):
    tel = Telemetry()
    log = str(tmp_path / "ev.jsonl")
    tel.configure(log_file=log)
    with tel.span("round"):
        with tel.span("step", idx=3):
            time.sleep(0.01)
        with tel.span("step"):
            pass
    tel.close()
    assert tel.registry.get("round/step").count == 2
    assert tel.registry.get("round").count == 1
    assert tel.registry.get("round/step").sum >= 0.01
    events = list(read_jsonl(log))
    spans = [e for e in events if e["kind"] == "span"]
    assert [s["name"] for s in spans] == ["round/step", "round/step",
                                          "round"]
    assert spans[0]["idx"] == 3  # extra fields ride on the event
    assert all(s["secs"] >= 0 for s in spans)


# ---------------------------------------------------------------------------
# sinks / central logger
# ---------------------------------------------------------------------------
def test_jsonl_round_trip_with_tags(tmp_path):
    tel = Telemetry()
    log = str(tmp_path / "ev.jsonl")
    met = str(tmp_path / "me.jsonl")
    tel.configure(log_file=log, metrics_file=met,
                  tags={"device": "cpu"})
    tel.inc("fault.retry", 2)
    tel.observe("train.step_s", 0.25)
    tel.event("checkpoint", op="save", round=3, secs=0.5, bytes=123)
    tel.emit_metrics(kind="round", round=3)
    tel.close()
    events = list(read_jsonl(log))
    assert len(events) == 1
    e = events[0]
    assert e["kind"] == "checkpoint" and e["op"] == "save"
    assert e["bytes"] == 123
    for tag in ("ts", "host", "pid", "proc", "device"):
        assert tag in e
    recs = list(read_jsonl(met))
    assert len(recs) == 1
    m = recs[0]["metrics"]
    assert m["fault.retry"] == 2
    assert m["train.step_s"]["count"] == 1
    assert recs[0]["round"] == 3


def test_jsonl_skips_torn_last_line(tmp_path):
    p = tmp_path / "m.jsonl"
    p.write_text(json.dumps({"kind": "round", "round": 1}) +
                 '\n{"kind": "round", "rou')  # killed mid-write
    recs = list(read_jsonl(str(p)))
    assert len(recs) == 1 and recs[0]["round"] == 1


def test_json_sanitizes_non_finite_floats(tmp_path):
    """A diverging run's NaN loss must not poison the stream: bare
    NaN/Infinity tokens are invalid JSON (rejected by jq/strict
    parsers); the sink writes null instead."""
    tel = Telemetry()
    log = str(tmp_path / "ev.jsonl")
    met = str(tmp_path / "me.jsonl")
    tel.configure(log_file=log, metrics_file=met)
    tel.set_gauge("train.loss", float("nan"))
    tel.event("span", name="train.step", secs=0.1, loss=float("nan"),
              ips=float("inf"), np_nan=np.float32("nan"))
    tel.emit_metrics(kind="final")
    tel.close()
    for path in (log, met):
        for line in open(path):
            assert "NaN" not in line and "Infinity" not in line
            json.loads(line)  # strictly valid
    ev = list(read_jsonl(log))[0]
    assert ev["loss"] is None and ev["ips"] is None
    assert ev["np_nan"] is None
    snap = list(read_jsonl(met))[0]["metrics"]
    assert snap["train.loss"] is None


def test_metrics_report_deltas_survive_resume(tmp_path):
    """Append-mode streams restart counters at 0 when a resumed
    process takes over; per-round deltas must be tracked per process,
    not across the reset (negative or under-counted deltas)."""
    from cxxnet_tpu.tools.metrics_report import aggregate
    p = tmp_path / "m.jsonl"
    recs = [
        # first process: 6 saves, 5 retries by its last round
        {"kind": "round", "host": "h", "pid": 1, "round": 1,
         "metrics": {"checkpoint.saves": 6, "fault.retry": 5}},
        # resumed process: fresh counters, 7 retries before round 2
        {"kind": "round", "host": "h", "pid": 2, "round": 2,
         "metrics": {"checkpoint.saves": 0, "fault.retry": 7}},
        {"kind": "round", "host": "h", "pid": 2, "round": 3,
         "metrics": {"checkpoint.saves": 1, "fault.retry": 7}},
    ]
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    rows = aggregate(str(p))["rounds"]
    assert [r["retries"] for r in rows] == [5, 7, 0]
    assert [r["saves"] for r in rows] == [6, 0, 1]


def test_sink_io_failure_never_raises(tmp_path, capfd):
    """ENOSPC/NFS blips on the stream file must not abort training:
    the sink disables itself (noted once on stderr) and later writes
    are silent no-ops."""
    from cxxnet_tpu.telemetry.sink import LineSink
    sink = LineSink(str(tmp_path / "ev.jsonl"))
    sink._f.close()  # simulate the handle dying under the sink
    sink.write({"kind": "x"})   # must not raise
    sink.write({"kind": "y"})
    sink.flush()
    sink.close()
    assert "telemetry: disabling sink" in capfd.readouterr().err


def test_metrics_report_multiproc_finals_and_rounds(tmp_path, capfd):
    """Merged multi-process streams: finals are reported per process
    (one last-wins snapshot would silently drop the other hosts'
    counters) and the round table grows a proc column."""
    from cxxnet_tpu.tools.metrics_report import aggregate, render
    p = tmp_path / "m.jsonl"
    recs = [
        {"kind": "round", "host": "a", "pid": 1, "round": 1,
         "steps": 2, "examples": 64, "images_per_sec": 10.0,
         "step_p50_ms": 1.0, "step_p99_ms": 2.0, "data_total_ms": 3.0,
         "metrics": {"fault.retry": 2}},
        {"kind": "round", "host": "b", "pid": 2, "round": 1,
         "steps": 2, "examples": 64, "images_per_sec": 11.0,
         "step_p50_ms": 1.0, "step_p99_ms": 2.0, "data_total_ms": 3.0,
         "metrics": {"fault.retry": 1}},
        {"kind": "final", "host": "a", "pid": 1,
         "metrics": {"fault.retry": 3}},
        {"kind": "final", "host": "b", "pid": 2,
         "metrics": {"fault.retry": 4}},
    ]
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    agg = aggregate(str(p))
    assert [r["retries"] for r in agg["rounds"]] == [2, 1]
    assert agg["finals"]["a/1"]["fault.retry"] == 3
    assert agg["finals"]["b/2"]["fault.retry"] == 4
    out = render(agg)
    assert "final counters/gauges [a/1]:" in out
    assert "final counters/gauges [b/2]:" in out
    assert "proc" in out.splitlines()[1]  # proc column in the table


def test_text_format_renders_fields():
    line = format_record({"ts": 12.0, "kind": "eval", "round": 2,
                          "values": {"test-error": 0.1}}, "text")
    assert line.startswith("12.000 eval")
    assert "round=2" in line and "test-error" in line


def test_log_format_validation(tmp_path):
    tel = Telemetry()
    with pytest.raises(ValueError):
        tel.configure(log_file=str(tmp_path / "x"), log_format="xml")


def test_stdout_stderr_passthrough_and_mirror(tmp_path, capfd):
    tel = Telemetry()
    log = str(tmp_path / "ev.jsonl")
    tel.configure(log_file=log)
    tel.stdout("hello out")
    tel.stderr("[1]\ttest-error:0.5\n", event_kind="eval", round=1,
               values={"test-error": 0.5})
    tel.stderr("plain line\n")
    tel.close()
    out, err = capfd.readouterr()
    assert out == "hello out\n"
    assert err == "[1]\ttest-error:0.5\nplain line\n"  # byte-exact
    events = list(read_jsonl(log))
    kinds = [e["kind"] for e in events]
    assert kinds == ["log", "eval", "log"]
    assert events[1]["values"]["test-error"] == 0.5


def test_disabled_telemetry_writes_no_files(tmp_path, capfd):
    tel = Telemetry()
    tel.stderr("text\n")
    tel.event("x", a=1)
    tel.emit_metrics()
    assert capfd.readouterr().err == "text\n"
    assert list(tmp_path.iterdir()) == []


def test_heartbeat_emits_periodic_snapshots(tmp_path):
    tel = Telemetry()
    met = str(tmp_path / "hb.jsonl")
    tel.configure(metrics_file=met, heartbeat_secs=0.05)
    tel.inc("beats.seen")
    time.sleep(0.18)
    tel.close()
    hb = [r for r in read_jsonl(met) if r["kind"] == "heartbeat"]
    assert len(hb) >= 2
    assert hb[-1]["metrics"]["beats.seen"] == 1


def test_configure_is_idempotent_and_closes_previous(tmp_path):
    tel = Telemetry()
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    tel.configure(log_file=a)
    tel.event("one")
    tel.configure(log_file=b)
    tel.event("two")
    tel.configure()  # disarm
    tel.event("three")
    assert [e["kind"] for e in read_jsonl(a)] == ["one"]
    assert [e["kind"] for e in read_jsonl(b)] == ["two"]


# ---------------------------------------------------------------------------
# StepProfiler
# ---------------------------------------------------------------------------
def test_profiler_percentile_and_ips_math():
    p = StepProfiler()
    p.round_start()
    steps = [0.010, 0.020, 0.030, 0.040]
    for s in steps:
        p.add_step(s, 32)
    p.add_data(0.100)
    st = p.stats()
    assert st["steps"] == 4 and st["examples"] == 128
    assert st["step_p50_ms"] == pytest.approx(
        np.percentile(steps, 50) * 1e3)
    assert st["step_p99_ms"] == pytest.approx(
        np.percentile(steps, 99) * 1e3)
    assert st["data_total_ms"] == pytest.approx(100.0)
    assert st["images_per_sec"] == pytest.approx(128 / 0.2)
    assert "images/sec" in p.summary()


def test_profiler_zero_step_summary_robust():
    p = StepProfiler()
    assert p.stats() is None
    assert p.summary() == "\tprofile: no steps"
    # steps but EMPTY data_s (staged/membuffer rounds): must not crash
    p.add_step(0.01, 0)
    st = p.stats()
    assert st["data_total_ms"] == 0.0
    assert math.isnan(st["images_per_sec"]) or st["images_per_sec"] >= 0
    assert "profile: 1 steps" in p.summary()


def test_profiler_trace_round_selects_round(monkeypatch, tmp_path):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    p = StepProfiler(str(tmp_path), trace_round=3)
    for _ in range(5):
        p.round_start()
        p.add_step(0.01, 1)
        p.round_end()
    # traced exactly once, on profiled round 3
    assert calls == [("start", str(tmp_path)), ("stop", None)]
    assert p._round_idx == 5 and p._traced_once


def test_profiler_default_traces_first_round(monkeypatch, tmp_path):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    p = StepProfiler(str(tmp_path))
    p.round_start()
    assert calls == ["start"]
    p.round_end()
    p.round_start()
    p.round_end()
    assert calls == ["start", "stop"]


# ---------------------------------------------------------------------------
# fault routing
# ---------------------------------------------------------------------------
def test_retry_warning_routes_through_telemetry(tmp_path, capfd):
    from cxxnet_tpu.utils.fault import retry
    log = str(tmp_path / "ev.jsonl")
    telemetry.configure(log_file=log)
    attempts = []

    @retry(attempts=3, backoff=0.0, jitter=0.0)
    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise OSError("transient")
        return "ok"

    assert flaky() == "ok"
    telemetry.close()
    err = capfd.readouterr().err
    # exact pre-telemetry stderr text preserved
    assert err.count("retry: ") == 2
    assert "(attempt 1/3: OSError: transient); retrying in 0.00s" in err
    assert telemetry.counter("fault.retry").value == 2
    faults = [e for e in read_jsonl(log) if e["kind"] == "fault"]
    assert len(faults) == 2
    assert all(f["type"] == "retry" for f in faults)


def test_retry_iterator_counts_io_retries(tmp_path, capfd):
    from cxxnet_tpu.io.iterators import DataIter, RetryIterator
    from cxxnet_tpu.utils import fault

    class Once(DataIter):
        def __init__(self):
            self.n = 0

        def before_first(self):
            self.n = 0

        def next(self):
            self.n += 1
            return self.n <= 2

        def value(self):
            return self.n

    it = RetryIterator(Once())
    it.set_param("io_retry_backoff", "0.0")
    fault.clear()
    fault.inject("io.next", "ioerror", at=1)
    try:
        it.before_first()
        served = sum(1 for _ in iter(lambda: it.next(), False))
    finally:
        fault.clear()
    assert served == 2
    assert telemetry.counter("io.retry").value == 1
    assert telemetry.counter("fault.retry").value == 1
    assert "retry: " in capfd.readouterr().err


# ---------------------------------------------------------------------------
# end-to-end acceptance: train with sinks armed -> streams -> report
# ---------------------------------------------------------------------------
def test_telemetry_steps_opt_out(tmp_path, capfd):
    """telemetry_steps=0 keeps event logging (checkpoint/eval/fault)
    but drops the per-step spans and their device-sync cost."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.tools.telemetry_smoke import CONF, write_synth_mnist
    d = str(tmp_path)
    write_synth_mnist(d, 256, 0, "train")
    write_synth_mnist(d, 64, 1, "test")
    conf = tmp_path / "t.conf"
    conf.write_text(CONF.format(d=d))
    LearnTask().run([str(conf), "telemetry_steps=0", "num_round=1",
                     "max_round=1"])
    capfd.readouterr()
    events = list(read_jsonl(d + "/events.jsonl"))
    # only the once-a-process set-up spans; none a step
    assert all(e["name"].startswith("setup.") for e in events
               if e["kind"] == "span")
    assert any(e["kind"] == "checkpoint" and e.get("op") == "save"
               for e in events)
    assert any(e["kind"] == "eval" for e in events)
    # the round record still rides on the profiler-free path? no -
    # with per-step instrumentation off and profile=0 there is no
    # profiler, so no round stats record is expected
    assert not any(e["kind"] == "round" for e in events)


def test_round_records_include_own_checkpoint_save(tmp_path, capfd):
    """The per-round metrics record is emitted AFTER the round's
    checkpoint save, so metrics_report attributes save deltas to the
    round that paid them (initial save + round-1 save land in round
    1's row)."""
    from cxxnet_tpu.tools.metrics_report import aggregate
    from cxxnet_tpu.tools.telemetry_smoke import run_smoke
    assert run_smoke(str(tmp_path)) == 0
    capfd.readouterr()
    rows = aggregate(str(tmp_path / "metrics.jsonl"))["rounds"]
    assert [r["saves"] for r in rows] == [2, 1]


def test_e2e_train_produces_valid_streams(tmp_path, capfd):
    """The ISSUE acceptance run: 2-round digits training with
    log_file/metrics_file set produces valid JSONL with step/data span
    timings, a checkpoint save duration, and a fault counter, and
    metrics_report renders a per-round summary from it."""
    from cxxnet_tpu.tools.telemetry_smoke import run_smoke
    assert run_smoke(str(tmp_path)) == 0
    out = capfd.readouterr().out
    assert "per-round summary:" in out
    assert "telemetry_smoke: PASS" in out
