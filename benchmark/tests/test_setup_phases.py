"""`tools/setup_phases.py` on made-up set-up span records: the rows add
up to the harness's `setup_s`, and the span sums count a child once; the
five set-up readers give the same sums, and nothing without records."""

import os
from types import SimpleNamespace

import pytest

from benchmark import run as bench, setup_spans
from benchmark.tools import setup_phases as sp


def rec(name, parent, start, end, trace=0.0, lower=0.0, compile_=0.0,
        load=0.0, traces=0):
    return {"name": name, "parent": parent, "start": start, "end": end,
            "trace_s": trace, "lower_s": lower, "compile_s": compile_,
            "cache_load_s": load, "traces": traces, "cache_hits": 0,
            "cache_misses": 0}


RECORDS = [
    rec("setup.init", None, 10.0, 18.0, 1.0, 0.5, 0.2, 2.0, 300),
    rec("setup.build", "setup.init", 10.1, 10.6, 0.4, 0.1, 0.0, 0.0, 100),
    rec("setup.params", "setup.init", 10.6, 17.0, 0.5, 0.3, 0.2, 1.5, 150),
    rec("setup.state", "setup.init", 17.0, 17.9, 0.1, 0.1, 0.0, 0.5, 50),
    rec("setup.step", None, 19.0, 23.0, 2.0, 0.8, 0.1, 0.9, 700),
]


def test_the_rows_add_up_to_setup_s():
    t_start, runtime_s, prep_end, win_t0 = 1.0, 4.0, 25.5, 25.6
    rows = sp.phases(t_start, runtime_s, prep_end, win_t0, RECORDS)
    got = dict(rows)
    assert sum(v for _, v in rows) == pytest.approx(win_t0 - t_start - runtime_s)
    assert got["imports_and_reference"] == pytest.approx(5.0)
    assert got["setup.init.other"] == pytest.approx(0.2)
    assert got["staging"] == pytest.approx(1.0)
    assert got["setup.step.compile_or_load"] == pytest.approx(1.0)
    assert got["setup.step.other"] == pytest.approx(0.2)
    assert got["further_steps_and_norms"] == pytest.approx(2.5)


def test_span_sums_take_the_top_level_spans_only():
    got = sp.span_metrics(RECORDS)
    assert got == pytest.approx({
        "setup_init_s": 8.0, "setup_step_s": 4.0,
        "setup_trace_s": 1.5 + 2.8, "setup_compile_s": 2.2 + 1.0,
        "setup_traces": 1000})


# ---------------------------------------------------------------------------
# the five per-layer readers: the same sums, from what the harness fetched
# ---------------------------------------------------------------------------
READERS = ("setup_init_s", "setup_step_s", "setup_trace_s",
           "setup_compile_s", "setup_traces")
# a `setup.step` still open, as the program records one: no end, no counts
OPEN = RECORDS[:4] + [{"name": "setup.step", "parent": None,
                       "start": 19.0, "end": None}]
CASES = {
    "nested": (RECORDS, {"setup_init_s": 8.0, "setup_step_s": 4.0,
                         "setup_trace_s": 4.3, "setup_compile_s": 3.2,
                         "setup_traces": 1000}),
    "open_step": (OPEN, {"setup_init_s": 8.0, "setup_step_s": None,
                         "setup_trace_s": 1.5, "setup_compile_s": 2.2,
                         "setup_traces": 300}),
    "no_records": ([], dict.fromkeys(READERS)),
}


def reader(name):
    return bench.load_module(os.path.join(bench.HERE, "layer_metrics",
                                          name + ".py"))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("name", READERS)
def test_each_reader_is_the_tools_sum(name, case):
    records, want = CASES[case]
    got = reader(name).read(SimpleNamespace(setup=records))
    assert got == sp.span_metrics(records).get(name)
    if want[name] is None:
        assert got is None
    else:
        assert got == pytest.approx(want[name])


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_without_the_programs_records(name):
    """A program without `fetch_setup()` (the harness then sets None), and
    an observation made before the harness fetched anything."""
    assert reader(name).read(SimpleNamespace(setup=None)) is None
    assert reader(name).read(SimpleNamespace()) is None


def test_one_definition_of_the_sums():
    assert sp.span_metrics is setup_spans.span_metrics
