"""Seconds JAX spent tracing to a jaxpr and lowering to MLIR inside the
program's set-up spans (their counters `trace_s` + `lower_s`, JAX's own
durations: work that every process pays, compile cache or not). Read
from the set-up records the harness fetches after the window
(`benchmark/setup_spans.py`). Silent where the program keeps no such
records."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, "setup_trace_s")
