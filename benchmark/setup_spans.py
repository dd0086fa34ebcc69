"""The program's set-up spans summed into the numbers the set-up
metrics report (`layer_metrics/setup_*.py`, `tools/setup_phases.py`).

A record is what `NetTrainer.fetch_setup()` returns for one span:
`name`, `parent` (None at the top), `start`, `end` (None while the span
is open) and what JAX did meanwhile (`trace_s`, `lower_s`, `compile_s`,
`cache_load_s`, `traces`). Only closed top-level spans are summed: a
child's seconds and counts are inside its parent's already, and an open
span has neither an end nor its counts yet.
"""

from __future__ import annotations

from typing import Any, Dict, List


def span_metrics(records: List[Dict[str, Any]]) -> Dict[str, float]:
    """The five numbers, each only where a record gives it: the
    durations where a `setup.init` / `setup.step` span closed, the
    counters where any top-level span closed; {} for none."""
    top = [r for r in records if r["parent"] is None and r["end"] is not None]
    out: Dict[str, float] = {}
    for metric, span in (("setup_init_s", "setup.init"),
                         ("setup_step_s", "setup.step")):
        got = [r["end"] - r["start"] for r in top if r["name"] == span]
        if got:
            out[metric] = sum(got)
    if top:
        out["setup_trace_s"] = sum(r["trace_s"] + r["lower_s"] for r in top)
        out["setup_compile_s"] = sum(r["compile_s"] + r["cache_load_s"]
                                     for r in top)
        out["setup_traces"] = sum(r["traces"] for r in top)
    return out


def read(obs, metric: str):
    """One of the five from `obs.setup`, the records the harness fetched
    after the window; None where the program keeps no such records."""
    records = getattr(obs, "setup", None)
    if records is None:
        return None
    return span_metrics(records).get(metric)
