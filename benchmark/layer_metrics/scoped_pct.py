"""The reduction's own honesty check: the share of the device's busy
time whose operation was found in the compiled step's text under a layer
or `update` scope (benchmark/scope_map.py). Low where the join fails (a
module that compiled to other instruction names) or where the program
traces work outside any scope."""

from benchmark import scope_map, trace_reduce


def read(obs):
    table = scope_map.by_scope(obs)
    if table is None:
        return None
    busy = trace_reduce.busy_ns(obs.device_events)
    return 100.0 * table.phase_ns("fwd", "bwd", "update") / busy
