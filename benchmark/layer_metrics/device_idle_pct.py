"""Share of the traced window in which no operation ran on the device:
1 - (union of the device plane's operation intervals / window)."""

from benchmark import trace_reduce


def read(obs):
    if not obs.device_events or obs.span is None:
        return None
    lo, hi = obs.span
    busy = trace_reduce.busy_ns(obs.device_events)
    return 100.0 * (1.0 - busy / (hi - lo))
