"""From a profiler trace (`.xplane.pb`) to numbers.

Pure functions over lists of `(name, start_ns, duration_ns)`, so that
they can be checked on a made-up list, and one loader that turns the
profiler's file into such lists (a copy of the reduction in
`cxxnet_tpu/tools/profile_step.py op_table`, which reads the "XLA Ops"
line of each device plane, extended by the busy union, the idle gaps
and a filter by kernel name).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]       # name, start ns, duration ns

HOST_PREFIX = "bench."                 # the harness's own host spans
WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    labels: Dict[str, str] = field(default_factory=dict)


_LAYOUT = re.compile(r"\{[^}]*\}")


def split_name(text: str) -> Tuple[str, str]:
    """The profiler names a device operation by its whole HLO line,
    `%fusion.7 = bf16[8,4]{1,0:T(8,128)} fusion(bf16[...] %copy.3)`.
    Its own name is what stands before ` = ` (matching a kernel by name
    must not match an operation that only reads the kernel's result);
    the label adds the result's type, layouts taken out."""
    head, sep, rest = text.partition(" = ")
    short = head.lstrip("%")
    if not sep:
        return short, short
    result = _LAYOUT.sub("", rest)
    depth = 0
    for i, ch in enumerate(result):
        depth += ch == "("
        depth -= ch == ")"
        if ch == " " and depth == 0:
            result = result[:i]
            break
    return short, f"{short} {result}"[:120]


def load(trace_dir: str) -> Trace:
    """Device operations of each device plane and the harness's host
    spans. A trace without a device plane (a CPU rehearsal) gives no
    devices: nothing on the host stands in for one."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    trace = Trace()
    for plane in data.planes:
        if "/device:" in plane.name:
            # a device plane has parallel lines over the same time
            # (Steps, XLA Modules, XLA Ops): the leaves are "XLA Ops"
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    short, label = split_name(ev.name)
                    trace.labels.setdefault(short, label)
                    ops.append((short, float(ev.start_ns),
                                float(ev.duration_ns)))
            if ops:
                trace.devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events
                    if ev.name.startswith(HOST_PREFIX))
    trace.host.sort(key=lambda e: e[1])
    return trace


def window_of(trace: Trace) -> Optional[Tuple[float, float]]:
    """(start, end) in ns: the harness's window span, else the extent of
    the device operations."""
    for name, start, dur in trace.host:
        if name == WINDOW_SPAN:
            return start, start + dur
    evs = [e for ops in trace.devices.values() for e in ops]
    if not evs:
        return None
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_ns(events: Sequence[Event]) -> float:
    """Length of the union of the events' intervals."""
    total = 0.0
    end = float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi] in which no event runs."""
    out = []
    end = lo
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if start > end:
            out.append((end, min(start, hi)))
        end = max(end, start + dur)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def named(events: Sequence[Event], kernel: str) -> List[Event]:
    """The events of the kernel `kernel`: its name as given to
    `pallas_call`, which XLA wraps (`jvp_lrn_fwd_.2`) but keeps."""
    return [e for e in events if kernel in e[0]]


def kernel_ns(events: Sequence[Event], kernels: Sequence[str]) -> float:
    """Summed duration of the events of these kernels."""
    return sum(e[2] for k in kernels for e in named(events, k))


def top_ops(events: Sequence[Event], n: int = 10
            ) -> List[Tuple[str, float]]:
    """(name, seconds) of the operations that took most time."""
    acc: Dict[str, float] = defaultdict(float)
    for name, _, dur in events:
        acc[name] += dur
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in rows]


def idle_by_host(events: Sequence[Event], host: Sequence[Event],
                 lo: float, hi: float, n: int = 10
                 ) -> List[Tuple[str, float]]:
    """(what the host was doing, seconds) over the device's idle gaps,
    the largest first. A gap belongs to the innermost harness span that
    covers its start, or to `host_other`."""
    spans = [s for s in host if s[0] != WINDOW_SPAN]
    acc: Dict[str, float] = defaultdict(float)
    for a, b in gaps(events, lo, hi):
        label, best = "host_other", float("inf")
        for name, start, dur in spans:
            if start <= a < start + dur and dur < best:
                label, best = name, dur
        acc[label] += b - a
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in rows]
