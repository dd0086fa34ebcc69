"""Device time by the program's own names.

The trace names a device operation by its HLO instruction (`fusion.193`),
and that number changes with every edit to the graph. The program names
what it traces (`Network.forward` runs each layer under the scope
`<type>.<key>`, `train_step` its updater under `update/<key>`), JAX adds
the direction, and the compiled module's text carries the result on
every instruction, inside fused computations too:

    jit(train_step)/jvp(conv.conv1)/conv_general_dilated              forward
    jit(train_step)/transpose(jvp(max_pooling.layer_2))/select_and_scatter_add
                                                                      backward
    jit(train_step)/update/conv1/sub                                  updater

`scopes_of` is the pure part, text -> {instruction: (phase, type, key)};
`by_scope` joins it onto a traced window's device events. The text comes
from one compile of the step that goes round the persistent cache (a
cached executable keeps the metadata it was written with, a parent's
perhaps, which has no scopes); the same module compiles to the same
instruction names, and `scoped_pct` is what proves the join.

A fusion is not its root: with `update_period = 1` XLA fuses the SGD
update into the weight-gradient fusions, so one operation is conv1's
weight gradient AND its weight and momentum update. The rule: a fusion
whose computation holds a `convolution` or `dot` takes that
instruction's scope; any other takes its own, which XLA copies from the
root, the instruction whose result the fusion writes; one without a
scope of its own takes the scope most of its scoped instructions carry.
(The majority alone misleads: a backward fusion that recomputes a relu's
mask holds more forward-named instructions than backward ones, and
counted as forward, 6 ms a step of AlexNet's bias gradients and pool
forwards went to `relu`, PERF.md section 6, PR 27.) Time in fusions that
hold more than one phase is also summed apart (`mixed`). An instruction
without a layer or `update` scope is `other`.
"""

from __future__ import annotations

import contextlib
import importlib
import re
import sys
import time
import traceback
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

Scope = Tuple[str, str, str]           # phase, layer type, layer key
PHASES = ("fwd", "bwd", "update", "other")
OTHER: Scope = ("other", "", "")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)}]+)")
# one component of an op_name that names a layer, as JAX wraps it
_LAYER = re.compile(r"^(?:transpose\()?(?:jvp\()?([a-z][a-z0-9_]*)\."
                    r"([A-Za-z0-9_.\-]+?)\)*$")
_CONTRACTIONS = ("convolution", "dot")


@dataclass
class _Instruction:
    name: str
    opcode: str
    op_name: str
    calls: Optional[str]


def _opcode(rest: str) -> str:
    """`<type> <opcode>(<operands>), ...` -> the opcode; the type may be
    a tuple with spaces and carries layouts with parentheses."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0]
    return ""


def _computations(text: str) -> Dict[str, List[_Instruction]]:
    comps: Dict[str, List[_Instruction]] = {}
    current: Optional[List[_Instruction]] = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        current.append(_Instruction(
            m.group(1), _opcode(rest), op.group(1) if op else "",
            calls.group(1) if calls else None))
    return comps


def _layer_of(component: str) -> Optional[Tuple[str, str]]:
    """(type, key) where the component names a layer."""
    m = _LAYER.match(component)
    return (m.group(1), m.group(2)) if m else None


def scope_of(op_name: str, types: Dict[str, str]) -> Scope:
    """The scope one `op_name` stands under. XLA joins the names of
    instructions it merged with `;`: the first that names a scope counts.
    `types` maps a layer key to its type, for the updater's scopes,
    which hold the key alone."""
    for path in op_name.split(";"):
        comps = path.split("/")
        backward = False
        for i, comp in enumerate(comps):
            if comp == "update":
                key = next((c for c in comps[i + 1:-1] if c in types), "")
                return ("update", types.get(key, ""), key)
            backward = backward or comp.startswith("transpose(")
            layer = _layer_of(comp)
            if layer:
                return ("bwd" if backward else "fwd",) + layer
    return OTHER


def _parse(text: str) -> Tuple[Dict[str, Scope], Set[str]]:
    comps = _computations(text)
    types: Dict[str, str] = {}
    for instrs in comps.values():
        for ins in instrs:
            for path in ins.op_name.split(";"):
                for comp in path.split("/"):
                    layer = _layer_of(comp)
                    if layer:
                        types.setdefault(layer[1], layer[0])

    def inside(comp: str, seen: Set[str]) -> Iterable[_Instruction]:
        """Every instruction of a fused computation, nested fusions'
        too."""
        if comp in seen:
            return
        seen.add(comp)
        for ins in comps.get(comp, ()):
            yield ins
            if ins.opcode == "fusion" and ins.calls:
                yield from inside(ins.calls, seen)

    scopes: Dict[str, Scope] = {}
    mixed: Set[str] = set()
    for instrs in comps.values():
        for ins in instrs:
            own = scope_of(ins.op_name, types)
            if ins.opcode != "fusion" or not ins.calls:
                scopes[ins.name] = own
                continue
            held = [(i.opcode, scope_of(i.op_name, types))
                    for i in inside(ins.calls, set())]
            named = [s for _, s in held if s != OTHER]
            contracted = [s for op, s in held
                          if op in _CONTRACTIONS and s != OTHER]
            if contracted:
                scopes[ins.name] = Counter(contracted).most_common(1)[0][0]
            elif own != OTHER or not named:
                scopes[ins.name] = own
            else:
                scopes[ins.name] = Counter(named).most_common(1)[0][0]
            if len({s[0] for s in named}) > 1:
                mixed.add(ins.name)
    return scopes, mixed


def scopes_of(hlo_text: str) -> Dict[str, Scope]:
    """{instruction: (phase, type, key)} for every instruction of the
    module, those of fused and called computations too."""
    return _parse(hlo_text)[0]


def mixed_fusions(hlo_text: str) -> Set[str]:
    """The fusions that hold instructions of more than one phase."""
    return _parse(hlo_text)[1]


# ---------------------------------------------------------------------------
# the join
# ---------------------------------------------------------------------------
@dataclass
class Table:
    """Device nanoseconds of one traced window, by scope."""
    steps: int
    ns: Dict[Scope, float] = field(default_factory=dict)
    mixed_ns: float = 0.0
    total_ns: float = 0.0              # all events, summed
    unknown_ns: float = 0.0            # events the module's text lacks
    compile_s: float = 0.0

    def phase_ns(self, *phases: str, kind: Optional[str] = None) -> float:
        """Summed over the scopes of these phases, of one layer type
        where `kind` names it."""
        return sum(ns for (phase, layer, _), ns in self.ns.items()
                   if phase in phases and kind in (None, layer))


def sum_events(events, scopes: Dict[str, Scope], mixed: Set[str],
               steps: int) -> Table:
    table = Table(steps)
    acc: Dict[Scope, float] = defaultdict(float)
    for name, _, dur in events:
        table.total_ns += dur
        if name not in scopes:
            table.unknown_ns += dur
        acc[scopes.get(name, OTHER)] += dur
        if name in mixed:
            table.mixed_ns += dur
    table.ns = dict(acc)
    return table


@contextlib.contextmanager
def _round_the_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def step_text(obs) -> Optional[str]:
    """The compiled text of the step this cell timed: a second trainer,
    built and fed as the cell's driver builds and feeds the first (the
    device is free by now), asked for its `step_hlo`. None where the
    program has no such method."""
    import numpy as np
    from cxxnet_tpu.nnet.trainer import NetTrainer
    if not hasattr(NetTrainer, "step_hlo"):
        return None
    cfg, traffic = obs.cell.cfg, obs.cell.traffic
    driver = importlib.import_module(
        "benchmark.drivers." + traffic["driver"])
    trainer = driver.build_trainer(cfg["conf_text"],
                                   dict(cfg["overrides"]), 0)
    shape = (obs.rows,) + tuple(obs.net.input_shape)
    staged = driver.stage(
        trainer, (np.zeros(shape, np.uint8), np.zeros(obs.rows, np.int64)),
        float(traffic["pixel_mean"]))
    with _round_the_compile_cache():
        return trainer.step_hlo(staged)


def by_scope(obs) -> Optional[Table]:
    """The traced window's device time by scope, made once a run and
    kept on `obs`. None on a run without device events (a CPU
    rehearsal), on a program without scopes to read, and, with the
    traceback on standard error, where the second compile fails: the
    metrics that read the table are then left out of the line, and the
    run's other numbers stand."""
    if hasattr(obs, "scope_table"):
        return obs.scope_table
    obs.scope_table = None
    if not obs.device_events or not obs.window.steps:
        return None
    t0 = time.perf_counter()
    try:
        text = step_text(obs)
    except Exception:                  # noqa: BLE001 - see the docstring
        traceback.print_exc()
        return None
    if text is None:
        return None
    table = sum_events(obs.device_events, *_parse(text), obs.window.steps)
    table.compile_s = time.perf_counter() - t0
    obs.scope_table = table
    print(render(table), file=sys.stderr, flush=True)
    return table


def ms_a_step(obs, *phases: str, kind: Optional[str] = None
              ) -> Optional[float]:
    """What a reader returns: the device time a step in these phases,
    of the layers of one type where `kind` names it. None where the
    conf has no layer of that type, and where `by_scope` has nothing."""
    if kind is not None and all(l.type != kind for l in obs.net.layers):
        return None
    table = by_scope(obs)
    if table is None:
        return None
    return table.phase_ns(*phases, kind=kind) / table.steps / 1e6


def render(table: Table) -> str:
    """The whole table, by layer and phase, for standard error."""
    total = table.total_ns or 1.0
    layers: Dict[Tuple[str, str], Dict[str, float]] = defaultdict(dict)
    for (phase, kind, key), ns in table.ns.items():
        layers[(kind, key)][phase] = ns
    lines = [f"[scope_map] device time by scope, ms a step over "
             f"{table.steps} steps (second compile and join "
             f"{table.compile_s:.1f} s)",
             f"[scope_map] {'layer':32s} {'fwd':>8s} {'bwd':>8s} "
             f"{'update':>8s} {'other':>8s} {'share':>7s}"]
    for (kind, key), row in sorted(layers.items(),
                                   key=lambda kv: -sum(kv[1].values())):
        name = f"{kind}.{key}" if kind or key else "(no scope)"
        cells = " ".join(f"{row.get(p, 0.0) / table.steps / 1e6:8.3f}"
                         for p in PHASES)
        lines.append(f"[scope_map] {name:32s} {cells} "
                     f"{100.0 * sum(row.values()) / total:6.2f}%")
    for phase in PHASES:
        lines.append(f"[scope_map] {phase}_ms = "
                     f"{table.phase_ns(phase) / table.steps / 1e6:.4f}")
    lines.append(f"[scope_map] mixed_pct = "
                 f"{100.0 * table.mixed_ns / total:.3f} (time in fusions "
                 f"of more than one phase, over the summed device time)")
    lines.append(f"[scope_map] not in the module's text: "
                 f"{100.0 * table.unknown_ns / total:.3f}% of the summed "
                 f"device time")
    return "\n".join(lines)
