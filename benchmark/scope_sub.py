"""Device time by a layer's named sub-scope, and the readers of the
Nemotron-H cell from one compile of the step.

`scope_leaf.table` keys its rows `(phase, type, key)`: a layer's own
named scopes (`ssd` inside `mamba2.<key>`, ops/ssd.py's `intra` and
`state` inside that) are under the key. Here an instruction also takes
the component that follows its layer's, as `scope_map` takes the layer:

    jit(train_step)/jvp(mamba2.l0_mamba)/ssd/intra/dot_general      fwd, ssd
    jit(train_step)/transpose(jvp(mamba2.l0_mamba))/ssd/...         bwd, ssd
    .../transpose(jvp(mamba2.l0_mamba))/jvp(mamba2.l0_mamba)/checkpoint/ssd/...
                                     bwd, ssd (a checkpointed layer's second forward)

and a fusion the sub-scope of its contraction, else its own, else the
one most of its instructions carry (`scope_map`'s rule). Loops, calls
and conditionals are left out as `scope_leaf` leaves them out.

The step's text is compiled once a run and kept on `obs` (a second
compile of a step this size costs a minute), and `leaf_table` leaves
`scope_leaf`'s table on `obs` from the same text, so that this cell's
readers (`mamba_ms`, `ssd_ms`, `mtp_ms`) compile once between them.
"""

from __future__ import annotations

import sys
import time
import traceback
from collections import Counter, defaultdict
from typing import Dict, Optional, Tuple

from benchmark import scope_leaf, scope_map

Sub = Tuple[str, str, str, str]        # phase, layer type, key, sub-scope
# components JAX puts between a layer's scope and the program's own
# (a checkpointed layer's backward runs under `checkpoint`)
_FRAMEWORK = ("checkpoint", "remat", "rematted_computation", "while",
              "body", "cond", "closed_call")


def step_text(obs) -> Optional[str]:
    """The compiled text of the timed step, made once a run; None where
    there is no traced window or the compile fails (the traceback on
    standard error)."""
    if not hasattr(obs, "step_text"):
        obs.step_text = None
        if obs.device_events and obs.window.steps:
            try:
                obs.step_text = scope_map.step_text(obs)
            except Exception:          # noqa: BLE001 - as scope_map.by_scope
                traceback.print_exc()
    return obs.step_text


def leaf_table(obs) -> Optional[scope_map.Table]:
    """`scope_leaf.table`, from `step_text`."""
    if hasattr(obs, "scope_table"):
        return obs.scope_table
    t0 = time.perf_counter()
    text = step_text(obs)
    obs.scope_table = None
    if text is None:
        return None
    holds = scope_leaf.containers_of(text)
    events = [e for e in obs.device_events if e[0] not in holds]
    got = scope_map.sum_events(events, *scope_map._parse(text),
                               obs.window.steps)
    got.compile_s = time.perf_counter() - t0
    obs.scope_table = got
    print(scope_map.render(got), file=sys.stderr, flush=True)
    return got


def sub_of(op_name: str) -> Sub:
    """`scope_map.scope_of`, with the component after the layer's (''
    where there is none)."""
    for path in op_name.split(";"):
        comps = path.split("/")
        backward = False
        for i, comp in enumerate(comps):
            if comp == "update":
                return ("update", "", "", "")
            backward = backward or comp.startswith("transpose(")
            layer = scope_map._layer_of(comp)
            if layer:
                rest = [c for c in comps[i + 1:-1] if not (
                    scope_map._layer_of(c) or c in _FRAMEWORK
                    or c.startswith(("jit(", "pjit(")))]
                return (("bwd" if backward else "fwd",) + layer
                        + (rest[0] if rest else "",))
    return ("other", "", "", "")


def subs_of(hlo_text: str) -> Dict[str, Sub]:
    """{instruction: (phase, type, key, sub-scope)}, fusions by
    `scope_map`'s rule."""
    comps = scope_map._computations(hlo_text)

    def inside(comp, seen):
        if comp in seen:
            return
        seen.add(comp)
        for ins in comps.get(comp, ()):
            yield ins
            if ins.opcode == "fusion" and ins.calls:
                yield from inside(ins.calls, seen)

    out: Dict[str, Sub] = {}
    none = ("other", "", "", "")
    for instrs in comps.values():
        for ins in instrs:
            own = sub_of(ins.op_name)
            if ins.opcode != "fusion" or not ins.calls:
                out[ins.name] = own
                continue
            held = [(i.opcode, sub_of(i.op_name))
                    for i in inside(ins.calls, set())]
            named = [s for _, s in held if s != none]
            contracted = [s for op, s in held
                          if op in scope_map._CONTRACTIONS and s != none]
            if contracted:
                out[ins.name] = Counter(contracted).most_common(1)[0][0]
            elif own != none or not named:
                out[ins.name] = own
            else:
                out[ins.name] = Counter(named).most_common(1)[0][0]
    return out


def sub_table(obs) -> Optional[Dict[Sub, float]]:
    """Device ns of the traced window by (phase, type, key, sub-scope),
    loops left out; made once a run."""
    if hasattr(obs, "sub_table"):
        return obs.sub_table
    obs.sub_table = None
    text = step_text(obs)
    if text is None:
        return None
    holds = scope_leaf.containers_of(text)
    subs = subs_of(text)
    acc: Dict[Sub, float] = defaultdict(float)
    for name, _, dur in obs.device_events:
        if name not in holds:
            acc[subs.get(name, ("other", "", "", ""))] += dur
    obs.sub_table = dict(acc)
    return obs.sub_table


def sub_ms_a_step(obs, kind: str, sub: str) -> Optional[float]:
    """Device ms a step, both directions, under sub-scope `sub` of the
    conf's layers of type `kind`. None where the conf has no such
    layer, where there is no table, and where no instruction carries
    that sub-scope (a program without it)."""
    keys = {l.name for l in obs.net.layers if l.type == kind}
    if not keys:
        return None
    table = sub_table(obs)
    if table is None:
        return None
    rows = [ns for (phase, layer, key, s), ns in table.items()
            if phase in ("fwd", "bwd") and layer == kind and key in keys
            and s == sub]
    return sum(rows) / obs.window.steps / 1e6 if rows else None
