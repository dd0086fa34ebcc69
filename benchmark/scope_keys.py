"""One layer type's rows of `scope_leaf.table`, chosen by what the
conf says of each layer: the table's rows are `(phase, type, key)`, and
a key is a conf layer's name, so a reader can split a type by one of its
layers' own settings (`gqa` layers with and without a window)."""

from __future__ import annotations

from typing import Callable, Optional

from benchmark import scope_leaf


def conf_layers(obs, kind: str, want: Callable) -> list:
    """The conf's own layers of one type that `want(layer)` keeps (the
    product rows a reference appends are typed `fullc`)."""
    return [l for l in obs.net.layers if l.type == kind and want(l)]


def ms_a_step(obs, kind: str, want: Callable) -> Optional[float]:
    """Device ms a step, both directions, under the layers of type
    `kind` that `want` keeps. None where the conf has no such layer,
    where the table cannot be made, and where it has no such row (a
    program without those scopes)."""
    keys = {l.name for l in conf_layers(obs, kind, want)}
    if not keys:
        return None
    table = scope_leaf.table(obs)
    if table is None:
        return None
    rows = [ns for (phase, layer, key), ns in table.ns.items()
            if phase in ("fwd", "bwd") and layer == kind and key in keys]
    return sum(rows) / table.steps / 1e6 if rows else None


def has_window(layer) -> bool:
    return int(layer.get("window", "0")) > 0
