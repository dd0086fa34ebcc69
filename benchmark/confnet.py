"""A cxxnet `.conf` read as a graph of layers with shapes.

The benchmark's own reading of the file format, so that the FLOP count
and the plain reference depend on nothing of the program: one
`key = value` pair per line, `#` starts a comment, the layers sit
between `netconfig=start` and `netconfig=end` as

    layer[a->b] = type:name      a, b node names; `a,c->b` for two inputs
    layer[+1] / layer[+1:name]   input = the node on top, a fresh output
    layer[+0]                    self-loop on the node on top

and the pairs after a layer line belong to that layer. Pairs outside the
net are global: every layer sees them first and its own pairs after.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Pairs = List[Tuple[str, str]]

_LAYER_RE = re.compile(r"^layer\[(.+)\]$")


@dataclass
class Layer:
    index: int
    type: str
    name: str                      # the key of its parameters
    ins: List[str]
    outs: List[str]
    pairs: Pairs = field(default_factory=list)   # globals, then its own
    in_shapes: List[Tuple[int, ...]] = field(default_factory=list)
    out_shape: Tuple[int, ...] = ()

    def get(self, key: str, default: str) -> str:
        val = default
        for k, v in self.pairs:
            if k == key:
                val = v
        return val

    def kernel(self) -> int:
        return int(self.get("kernel_size", "0"))

    def stride(self) -> int:
        return int(self.get("stride", "1"))

    def pad(self) -> int:
        return int(self.get("pad", "0"))

    def group(self) -> int:
        return int(self.get("ngroup", "1"))


@dataclass
class Net:
    layers: List[Layer]
    globals: Pairs
    input_shape: Tuple[int, int, int]

    def get(self, key: str, default: str) -> str:
        val = default
        for k, v in self.globals:
            if k == key:
                val = v
        return val


def parse_pairs(text: str) -> Pairs:
    pairs: Pairs = []
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].strip()
        if not line:
            continue
        key, eq, val = line.partition("=")
        if not eq:
            raise ValueError(f"conf line without '=': {raw!r}")
        pairs.append((key.strip(), val.strip().strip('"')))
    return pairs


def with_layer_pairs(text: str, layer_pairs: Dict[str, Dict[str, str]]
                     ) -> str:
    """The conf's text with `key = value` lines put under every layer of
    a type: `{"max_pooling": {"pool_grad": "winner"}}`. A global pair
    would reach every layer, and some keys only a few layers take."""
    out = []
    for raw in text.splitlines():
        out.append(raw)
        key, eq, val = raw.split("#", 1)[0].strip().partition("=")
        if eq and _LAYER_RE.match(key.strip()):
            ltype = val.strip().strip('"').partition(":")[0].strip()
            out.extend(f"  {k} = {v}"
                       for k, v in layer_pairs.get(ltype, {}).items())
    return "\n".join(out) + "\n"


def conv_out(n: int, k: int, s: int, p: int) -> int:
    return (n + 2 * p - k) // s + 1


def pool_out(n: int, k: int, s: int) -> int:
    """The reference's pooling size: the last window may hang over the
    edge (pooling_layer-inl.hpp)."""
    return min(n - k + s - 1, n - 1) // s + 1


def build(pairs: Pairs, overrides: Dict[str, str] | None = None) -> Net:
    """Layers in declaration order with their shapes (per image, no
    batch dimension). `overrides` replace global pairs of the same key."""
    overrides = dict(overrides or {})
    glob: Pairs = []
    layers: List[Layer] = []
    in_net = False
    cur = None
    top = "0"
    for key, val in pairs:
        if key == "netconfig":
            in_net = val == "start"
            cur = None
            continue
        m = _LAYER_RE.match(key) if in_net else None
        if m:
            ltype, _, lname = val.partition(":")
            idx = len(layers)
            spec = m.group(1)
            if spec.startswith("+"):
                step, _, out_name = spec[1:].partition(":")
                ins = [top]
                outs = [top] if int(step) == 0 else [
                    out_name or f"_node{idx}"]
            else:
                a, _, b = spec.partition("->")
                ins = [t.strip() for t in a.split(",")]
                outs = [t.strip() for t in b.split(",")]
            cur = Layer(idx, ltype.strip(), lname.strip() or f"layer_{idx}",
                        ins, outs)
            layers.append(cur)
            top = outs[0]
        elif in_net and cur is not None:
            cur.pairs.append((key, val))
        else:
            glob.append((key, overrides.get(key, val)))
    seen = {k for k, _ in glob}
    glob.extend((k, v) for k, v in overrides.items() if k not in seen)
    net = Net(layers, glob, (0, 0, 0))
    net.input_shape = tuple(int(t) for t in
                            net.get("input_shape", "0,0,0").split(","))
    for lay in layers:
        lay.pairs = glob + lay.pairs
    _infer_shapes(net)
    return net


def _infer_shapes(net: Net) -> None:
    shapes: Dict[str, Tuple[int, ...]] = {"0": net.input_shape,
                                          "in": net.input_shape}
    for lay in net.layers:
        lay.in_shapes = [shapes[n] for n in lay.ins]
        s = lay.in_shapes[0]
        t = lay.type
        if t == "conv":
            k, st, p = lay.kernel(), lay.stride(), lay.pad()
            out = (int(lay.get("nchannel", "0")),
                   conv_out(s[1], k, st, p), conv_out(s[2], k, st, p))
        elif t in ("max_pooling", "avg_pooling"):
            k, st = lay.kernel(), lay.stride()
            if lay.pad():
                raise NotImplementedError("padded pooling")
            out = (s[0], pool_out(s[1], k, st), pool_out(s[2], k, st))
        elif t == "flatten":
            out = (s[0] * s[1] * s[2],)
        elif t == "fullc":
            if len(s) != 1:
                raise ValueError(f"{lay.name}: fullc needs a flat input")
            out = (int(lay.get("nhidden", "0")),)
        elif t in ("relu", "lrn", "dropout", "batch_norm", "softmax"):
            out = s
        elif t == "add":
            if any(x != s for x in lay.in_shapes):
                raise ValueError(f"{lay.name}: add over unequal shapes")
            out = s
        else:
            raise NotImplementedError(f"layer type {t!r}")
        lay.out_shape = out
        shapes[lay.outs[0]] = out
