"""Plain reference for the `smallthinker` family
(SmallThinker-21BA3B-Instruct): forward, loss, gradients and Adam in
`jax.numpy`, float32, every product at `Precision.HIGHEST`, nothing of
the program.

Every published layer is the same block; `x` is (T, d), layer `l`
counts from 0, eps from the conf:

    a      = RMSNorm(x)
    r      = a Wr^T                    THE ROUTER READS THE ATTENTION'S INPUT
    q,k,v  = a Wq, a Wk, a Wv          nhead query heads, nkvhead key/value
                                       heads, head_dim wide, no bias
    if rope_theta:  q, k <- rotary(q, k; positions 0..T-1)
    s[t,u] = q_h[t] . k_(h // group)[u] / sqrt(head_dim),  u <= t,
             and t - u < window if the layer has a window
    y      = x + concat_h(softmax(s) v_(h // group)) Wo
    b      = RMSNorm(y)
    chosen = top_k(r);  w = softmax(r[chosen])
    e_i(b) = (relu(b Wg_i) * (b Wu_i)) Wd_i
    x'     = y + sum over i chosen AND held here of w_i e_i(b)

    after the last layer RMSNorm, logits = x W_lm (untied), and the loss
    of `kimi_linear.py`: the mean over positions t < T-1 of the
    cross-entropy of position t's logits against token t+1.

The conf states a published layer as six conf layers (`rms_norm`, `gqa`,
`add`, `rms_norm`, `moe` with two inputs, `add`) and this module follows
the conf layer by layer, as `kimi_linear.Reference` does, whose reading
of the conf, start of every leaf, loss in blocks and Adam it inherits
(the leaves of `embed`, `rms_norm`, `lm_head` and `moe`, without the
selection bias, are that module's). What is new is here: `gqa`, the
router's second input, the ReLU gate and the softmax over the chosen,
the products the new layers require, and how 16,384 positions fit
beside 10.3 GB of parameters, moments and gradient: one checkpoint a
PUBLISHED layer (wherever the conf leaves one node live), so that the
backward keeps a (T, d) map a layer and runs the block again, and the
attention in blocks of `ATTN_BLOCK` query rows against the keys their
window can reach (all keys in a layer without one), each block under a
checkpoint of its own. Neither changes a number.

Departures from the published model, each also under `assumed` in the
configuration's file: no secondary experts (the config has no key for
them), no attention bias and no QK-norm (the config names neither),
rotary in the rotate-half convention over the whole head, no auxiliary
load-balancing loss, the initial values, Adam's settings.

`variant` puts something else in the reference's place, for the readings
a cell's limits are set from: a dtype name (`float8_e4m3fn`) rounds the
operands of every weight-bearing product to that type (the control);
`no_window` runs the window layers full; `rope_everywhere` turns the
layers without a positional encoding too; `router_after` lets the router
read `b`; `silu_experts` gates the experts with SiLU; `no_routed`
zeroes the held experts' part.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import confnet
from benchmark.kernel_work_attention import seen_pairs
from benchmark.reference import kimi_linear as base
from benchmark.reference.kimi_linear import (HI, _int, held_of, mm, rotary,
                                             silu)

FAULTS = ("no_window", "rope_everywhere", "router_after", "silu_experts",
          "no_routed")
ATTN_BLOCK = 256          # query rows a block of scores


# ---------------------------------------------------------------------------
# the conf as a net: shapes, and the matrix products each layer needs
# ---------------------------------------------------------------------------
def infer_shapes(net: confnet.Net) -> None:
    seq = net.input_shape[1]
    shapes: Dict[str, Tuple[int, ...]] = {"0": (seq,)}
    for lay in net.layers:
        lay.in_shapes = [shapes[n] for n in lay.ins]
        if lay.type == "embed":
            out = (seq, _int(lay, "nhidden"))
        elif lay.type == "lm_head":
            out = (seq, _int(lay, "nvocab"))
        elif lay.type in ("rms_norm", "gqa", "moe", "add"):
            out = lay.in_shapes[0]
        else:
            raise NotImplementedError(f"layer type {lay.type!r}")
        lay.out_shape = out
        shapes[lay.outs[0]] = out


def products(lay) -> List[Tuple[str, int, int]]:
    """(name, a, b): the matrix products a conf layer REQUIRES for one
    row (one sequence of T positions), each as `a x b` multiply-adds.
    Nothing a program recomputes; scores and values count the pairs a
    query sees and no other; routed experts count their expected share
    of assignments."""
    if lay.type not in ("gqa", "moe", "lm_head"):
        return []
    t, d = lay.in_shapes[0]
    if lay.type == "lm_head":
        return [("logits", t * d, _int(lay, "nvocab"))]
    if lay.type == "moe":
        e, k, h = (_int(lay, "nexpert"), _int(lay, "moe_top_k", "1"),
                   _int(lay, "nhidden"))
        # t*k assignments, held/e of them expected on the experts here
        return [("router", t * lay.in_shapes[-1][1], e),
                ("routed", (t * k * held_of(lay)[1] // e) * d, 3 * h)]
    nh, nkv, dh = (_int(lay, "nhead"), _int(lay, "nkvhead"),
                   _int(lay, "head_dim"))
    return [("q", t * d, nh * dh),
            ("kv", t * d, 2 * nkv * dh),
            ("o", t * nh * dh, d),
            ("scores_values", seen_pairs(t, _int(lay, "window")),
             2 * nh * dh)]


def with_products(net: confnet.Net) -> None:
    """`kimi_linear.with_products` over this module's `products`: rows
    typed `fullc`, which is what `benchmark/flops.py` counts; none fed
    by node `0` (the embedding trains)."""
    rows = []
    for lay in net.layers:
        for name, a, b in products(lay):
            rows.append(confnet.Layer(
                len(net.layers) + len(rows), "fullc",
                f"{lay.name}/{name}", [lay.outs[0]], [f"_{lay.name}/{name}"],
                list(lay.pairs), [(a,)], (b,)))
    net.layers.extend(rows)


def published_layers(layers) -> List[List]:
    """The conf's layers in runs that end wherever one node alone (the
    token node apart) is read later: the residual stream between two
    published layers."""
    groups, cur = [], []
    for i, lay in enumerate(layers):
        cur.append(lay)
        made = {n for g in groups + [cur] for l in g for n in l.outs}
        later = {n for l in layers[i + 1:] for n in l.ins}
        if len((made & later) - {"0"}) <= 1:
            groups.append(cur)
            cur = []
    return groups + ([cur] if cur else [])


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attend(q, k, v, window: int):
    """q (T, G, R, d): R query heads on each of the G key/value heads
    k, v (T, G, d). Causal softmax attention, a query seeing the
    `window` positions up to its own (0: all of them), in blocks of
    query rows against the keys their window can reach."""
    t = q.shape[0]
    blk = max(b for b in range(1, min(t, ATTN_BLOCK) + 1) if t % b == 0)
    span = min(t, window + blk - 1) if window else t
    scale = 1.0 / math.sqrt(q.shape[-1])

    @jax.checkpoint
    def rows(lo):
        start = jnp.maximum(lo + blk - span, 0)
        qb = lax.dynamic_slice_in_dim(q, lo, blk, 0)
        kb = lax.dynamic_slice_in_dim(k, start, span, 0)
        vb = lax.dynamic_slice_in_dim(v, start, span, 0)
        s = jnp.einsum("qgrd,kgd->grqk", qb, kb, precision=HI) * scale
        qpos = lo + jnp.arange(blk)[:, None]
        kpos = start + jnp.arange(span)[None, :]
        seen = kpos <= qpos
        if window:
            seen = seen & (qpos - kpos < window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), vb,
                          precision=HI)

    return lax.map(rows, jnp.arange(0, t, blk)).reshape(q.shape)


class Reference(base.Reference):
    def __init__(self, conf_text: str, overrides: Dict[str, str],
                 variant: Optional[str] = None):
        self.net = base.read_conf(conf_text, overrides)
        infer_shapes(self.net)
        self.conf_layers = list(self.net.layers)
        self.groups = published_layers(self.conf_layers)
        with_products(self.net)
        self.batch = int(self.net.get("batch_size", "0"))
        if self.net.get("updater", "sgd") != "adam":
            raise NotImplementedError("only the adam updater")
        self.fault = variant if variant in FAULTS else None
        self._q = lambda a: a
        if variant and self.fault is None:
            qt = jnp.dtype(variant)
            self._q = lambda a: a.astype(qt).astype(jnp.float32)
        self._grad_fn = None
        # what `rope_everywhere` turns the other layers by
        self.theta = max(float(l.get("rope_theta", "0"))
                         for l in self.conf_layers)

    # -- weights ------------------------------------------------------
    @staticmethod
    def _table(lay):
        if lay.type != "gqa":
            return base.Reference._table(lay)
        d = lay.in_shapes[0][-1]
        nh, nkv, dh = (_int(lay, "nhead"), _int(lay, "nkvhead"),
                       _int(lay, "head_dim"))
        return [("wq", (d, nh * dh), "normal"), ("wk", (d, nkv * dh), "normal"),
                ("wv", (d, nkv * dh), "normal"), ("wo", (nh * dh, d), "normal")]

    @staticmethod
    def _init_moe(lay, k, sigma):
        """`kimi_linear`'s leaves (the router is as wide as the experts'
        input here), without the selection bias this router has not."""
        p = base.Reference._init_moe(lay, k, sigma)
        del p["sbias"]
        return p

    # -- forward ------------------------------------------------------
    def _gqa(self, lay, p, x):
        nh, nkv, dh = (_int(lay, "nhead"), _int(lay, "nkvhead"),
                       _int(lay, "head_dim"))
        t = x.shape[0]
        window = 0 if self.fault == "no_window" else _int(lay, "window")
        theta = float(lay.get("rope_theta", "0"))
        if self.fault == "rope_everywhere":
            theta = theta or self.theta
        q = self.lin(x, p["wq"]).reshape(t, nh, dh)
        k = self.lin(x, p["wk"]).reshape(t, nkv, dh)
        v = self.lin(x, p["wv"]).reshape(t, nkv, dh)
        if theta:
            q, k = rotary(q, theta), rotary(k, theta)
        # query head h reads key/value head h // (nh // nkv)
        o = attend(q.reshape(t, nkv, nh // nkv, dh), k, v, window)
        return self.lin(o.reshape(t, nh * dh), p["wo"])

    def _moe(self, lay, p, b, a):
        k = _int(lay, "moe_top_k", "1")
        first, held = held_of(lay)
        r = mm(b if self.fault == "router_after" else a, p["gate"].T)
        top, chosen = lax.top_k(r, k)                       # (T, k)
        w = jax.nn.softmax(top, axis=-1)
        act = silu if self.fault == "silu_experts" else jax.nn.relu
        if self.fault == "no_routed":
            return jnp.zeros_like(b)

        @jax.checkpoint
        def add_expert(out, ws):
            """Every held expert on all tokens, weighted by 0 where it
            was not chosen (a scan over the experts: one body, and the
            backward computes an expert's products again instead of
            keeping eight experts' worth)."""
            w1, w3, w2, e = ws
            w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
            hid = act(self.lin(b, w1.T)) * self.lin(b, w3.T)
            return out + w_e[:, None] * self.lin(hid, w2.T), None

        return lax.scan(add_expert, jnp.zeros_like(b), (
            p["w1"], p["w3"], p["w2"], first + jnp.arange(held)))[0]

    def _apply(self, lay, p, ins):
        if lay.type == "gqa":
            return self._gqa(lay, p, ins[0])
        if lay.type == "moe":
            return self._moe(lay, p, ins[0], ins[-1])
        return super()._apply(lay, p, ins)

    def row_loss(self, params, tokens, *, logits: bool = False):
        """One row's loss; with `logits` the full (T, V) logits instead
        (tests and tiny sizes only). A published layer at a time, each
        under one checkpoint."""
        vals = {"0": tokens}
        for group in self.groups:
            head = group[-1] if group[-1].type == "lm_head" else None
            if head is not None:
                p = params[head.name]
                y, ids = (vals[n] for n in head.ins)
                if logits:
                    return self.lin(y, p["wmat"])
                return self._head_loss(head, p, y, ids)
            reads = sorted({n for l in group for n in l.ins}
                           - {n for l in group for n in l.outs})

            def run(ps, *given, group=group, reads=reads):
                inner = dict(zip(reads, given))
                for lay in group:
                    inner[lay.outs[0]] = self._apply(
                        lay, ps.get(lay.name, {}), [inner[n] for n in lay.ins])
                return inner[group[-1].outs[0]]

            ps = {l.name: params[l.name] for l in group if l.name in params}
            vals[group[-1].outs[0]] = jax.checkpoint(run)(
                ps, *(vals[n] for n in reads))
        raise ValueError("the conf has no lm_head layer")
