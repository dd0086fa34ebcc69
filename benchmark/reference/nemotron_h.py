"""Plain reference for the `nemotron_h` family
(NVIDIA-Nemotron-3-Super-120B-A12B): forward, loss, gradients and Adam
in `jax.numpy`, float32, every product at `Precision.HIGHEST`, nothing
of the program.

`x` is (T, d); eps from the conf; no bias but the conv's. Every layer
is x' = x + Mixer(RMSNorm(x)), the mixer one of:

    Mamba-2  [z | xBC | dt] = a W_in
             xBC = SiLU(sum_j w[j] xBC[t - 3 + j] + bias)   4 taps, causal
             X (H heads of P), B, C (G groups of N) = split(xBC)
             dt = softplus(dt + dt_bias),  A = -exp(A_log)  one a head
             Y_t = sum_{i<=t} (C_t . B_i) exp(sum_{i<j<=t} dt_j A)
                   dt_i X_i  +  D X_t                      head h reads
                                                           group h // (H/G)
             Y = RMSNorm over groups of H P / G channels of (Y * SiLU(z))
             out = Y W_out
    attention  q = a Wq (nhead heads), k = a Wk, v = a Wv (nkvhead),
             s[t,u] = q_h[t] . k_(h // group)[u] / sqrt(head_dim), u <= t,
             NO positional encoding; out = concat_h(softmax(s) v) Wo
    LatentMoE  s = sigmoid(a Wr^T) over all experts; chosen = top_k(s + b)
             (b chooses and does not weigh, takes no gradient);
             w_i = scale * s_i / sum over the chosen of s
             u = a W_down^T;  e_i(u) = relu(u W1_i^T)^2 W2_i^T
             out = (sum over i chosen AND held here of w_i e_i(u)) W_up^T
                   + relu(a S1^T)^2 S2^T                   the shared expert

    after the last layer RMSNorm, logits = x W_lm (untied), and the loss
    of `kimi_linear.py`: the mean over t < T-1 of CE(logits_t, token_{t+1}).
    MTP:     h'_t = W_eh [RMSNorm(E(token_{t+1})); RMSNorm(h_t)], h the
             trunk's output before its final norm, E the embedding; then
             the layers the conf gives (attention, LatentMoE), RMSNorm,
             and the same head against token_{t+2}: the mean over
             t < T-2; the total loss is L + loss_weight * L_mtp.

The Mamba-2 output is computed in its quadratic dual form, `SSD_BLOCK`
query positions at a time against every key up to them (the program
computes chunks and carries a state between them: ops/ssd.py). The
decay sum_{i<j<=t} a_j is taken, for a block that starts at `lo`, as
W(t) + V(i): W the running sum from `lo`, V(i) the sum from i+1 to
lo-1 for a key before the block (a running sum from the block's start
backwards) and -W(i) inside it, so that a sum is never the difference
of two sums from the row's start, whose float32 rounding at 8,192
positions would be larger than the decays it leaves.

The conf states a layer as three conf layers (`rms_norm`, the mixer,
`add`); this module follows the conf layer by layer, each such triple
under one checkpoint, and takes the reading of the conf, the start of
every leaf, the loss in blocks and Adam from `kimi_linear.Reference`,
the attention in blocks from `smallthinker.attend`.

Departures from the published model, each also under `assumed` in the
configuration's file: no positional encoding in the attention (the
family's modelling code reads neither rope_theta nor
partial_rotary_factor); the MTP wiring and its weight; the selection
bias seeded and fixed; no auxiliary loss; the initial values; Adam's
settings.

`variant` puts something else in the reference's place, for the readings
a cell's limits are set from: a dtype name (`float8_e4m3fn`) rounds the
operands of every weight-bearing product to that type (the control);
`rotary_on` turns q and k by a rotary embedding (theta 10,000, the
config's rope_theta); `relu_experts` leaves the experts' ReLU unsquared;
`ungated_norm` gates after the norm; `no_D` leaves D X out;
`mtp_unshifted` feeds the MTP the embedding of token t.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import confnet
from benchmark.reference import kimi_linear as base
from benchmark.reference import smallthinker
from benchmark.reference.kimi_linear import (HI, _int, held_of, mm, rms,
                                             rotary, short_conv, silu)

FAULTS = ("rotary_on", "relu_experts", "ungated_norm", "no_D",
          "mtp_unshifted")
SSD_BLOCK = 256           # query positions a block of the dual form
MIXERS = ("mamba2", "gqa", "moe", "mtp")


# ---------------------------------------------------------------------------
# the conf as a net: shapes, and the matrix products each layer needs
# ---------------------------------------------------------------------------
def _shared_heads(net: confnet.Net) -> None:
    """`share[tag]:name` -> type `share`, `lay.primary` the tag's layer;
    the connection's own pairs (target_offset, loss_weight) stay its
    own."""
    by_name = {l.name: l for l in net.layers}
    for lay in net.layers:
        if lay.type.startswith("share["):
            lay.primary = by_name[lay.type[len("share["):-1]]
            lay.type = "share"


def infer_shapes(net: confnet.Net) -> None:
    seq = net.input_shape[1]
    shapes: Dict[str, Tuple[int, ...]] = {"0": (seq,)}
    for lay in net.layers:
        lay.in_shapes = [shapes[n] for n in lay.ins]
        if lay.type == "embed":
            out = (seq, _int(lay, "nhidden"))
        elif lay.type == "lm_head":
            out = (seq, _int(lay, "nvocab"))
        elif lay.type == "share":
            out = lay.primary.out_shape
        elif lay.type in ("rms_norm", "add") + MIXERS:
            out = lay.in_shapes[0]
        else:
            raise NotImplementedError(f"layer type {lay.type!r}")
        lay.out_shape = out
        shapes[lay.outs[0]] = out


def mamba_widths(lay) -> Tuple[int, int, int, int, int, int]:
    """(H, P, G, N, d_in, the conv's channels)."""
    h, p, g, n = (_int(lay, "nhead"), _int(lay, "head_dim"),
                  _int(lay, "ngroups", "1"), _int(lay, "ssm_state"))
    return h, p, g, n, h * p, h * p + 2 * g * n


def products(lay) -> List[Tuple[str, int, int]]:
    """(name, a, b): the matrix products a conf layer REQUIRES for one
    row, each as `a x b` multiply-adds: `smallthinker.products` for `gqa`
    and the head; the head's second pass; the MTP's projection; a
    Mamba-2 layer's two projections and its SSD in chunks of
    `ssd_chunk` (C.B over the causal pairs of a chunk a group; a head's
    weighted sum over them, its chunk's contribution to the state and
    its output from the state before it: the conv and the gated norm are
    elementwise work, which `flops.py` does not count); a LatentMoE
    layer's router, latent pair, expected held assignments through two
    products (no gate) and shared expert."""
    if lay.type not in MIXERS + ("lm_head", "share"):
        return []
    t, d = lay.in_shapes[0]
    if lay.type == "share":
        return [("logits", t * d, lay.out_shape[1])]
    if lay.type == "mtp":
        return [("eh", t * 2 * d, d)]
    if lay.type == "mamba2":
        h, p, g, n, d_in, conv = mamba_widths(lay)
        q = _int(lay, "ssd_chunk", "128")
        return [("in", t * d, d_in + conv + h), ("out", t * d_in, d),
                ("ssd", t, g * (q // 2) * n + h * (q // 2) * p
                 + 2 * h * p * n)]
    if lay.type == "moe":
        e, k, hid = (_int(lay, "nexpert"), _int(lay, "moe_top_k", "1"),
                     _int(lay, "nhidden"))
        lat = _int(lay, "moe_latent")
        rows = [("router", t * d, e), ("latent_in", t * d, lat),
                ("latent_out", t * lat, d),
                ("routed", (t * k * held_of(lay)[1] // e) * lat, 2 * hid)]
        if _int(lay, "moe_shared_width"):
            rows.append(("shared", t * d, 2 * _int(lay, "moe_shared_width")))
        return rows
    return smallthinker.products(lay)


def with_products(net: confnet.Net) -> None:
    """`kimi_linear.with_products` over this module's `products`."""
    rows = []
    for lay in net.layers:
        for name, a, b in products(lay):
            rows.append(confnet.Layer(
                len(net.layers) + len(rows), "fullc",
                f"{lay.name}/{name}", [lay.outs[0]], [f"_{lay.name}/{name}"],
                list(lay.pairs), [(a,)], (b,)))
    net.layers.extend(rows)


# ---------------------------------------------------------------------------
# the SSD, in its dual form
# ---------------------------------------------------------------------------
def ssd_dual(x, dt, a, b, c):
    """x (T, H, P), dt (T, H), a (H,), b, c (T, G, N) -> y (T, H, P):
    y_t = sum_{i<=t} (C_t . B_i) exp(sum_{i<j<=t} dt_j a) dt_i x_i."""
    t, h, p = x.shape
    g = b.shape[1]
    r = h // g
    blk = max(s for s in range(1, min(t, SSD_BLOCK) + 1) if t % s == 0)
    la = (dt * a).reshape(t, g, r)                    # <= 0
    dtx = (dt[..., None] * x).reshape(t, g, r, p)
    pos = jnp.arange(t)

    @jax.checkpoint
    def rows(lo):
        inside = (pos >= lo) & (pos < lo + blk)
        w_run = jnp.cumsum(jnp.where(inside[:, None, None], la, 0.0), 0)
        before = jnp.where((pos < lo)[:, None, None], la, 0.0)
        # sum over lo > j > i of a_j: from the block's start backwards
        back = jnp.cumsum(before[::-1], 0)[::-1]
        back = jnp.concatenate([back[1:], jnp.zeros_like(back[:1])], 0)
        v = jnp.where((pos < lo)[:, None, None], back, -w_run)
        wq = lax.dynamic_slice_in_dim(w_run, lo, blk, 0)     # (blk, g, r)
        cq = lax.dynamic_slice_in_dim(c, lo, blk, 0)
        qpos = lo + jnp.arange(blk)
        seen = (pos[None, :] <= qpos[:, None])[..., None, None]
        decay = jnp.exp(jnp.where(seen, wq[:, None] + v[None], -jnp.inf))
        cb = jnp.einsum("qgn,kgn->qkg", cq, b, precision=HI)
        return jnp.einsum("qkgr,kgrp->qgrp", cb[..., None] * decay, dtx,
                          precision=HI)

    return lax.map(rows, jnp.arange(0, t, blk)).reshape(t, h, p)


class Reference(base.Reference):
    def __init__(self, conf_text: str, overrides: Dict[str, str],
                 variant: Optional[str] = None):
        self.net = base.read_conf(conf_text, overrides)
        _shared_heads(self.net)
        infer_shapes(self.net)
        self.conf_layers = list(self.net.layers)
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

    # -- weights ------------------------------------------------------
    @staticmethod
    def _table(lay):
        d = lay.in_shapes[0][-1] if len(lay.in_shapes[0]) > 1 else 0
        if lay.type == "mamba2":
            h, _, _, _, d_in, conv = mamba_widths(lay)
            return [("win", (d, d_in + conv + h), "normal"),
                    ("conv", (_int(lay, "conv_size", "4"), conv), "conv"),
                    ("conv_b", (conv,), "zeros"),
                    ("dt_bias", (h,), "dt_bias"), ("a_log", (h,), "a_log"),
                    ("d", (h,), "ones"), ("norm", (d_in,), "ones"),
                    ("wout", (d_in, d), "normal")]
        if lay.type == "mtp":
            return [("enorm", (d,), "ones"), ("hnorm", (d,), "ones"),
                    ("weh", (2 * d, d), "normal")]
        return smallthinker.Reference._table(lay)

    @staticmethod
    def _moe_names(lay) -> List[str]:
        return (["gate", "w1", "w2"]
                + (["s1", "s2"] if _int(lay, "moe_shared_width") else [])
                + (["lat_in", "lat_out"] if _int(lay, "moe_latent") else []))

    def init(self, seed: int):
        params = super().init(seed)
        for lay in self.conf_layers:       # the leaves `init` leaves out
            for name, shape, how in self._table(lay):
                if how == "zeros":
                    params[lay.name][name] = jnp.zeros(shape, jnp.float32)
        return params

    @staticmethod
    def _init_moe(lay, k, sigma):
        """The program's order of keys: the router, W1, W2 from one
        split, the shared expert, the selection bias and the latent pair
        each from a key folded in."""
        d = lay.in_shapes[0][-1]
        e, h = _int(lay, "nexpert"), _int(lay, "nhidden")
        lat = _int(lay, "moe_latent") or d
        held = held_of(lay)[1]
        kg, k1, k2 = jax.random.split(k, 3)

        def normal(kk, shape):
            return sigma * jax.random.normal(kk, shape, jnp.float32)

        p = {"gate": normal(kg, (e, d)), "w1": normal(k1, (held, h, lat)),
             "w2": normal(k2, (held, lat, h))}
        hs = _int(lay, "moe_shared_width")
        if hs:
            p["s1"] = normal(jax.random.fold_in(k, 4), (hs, d))
            p["s2"] = normal(jax.random.fold_in(k, 6), (d, hs))
        p["sbias"] = float(lay.get("moe_bias_sigma", "0")) * \
            jax.random.normal(jax.random.fold_in(k, 7), (e,), jnp.float32)
        if _int(lay, "moe_latent"):
            p["lat_in"] = normal(jax.random.fold_in(k, 8), (lat, d))
            p["lat_out"] = normal(jax.random.fold_in(k, 9), (d, lat))
        return p

    def hyper(self):
        """`kimi_linear.Reference.hyper`, with this router's leaves."""
        out = super().hyper()
        for lay in self.conf_layers:
            if lay.type == "moe":
                h = next(iter(out[lay.name].values()))
                out[lay.name] = {n: dict(h) for n in self._moe_names(lay)}
        return out

    # -- forward ------------------------------------------------------
    def _mamba2(self, lay, p, x):
        h, hd, g, n, d_in, conv = mamba_widths(lay)
        t = x.shape[0]
        eps = float(lay.get("eps", "1e-5"))
        zxd = self.lin(x, p["win"])
        z, xbc, dt = (zxd[:, :d_in], zxd[:, d_in:d_in + conv],
                      zxd[:, d_in + conv:])
        xbc = silu(short_conv(xbc, p["conv"]) + p["conv_b"])
        xs = xbc[:, :d_in].reshape(t, h, hd)
        bb = xbc[:, d_in:d_in + g * n].reshape(t, g, n)
        cc = xbc[:, d_in + g * n:].reshape(t, g, n)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = ssd_dual(xs, dt, -jnp.exp(p["a_log"]), bb, cc)
        if self.fault != "no_D":
            y = y + p["d"][:, None] * xs
        y = y.reshape(t, d_in)

        def group_norm(v):
            v = v.reshape(t, g, d_in // g)
            v = v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
            return v.reshape(t, d_in) * p["norm"]

        if self.fault == "ungated_norm":
            y = group_norm(y) * silu(z)
        else:
            y = group_norm(y * silu(z))
        return self.lin(y, p["wout"])

    def _gqa(self, lay, p, x):
        nh, nkv, dh = (_int(lay, "nhead"), _int(lay, "nkvhead"),
                       _int(lay, "head_dim"))
        t = x.shape[0]
        q = self.lin(x, p["wq"]).reshape(t, nh, dh)
        k = self.lin(x, p["wk"]).reshape(t, nkv, dh)
        v = self.lin(x, p["wv"]).reshape(t, nkv, dh)
        if self.fault == "rotary_on":
            q, k = rotary(q, 10000.0), rotary(k, 10000.0)
        o = smallthinker.attend(q.reshape(t, nkv, nh // nkv, dh), k, v, 0)
        return self.lin(o.reshape(t, nh * dh), p["wo"])

    def _moe(self, lay, p, x):
        k = _int(lay, "moe_top_k", "1")
        first, held = held_of(lay)
        s = jax.nn.sigmoid(mm(x, p["gate"].T))              # (T, E)
        _, chosen = lax.top_k(s + lax.stop_gradient(p["sbias"]), k)
        picked = jnp.take_along_axis(s, chosen, axis=1)
        w = float(lay.get("moe_scale", "1")) * picked / (
            jnp.sum(picked, axis=1, keepdims=True)
            + float(lay.get("moe_norm_eps", "0")))
        sq = self.fault != "relu_experts"

        def act(v):
            v = jax.nn.relu(v)
            return v * v if sq else v

        u = self.lin(x, p["lat_in"].T)

        @jax.checkpoint
        def add_expert(out, ws):
            """Every held expert on all tokens, weighted by 0 where it
            was not chosen (a scan: one body, and the backward computes
            an expert's products again)."""
            w1, w2, e = ws
            w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
            return out + w_e[:, None] * self.lin(act(self.lin(u, w1.T)),
                                                 w2.T), None

        routed = lax.scan(add_expert, jnp.zeros_like(u), (
            p["w1"], p["w2"], first + jnp.arange(held)))[0]
        out = self.lin(routed, p["lat_out"].T)
        if "s1" in p:
            out = out + self.lin(act(self.lin(x, p["s1"].T)), p["s2"].T)
        return out

    def _mtp(self, lay, p, h, emb):
        nxt = emb if self.fault == "mtp_unshifted" else jnp.concatenate(
            [emb[1:], jnp.zeros_like(emb[:1])], 0)
        eps = float(lay.get("eps", "1e-5"))
        return self.lin(jnp.concatenate(
            [rms(nxt, p["enorm"], eps), rms(h, p["hnorm"], eps)], -1),
            p["weh"])

    def _apply(self, lay, p, ins):
        if lay.type == "mamba2":
            return self._mamba2(lay, p, ins[0])
        if lay.type == "gqa":
            return self._gqa(lay, p, ins[0])
        if lay.type == "moe":
            return self._moe(lay, p, ins[0])
        if lay.type == "mtp":
            return self._mtp(lay, p, ins[0], ins[1])
        return super()._apply(lay, p, ins)

    def _loss_at(self, lay, p, y, tokens, k: int):
        """Sum over positions t < T-k of CE(logits_t, token_{t+k}), over
        T-k, in blocks of positions."""
        t = y.shape[0]
        blk = int(lay.get("loss_block", "1024"))
        target = jnp.concatenate([tokens[k:], tokens[:k]])
        weight = (jnp.arange(t) < t - k).astype(jnp.float32)

        @jax.checkpoint
        def part(yb, tb, wb):
            logits = self.lin(yb, p["wmat"])
            logz = jax.nn.logsumexp(logits, axis=-1)
            got = jnp.take_along_axis(logits, tb[:, None], axis=1)[:, 0]
            return jnp.sum((logz - got) * wb)

        total = jnp.zeros((), jnp.float32)
        for lo in range(0, t, blk):
            sl = slice(lo, lo + blk)
            total = total + part(y[sl], target[sl], weight[sl])
        return total / (t - k)

    def row_loss(self, params, tokens, *, logits: bool = False):
        """One row's loss, L + loss_weight x L_mtp; with `logits` the
        trunk's full (T, V) logits instead (tests and tiny sizes only).
        A norm, its mixer and the `add` after it run under one
        checkpoint, so that the backward keeps the (T, d) stream
        between layers and runs a layer again."""
        vals = {"0": tokens}
        total = jnp.zeros((), jnp.float32)
        layers = self.conf_layers
        i = 0
        while i < len(layers):
            lay = layers[i]
            if lay.type in ("lm_head", "share"):
                head = lay.primary if lay.type == "share" else lay
                y, ids = (vals[n] for n in lay.ins)
                if logits and lay.type == "lm_head":
                    return self.lin(y, params[head.name]["wmat"])
                total = total + float(lay.get("loss_weight", "1")) * \
                    self._loss_at(head, params[head.name], y, ids,
                                  _int(lay, "target_offset", "1"))
                i += 1
                continue
            j = i + 2 if (lay.type == "rms_norm" and i + 2 < len(layers)
                          and layers[i + 2].type == "add") else i
            group = layers[i:j + 1]
            reads = sorted({n for l in group for n in l.ins}
                           - {n for l in group for n in l.outs})

            def run(ps, *given, group=group, reads=reads):
                inner = dict(zip(reads, given))
                for g in group:
                    inner[g.outs[0]] = self._apply(
                        g, ps.get(g.name, {}), [inner[n] for n in g.ins])
                return inner[group[-1].outs[0]]

            ps = {l.name: params[l.name] for l in group if l.name in params}
            vals[group[-1].outs[0]] = jax.checkpoint(run)(
                ps, *(vals[n] for n in reads))
            i = j + 1
        if logits:
            raise ValueError("the conf has no lm_head layer")
        return total
