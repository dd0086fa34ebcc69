"""Plain reference for the `lfm2` family (LFM2-24B-A2B): forward, loss,
gradients and Adam in `jax.numpy`, float32, every product at
`Precision.HIGHEST`, nothing of the program.

`x` is (T, d); layers count from 0; eps from the conf; no bias anywhere.
`layer_types` is conv, conv, full_attention, conv, conv, conv,
full_attention, ...; the first `num_dense_layers` layers have a dense
feed-forward, the others the experts:

    a = RMSNorm(x; g_op)
    conv layer:  (B, C, z) = split3(a W_in)
                 u    = B * z
                 c[t] = sum_j w[j] * u[t - (K-1) + j]      K taps, causal,
                                                           zeros before the start
                 m    = (C * c) W_out                      NO activation
    full layer:  q = a Wq (nhead x head_dim), k = a Wk, v = a Wv (nkvhead)
                 q_h <- RMSNorm(q_h; gq), k_h <- RMSNorm(k_h; gk)   per head,
                                                           BEFORE the rotary
                 q, k <- rotary(q, k; theta, positions 0..T-1)
                 s[t,u] = q_h[t] . k_(h // group)[u] / sqrt(head_dim), u <= t
                 m    = concat_h(softmax(s) v_(h // group)) Wo
    y = x + m
    b = RMSNorm(y; g_ffn)
    dense layer: f = (SiLU(b W1) * (b W3)) W2
    else:        s = sigmoid(b Wr^T) over all experts
                 chosen = top_k(s + bias)                  the bias chooses and
                                                           does not weigh; no
                                                           gradient
                 w_i = scale * s_i / (sum over chosen of s + moe_norm_eps)
                 f = sum over i chosen AND held here of w_i E_i(b)
    x' = y + f

    after the last layer RMSNorm, logits = x W_lm (untied), and the loss
    of `kimi_linear.py`: the mean over positions t < T-1 of the
    cross-entropy of position t's logits against token t+1.

The conf states a published layer as six conf layers (`rms_norm`, `gconv`
or `gqa`, `add`, `rms_norm`, `glu_ffn` or `moe`, `add`) and this module
follows the conf layer by layer, as `kimi_linear.Reference` does, whose
reading of the conf, start of every leaf, loss in blocks, sigmoid router
leaves and Adam it inherits, with `smallthinker.Reference`'s one
checkpoint a PUBLISHED layer and its attention in blocks of query rows.
What is new is here: `gconv`, QK-norm in `gqa`, the 1e-6 under the
router's weights, the dense feed-forward in blocks of `FFN_BLOCK`
positions (three maps of 32,768 x 11,776 float32 are 4.6 GB), the held
experts as a scan, and the products the new layers require. None of it
changes a number.

Departures from the published model, each also under `assumed` in the
configuration's file: the QK-norm, the absence of an activation in the
conv mixer and the final norm's place are the family's published
modelling code, not keys of the config; the head is untied; the
selection bias is seeded and fixed; no auxiliary loss; the initial
values, Adam's settings.

`variant` puts something else in the reference's place, for the readings
a cell's limits are set from: a dtype name (`float8_e4m3fn`) rounds the
operands of every weight-bearing product to that type (the control);
`conv_silu` puts SiLU after the conv (as `kda`'s conv has); `no_c_gate`
leaves C out; `no_qknorm` leaves the two head norms out; `rope_off`
turns nothing; `softmax_router` scores by softmax over the experts;
`no_routed` zeroes the held experts' part.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import confnet
from benchmark.reference import kimi_linear as base
from benchmark.reference import smallthinker
from benchmark.reference.kimi_linear import (_int, held_of, mm, rms, rotary,
                                             short_conv, silu)

FAULTS = ("conv_silu", "no_c_gate", "no_qknorm", "rope_off",
          "softmax_router", "no_routed")
FFN_BLOCK = 4096          # positions a block of the dense feed-forward


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
        elif lay.type in ("rms_norm", "gconv", "gqa", "glu_ffn", "moe",
                          "add"):
            out = lay.in_shapes[0]
        else:
            raise NotImplementedError(f"layer type {lay.type!r}")
        lay.out_shape = out
        shapes[lay.outs[0]] = out


def products(lay) -> List[Tuple[str, int, int]]:
    """(name, a, b): the matrix products a conf layer REQUIRES for one
    row, each as `a x b` multiply-adds: `smallthinker.products` for
    `gqa` (scores and values count causal pairs only), `moe` and the
    head, `kimi_linear.products` for the dense feed-forward; a conv
    mixer's two projections (its three taps a channel are elementwise
    work, which `flops.py` does not count)."""
    if lay.type == "gconv":
        t, d = lay.in_shapes[0]
        return [("in", t * d, 3 * d), ("out", t * d, d)]
    if lay.type == "glu_ffn":
        return base.products(lay)
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


class Reference(smallthinker.Reference):
    def __init__(self, conf_text: str, overrides: Dict[str, str],
                 variant: Optional[str] = None):
        self.net = base.read_conf(conf_text, overrides)
        infer_shapes(self.net)
        self.conf_layers = list(self.net.layers)
        self.groups = smallthinker.published_layers(self.conf_layers)
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
        if lay.type == "gconv":
            return [("win", (d, 3 * d), "normal"),
                    ("conv", (_int(lay, "conv_size", "3"), d), "conv"),
                    ("wout", (d, d), "normal")]
        table = smallthinker.Reference._table(lay)
        if lay.type == "gqa" and _int(lay, "qk_norm"):
            dh = _int(lay, "head_dim")
            table = table + [("qnorm", (dh,), "ones"), ("knorm", (dh,), "ones")]
        return table

    # the sigmoid router's leaves, selection bias among them
    _init_moe = staticmethod(base.Reference._init_moe)

    # -- forward ------------------------------------------------------
    def _gconv(self, lay, p, x):
        d = x.shape[1]
        bcz = self.lin(x, p["win"])
        b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
        y = short_conv(b * z, p["conv"])
        if self.fault == "conv_silu":
            y = silu(y)
        if self.fault != "no_c_gate":
            y = c * y
        return self.lin(y, p["wout"])

    def _gqa(self, lay, p, x):
        nh, nkv, dh = (_int(lay, "nhead"), _int(lay, "nkvhead"),
                       _int(lay, "head_dim"))
        t = x.shape[0]
        eps = float(lay.get("eps", "1e-5"))
        theta = float(lay.get("rope_theta", "0"))
        q = self.lin(x, p["wq"]).reshape(t, nh, dh)
        k = self.lin(x, p["wk"]).reshape(t, nkv, dh)
        v = self.lin(x, p["wv"]).reshape(t, nkv, dh)
        if "qnorm" in p and self.fault != "no_qknorm":
            q, k = rms(q, p["qnorm"], eps), rms(k, p["knorm"], eps)
        if theta and self.fault != "rope_off":
            q, k = rotary(q, theta), rotary(k, theta)
        # query head h reads key/value head h // (nh // nkv)
        o = smallthinker.attend(q.reshape(t, nkv, nh // nkv, dh), k, v,
                                _int(lay, "window"))
        return self.lin(o.reshape(t, nh * dh), p["wo"])

    def _glu(self, x, wgate, wup, wdown):
        """The dense feed-forward `FFN_BLOCK` positions at a time, each
        block under a checkpoint."""
        t = x.shape[0]
        blk = max(b for b in range(1, min(t, FFN_BLOCK) + 1) if t % b == 0)
        glu = super()._glu

        @jax.checkpoint
        def rows(xb):
            return glu(xb, wgate, wup, wdown)

        return lax.map(rows, x.reshape(t // blk, blk, -1)).reshape(x.shape)

    def _moe(self, lay, p, b, a=None):
        # (`a`: the router's own node where a conf gives it one, which
        # `smallthinker.Reference._apply` hands over; this router reads b)
        k = _int(lay, "moe_top_k", "1")
        first, held = held_of(lay)
        logits = mm(b, p["gate"].T)                          # (T, E)
        s = jax.nn.softmax(logits, axis=-1) \
            if self.fault == "softmax_router" else jax.nn.sigmoid(logits)
        _, chosen = lax.top_k(s + lax.stop_gradient(p["sbias"]), k)
        picked = jnp.take_along_axis(s, chosen, axis=1)
        w = float(lay.get("moe_scale", "1")) * picked / (
            jnp.sum(picked, axis=1, keepdims=True)
            + float(lay.get("moe_norm_eps", "0")))
        if self.fault == "no_routed":
            return jnp.zeros_like(b)

        @jax.checkpoint
        def add_expert(out, ws):
            """Every held expert on all tokens, weighted by 0 where it
            was not chosen (a scan: one body, and the backward computes
            an expert's products again)."""
            w1, w3, w2, e = ws
            w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
            hid = silu(self.lin(b, w1.T)) * self.lin(b, w3.T)
            return out + w_e[:, None] * self.lin(hid, w2.T), None

        return lax.scan(add_expert, jnp.zeros_like(b), (
            p["w1"], p["w3"], p["w2"], first + jnp.arange(held)))[0]

    def _apply(self, lay, p, ins):
        if lay.type == "gconv":
            return self._gconv(lay, p, ins[0])
        return super()._apply(lay, p, ins)
