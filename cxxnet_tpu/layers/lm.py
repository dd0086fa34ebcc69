"""Language-model layers over sequence nodes (batch, 1, seq, embed):
token embedding, RMS norm, gated FFN, the two token mixers of the
Kimi-Linear family (`kda`, `mla`), the double-gated short convolution
of the LFM2 family (`gconv`), grouped-query attention with rotary, a
window and QK-norm (`gqa`) and the untied head with its next-token loss.

embed     node of token ids (batch, 1, seq, 1), INTEGER, -> (batch, 1,
          seq, nhidden). Keys: nvocab, nhidden. Ids stay integers from
          `DataBatch` to here (a bf16 cast cannot hold an id over 256).
rms_norm  y = x / sqrt(mean(x^2) + eps) * slope, statistics in float32.
          Key: eps (1e-5).
glu_ffn   (SiLU(x Wgate) * (x Wup)) Wdown. Key: nhidden.
kda       Kimi Delta Attention (ops/kda.py): q, k, v through a causal
          depthwise short conv and SiLU, L2-normalised q and k, a
          per-channel log-decay g = -exp(A_log) softplus(low-rank(x) +
          dt_bias), beta = sigmoid(x Wb), the gated delta rule in
          chunks, a per-head RMS norm and a low-rank sigmoid output
          gate. Keys: nhead, head_dim, conv_size (4), gate_rank,
          kda_chunk (64), eps.
gconv     the double-gated short convolution: (B, C, z) = the three
          thirds of x Win; u = B * z; c = the causal depthwise conv of
          `conv_size` taps over time of u (zeros before the start, no
          bias); out = (C * c) Wout. NO activation anywhere. Key:
          conv_size (3).
mla       multi-head latent attention WITHOUT rotary (`mla_use_nope`):
          keys and values come from a `kv_rank`-wide normalised latent,
          a `qk_rope_dim`-wide key part is shared by all heads, queries
          are `qk_nope_dim + qk_rope_dim` wide against values `v_dim`
          wide. Keys: nhead, kv_rank, qk_nope_dim, qk_rope_dim, v_dim,
          eps. The core is causal softmax attention through the flash
          kernel where it runs (ops/pallas_attention.py), else through
          `blockwise_attention`; both take one head size, so the values
          are padded to the query width and cut back.
gqa       grouped-query causal attention: `nhead` query heads read the
          `nkvhead` key/value heads, `nhead // nkvhead` to one, all
          `head_dim` wide, no bias. `qk_norm = 1`: every query head and
          every key head is RMS-normed over its `head_dim` entries (one
          slope vector for all query heads, `qnorm`, one for all key
          heads, `knorm`; `eps`) BEFORE the rotary embedding.
          `rope_theta` > 0 turns q and k by a
          rotary embedding (the halves of a head paired, over the whole
          head, positions 0..seq-1); 0 = no positional encoding.
          `window` > 0: a query sees the `window` positions up to its
          own; 0 = full causal. The core runs through the flash kernel
          where it runs - a window layer walks only the score tiles of
          its band, the shared heads are read through the index map -
          else through `blockwise_attention`, which masks. Counter
          `tiles`: the score tiles the core computes over those of a
          full causal layer (1 on the XLA route, which skips none).
          Keys: nhead, nkvhead, head_dim, window (0), rope_theta (0),
          qk_norm (0), eps (1e-5).
lm_head   inputs: the final hidden node and the token node. Output: the
          logits (batch, 1, seq, nvocab) - what `task = pred` and
          `extract` read. In training its loss term is the mean over
          positions t < seq-1 of the cross-entropy of position t's
          logits against token t+1, summed over rows (the trainer
          divides by the batch), computed `loss_block` positions at a
          time under `jax.checkpoint`, so that the float32 logits of a
          long sequence and their gradient are never all live.
          Keys: nvocab, loss_block (1024).

Every leaf starts from `init_sigma x N(0, 1)` unless said otherwise in
`_start`; the benchmark's reference (benchmark/reference/kimi_linear.py)
mirrors the order the keys are split in.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from cxxnet_tpu.layers.base import Layer, Params, Shape, register_layer
from cxxnet_tpu.ops import attention as ops_attn
from cxxnet_tpu.ops import kda as ops_kda

Table = List[Tuple[str, Tuple[int, ...], str]]


def _start(key: jax.Array, table: Table, sigma: float) -> Params:
    """One key a leaf, in the table's order. `normal`: sigma x N(0, 1);
    `ones`; `conv`: U(+-1/sqrt(width)); `a_log`: log U(1, 16);
    `dt_bias`: the inverse softplus of a step drawn log-uniformly from
    (1e-3, 1e-1)."""
    out: Params = {}
    for (name, shape, how), k in zip(table,
                                     jax.random.split(key, len(table))):
        if how == "normal":
            out[name] = sigma * jax.random.normal(k, shape, jnp.float32)
        elif how == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif how == "conv":
            a = 1.0 / math.sqrt(shape[0])
            out[name] = jax.random.uniform(k, shape, jnp.float32, -a, a)
        elif how == "a_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                   1.0, 16.0))
        elif how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(how)
    return out


def rms_norm(x, slope, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * slope.astype(jnp.float32)).astype(x.dtype)


def _lin(x, w):
    return jnp.einsum("...i,io->...o", x, w.astype(x.dtype))


def _seq(shape: Shape, who: str) -> Tuple[int, int, int]:
    b, c, s, e = shape
    if c != 1:
        raise ValueError(f"{who}: input must be a sequence node")
    return b, s, e


class _TableLayer(Layer):
    """A layer whose leaves are a table; matrices are tagged `wmat`,
    vectors `bias`."""

    #: one checkpoint around this layer under `remat = 1`
    #: (nnet/network.py): only its inputs are kept for the backward,
    #: which runs the forward again. A kind says True where that buys
    #: enough memory for the time (at 8,192 positions, docs/global.md)
    remat_worthy = False

    def table(self, in_shapes: List[Shape]) -> Table:
        raise NotImplementedError

    def init_params(self, key, in_shapes):
        return _start(key, self.table(in_shapes), self.param.init_sigma)

    def param_tags(self) -> Dict[str, str]:
        return {n: ("wmat" if len(s) > 1 else "bias")
                for n, s, _ in self.table(self._in_shapes)}

    def infer_shapes(self, in_shapes):
        self._in_shapes = list(in_shapes)
        return self.shapes(in_shapes)


@register_layer
class EmbedLayer(_TableLayer):
    type_name = "embed"

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.nvocab = 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nvocab":
            self.nvocab = int(val)

    def shapes(self, in_shapes):
        self.check_one_to_one(in_shapes)
        b, s, e = _seq(in_shapes[0], "embed")
        if e != 1:
            raise ValueError("embed: input must be (batch, 1, seq, 1) ids")
        if self.nvocab <= 0 or self.param.num_hidden <= 0:
            raise ValueError("embed: must set nvocab and nhidden")
        return [(b, 1, s, self.param.num_hidden)]

    def table(self, in_shapes):
        return [("wmat", (self.nvocab, self.param.num_hidden), "normal")]

    def apply(self, params, inputs, *, train, rng=None):
        ids = inputs[0]
        if not jnp.issubdtype(ids.dtype, jnp.integer):
            raise TypeError(
                f"embed: token ids must be integers, got {ids.dtype} (a "
                "float batch cannot hold every id exactly)")
        return [jnp.take(params["wmat"], ids[..., 0], axis=0)]


@register_layer
class RMSNormLayer(_TableLayer):
    type_name = "rms_norm"

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.eps = 1e-5

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "eps":
            self.eps = float(val)

    def shapes(self, in_shapes):
        self.check_one_to_one(in_shapes)
        return [in_shapes[0]]

    def table(self, in_shapes):
        return [("slope", (in_shapes[0][3],), "ones")]

    def apply(self, params, inputs, *, train, rng=None):
        return [rms_norm(inputs[0], params["slope"], self.eps)]


@register_layer
class GluFFNLayer(_TableLayer):
    type_name = "glu_ffn"
    #: gate, up and their product, each nhidden wide: 0.45 GB unsaved
    #: for a second forward of 5.5 ms, about 80 MB a ms
    remat_worthy = True

    def shapes(self, in_shapes):
        self.check_one_to_one(in_shapes)
        _seq(in_shapes[0], "glu_ffn")
        if self.param.num_hidden <= 0:
            raise ValueError("glu_ffn: must set nhidden")
        return [in_shapes[0]]

    def table(self, in_shapes):
        e, h = in_shapes[0][3], self.param.num_hidden
        return [("wgate", (e, h), "normal"), ("wup", (e, h), "normal"),
                ("wdown", (h, e), "normal")]

    def apply(self, params, inputs, *, train, rng=None):
        x = inputs[0]
        h = jax.nn.silu(_lin(x, params["wgate"])) * _lin(x, params["wup"])
        return [_lin(h, params["wdown"])]


def short_conv(x, w):
    """Causal depthwise conv over time (axis 1): y_t = sum_j w[j]
    x[t - (K-1) + j], zeros before the start. x (b, T, C), w (K, C)."""
    kk = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (kk - 1, 0), (0, 0)))
    w = w.astype(x.dtype)
    return sum(xp[:, j:j + x.shape[1]] * w[j] for j in range(kk))


@register_layer
class KDALayer(_TableLayer):
    type_name = "kda"
    #: the chunk maps of every head: 1.5 GB unsaved for a second forward
    #: of 17-20 ms, about 80 MB a ms (what `remat` is there for)
    remat_worthy = True

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.nhead = 0
        self.head_dim = 0
        self.conv_size = 4
        self.gate_rank = 0
        self.chunk = 64
        self.eps = 1e-5

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nhead":
            self.nhead = int(val)
        if name == "head_dim":
            self.head_dim = int(val)
        if name == "conv_size":
            self.conv_size = int(val)
        if name == "gate_rank":
            self.gate_rank = int(val)
        if name == "kda_chunk":
            self.chunk = int(val)
        if name == "eps":
            self.eps = float(val)

    def shapes(self, in_shapes):
        self.check_one_to_one(in_shapes)
        _seq(in_shapes[0], "kda")
        if min(self.nhead, self.head_dim, self.gate_rank,
               self.conv_size, self.chunk) <= 0:
            raise ValueError(
                "kda: must set nhead, head_dim and gate_rank")
        return [in_shapes[0]]

    def table(self, in_shapes):
        e = in_shapes[0][3]
        w, r = self.nhead * self.head_dim, self.gate_rank
        kk = self.conv_size
        return [("wq", (e, w), "normal"), ("wk", (e, w), "normal"),
                ("wv", (e, w), "normal"),
                ("conv_q", (kk, w), "conv"), ("conv_k", (kk, w), "conv"),
                ("conv_v", (kk, w), "conv"),
                ("wf1", (e, r), "normal"), ("wf2", (r, w), "normal"),
                ("dt_bias", (w,), "dt_bias"), ("a_log", (self.nhead,), "a_log"),
                ("wb", (e, self.nhead), "normal"),
                ("wg1", (e, r), "normal"), ("wg2", (r, w), "normal"),
                ("onorm", (self.head_dim,), "ones"), ("wo", (w, e), "normal")]

    def apply(self, params, inputs, *, train, rng=None):
        p = params
        b, _, t, e = inputs[0].shape
        x = inputs[0].reshape(b, t, e)
        nh, dk = self.nhead, self.head_dim

        def heads(a):
            return a.reshape(b, t, nh, dk)

        with jax.named_scope("proj"):
            q, k, v = (_lin(x, p[n]) for n in ("wq", "wk", "wv"))
        with jax.named_scope("conv"):
            q, k, v = (heads(jax.nn.silu(short_conv(a, p[c])))
                       for a, c in ((q, "conv_q"), (k, "conv_k"),
                                    (v, "conv_v")))
            qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
            q = (qf * jax.lax.rsqrt(jnp.sum(qf * qf, -1, keepdims=True)
                                    + 1e-6) * dk ** -0.5).astype(x.dtype)
            k = (kf * jax.lax.rsqrt(jnp.sum(kf * kf, -1, keepdims=True)
                                    + 1e-6)).astype(x.dtype)
        with jax.named_scope("gates"):
            # the decay and its softplus in float32 inside a bf16 step
            f = _lin(_lin(x, p["wf1"]), p["wf2"]).astype(jnp.float32)
            g = -jnp.exp(p["a_log"].astype(jnp.float32))[None, None, :, None] \
                * heads(jax.nn.softplus(f + p["dt_bias"].astype(jnp.float32)))
            beta = jax.nn.sigmoid(_lin(x, p["wb"]).astype(jnp.float32))
            gate = jax.nn.sigmoid(_lin(_lin(x, p["wg1"]), p["wg2"]))
        with jax.named_scope("chunk"):
            o = ops_kda.kda_chunked(q, k, v, g, beta, self.chunk)
        with jax.named_scope("out"):
            o = rms_norm(o, p["onorm"], self.eps).reshape(b, t, nh * dk)
            out = _lin(o * gate, p["wo"])
        return [out.reshape(b, 1, t, e)]


@register_layer
class GConvLayer(_TableLayer):
    type_name = "gconv"
    #: x Win (three thirds), B * z, the conv and C * c: at 32,768
    #: positions of 2,048 0.8 GB unsaved a layer for a second forward
    #: of 7.0 ms (one 2048 x 6144 product and three elementwise
    #: passes; my chip runs, PR 39): about 115 MB a ms, more than `kda`
    #: or `glu_ffn` buy. (The one conf that runs it fits with every
    #: layer kept and says `remat = 0`.)
    remat_worthy = True

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.conv_size = 3

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "conv_size":
            self.conv_size = int(val)

    def shapes(self, in_shapes):
        self.check_one_to_one(in_shapes)
        _seq(in_shapes[0], "gconv")
        if self.conv_size <= 0:
            raise ValueError("gconv: conv_size must be 1 or more")
        return [in_shapes[0]]

    def table(self, in_shapes):
        e = in_shapes[0][3]
        return [("win", (e, 3 * e), "normal"),
                ("conv", (self.conv_size, e), "conv"),
                ("wout", (e, e), "normal")]

    def apply(self, params, inputs, *, train, rng=None):
        p = params
        b, _, t, e = inputs[0].shape
        x = inputs[0].reshape(b, t, e)
        with jax.named_scope("proj"):
            bcz = _lin(x, p["win"])
        with jax.named_scope("gate"):
            u = bcz[..., :e] * bcz[..., 2 * e:]
        with jax.named_scope("conv"):
            c = short_conv(u, p["conv"])
        with jax.named_scope("gate"):
            y = bcz[..., e:2 * e] * c
        with jax.named_scope("out"):
            out = _lin(y, p["wout"])
        return [out.reshape(b, 1, t, e)]


@register_layer
class MLALayer(_TableLayer):
    type_name = "mla"
    #: q, k, padded v, o, the latents and `lse`: 0.55 GB unsaved would
    #: cost a second flash forward and the projections again, 21 ms,
    #: about 25 MB a ms: kept, the backward uses what the forward left
    remat_worthy = False

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.nhead = 0
        self.kv_rank = 0
        self.nope = 0
        self.rope = 0
        self.v_dim = 0
        self.eps = 1e-5
        self.kv_block = 512

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nhead":
            self.nhead = int(val)
        if name == "kv_rank":
            self.kv_rank = int(val)
        if name == "qk_nope_dim":
            self.nope = int(val)
        if name == "qk_rope_dim":
            self.rope = int(val)
        if name == "v_dim":
            self.v_dim = int(val)
        if name == "eps":
            self.eps = float(val)

    def shapes(self, in_shapes):
        self.check_one_to_one(in_shapes)
        _seq(in_shapes[0], "mla")
        if min(self.nhead, self.kv_rank, self.nope, self.v_dim) <= 0 \
                or self.rope < 0:
            raise ValueError("mla: must set nhead, kv_rank, qk_nope_dim, "
                             "qk_rope_dim and v_dim")
        if self.v_dim > self.nope + self.rope:
            raise ValueError("mla: v_dim wider than the query")
        return [in_shapes[0]]

    def table(self, in_shapes):
        e, nh = in_shapes[0][3], self.nhead
        return [("wq", (e, nh * (self.nope + self.rope)), "normal"),
                ("wkva", (e, self.kv_rank + self.rope), "normal"),
                ("kvnorm", (self.kv_rank,), "ones"),
                ("wkvb", (self.kv_rank, nh * (self.nope + self.v_dim)),
                 "normal"),
                ("wo", (nh * self.v_dim, e), "normal")]

    def apply(self, params, inputs, *, train, rng=None):
        p = params
        b, _, t, e = inputs[0].shape
        x = inputs[0].reshape(b, t, e)
        nh, nope, rope, dv = self.nhead, self.nope, self.rope, self.v_dim
        with jax.named_scope("proj"):
            a = _lin(x, p["wkva"])
            c = rms_norm(a[..., :self.kv_rank], p["kvnorm"], self.eps)
            k_pe = a[..., self.kv_rank:]                  # (b, T, rope)
            kv = _lin(c, p["wkvb"]).reshape(b, t, nh, nope + dv)
            q = _lin(x, p["wq"]).reshape(b, t, nh, nope + rope)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(
                    k_pe[:, :, None, :], (b, t, nh, rope))], axis=-1)
            # one head size for the core: values padded to the query's
            v = jnp.pad(kv[..., nope:],
                        ((0, 0), (0, 0), (0, 0), (0, nope + rope - dv)))
            q, k, v = (jnp.moveaxis(z, 2, 1) for z in (q, k, v))  # BHSD
        with jax.named_scope("scores"):
            # imported here: Pallas costs every process that imports the
            # layers 1.3-2 s of start-up, which the CNN cells' `setup_s`
            # showed (my chip runs, PR 29)
            from cxxnet_tpu.ops import pallas_attention
            if pallas_attention.use_flash(q):
                o = pallas_attention.flash_attention(
                    q, k, v, True, None, pallas_attention._FORCE_INTERPRET)
            else:
                o = ops_attn.blockwise_attention(
                    q, k, v, causal=True, kv_block=self.kv_block)
        with jax.named_scope("out"):
            o = jnp.moveaxis(o[..., :dv], 1, 2).reshape(b, t, nh * dv)
            out = _lin(o, p["wo"])
        return [out.reshape(b, 1, t, e)]


def rotary(x, theta: float):
    """(b, h, T, d) turned by position: the halves of a head are paired,
    pair i by the angle t * theta^(-i / (d/2)). Angles and the turn in
    float32 inside a bf16 step."""
    t, half = x.shape[2], x.shape[3] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


@register_layer
class GQALayer(_TableLayer):
    type_name = "gqa"
    #: q and o at nhead x head_dim, k, v and `lse`: at 16,384 positions
    #: 0.28 GB unsaved a layer for the projections, rotary and the flash
    #: forward again, 18-30 ms: 5-9 MB a ms, far under the 25 at which
    #: `mla` is kept. Memory no longer asks for it (with `lse` unpadded,
    #: ops/pallas_attention.py `_LANE`, eight kept layers compile and
    #: run beside 7.7 GB of state, 22.6% faster). It stays because on
    #: the chip a kept layer's wo and wv gradients read 0.3-0.5% short
    #: of the float32 reference's norms where a checkpointed layer's
    #: read 0.1-0.2%, and the cell's limit lies between: PERF.md
    #: section 6, PR 37, has the readings and what was ruled out
    remat_worthy = True
    stat_names = ("tiles",)

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.nhead = 0
        self.nkvhead = 0
        self.head_dim = 0
        self.window = 0
        self.rope_theta = 0.0
        self.qk_norm = 0
        self.eps = 1e-5
        self.kv_block = 512

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nhead":
            self.nhead = int(val)
        if name == "nkvhead":
            self.nkvhead = int(val)
        if name == "head_dim":
            self.head_dim = int(val)
        if name == "window":
            self.window = int(val)
        if name == "rope_theta":
            self.rope_theta = float(val)
        if name == "qk_norm":
            self.qk_norm = int(val)
        if name == "eps":
            self.eps = float(val)

    def shapes(self, in_shapes):
        self.check_one_to_one(in_shapes)
        _seq(in_shapes[0], "gqa")
        if min(self.nhead, self.nkvhead, self.head_dim) <= 0:
            raise ValueError("gqa: must set nhead, nkvhead and head_dim")
        if self.nhead % self.nkvhead:
            raise ValueError("gqa: nhead must be a multiple of nkvhead")
        if self.window < 0 or self.rope_theta < 0 or (
                self.rope_theta and self.head_dim % 2):
            raise ValueError("gqa: window and rope_theta are 0 or more, "
                             "and a turned head has an even width")
        return [in_shapes[0]]

    def table(self, in_shapes):
        e, d = in_shapes[0][3], self.head_dim
        table = [("wq", (e, self.nhead * d), "normal"),
                 ("wk", (e, self.nkvhead * d), "normal"),
                 ("wv", (e, self.nkvhead * d), "normal"),
                 ("wo", (self.nhead * d, e), "normal")]
        if self.qk_norm:
            table += [("qnorm", (d,), "ones"), ("knorm", (d,), "ones")]
        return table

    def apply_with_stats(self, params, inputs, *, train, rng=None,
                         mask=None):
        p = params
        b, _, t, e = inputs[0].shape
        x = inputs[0].reshape(b, t, e)
        d = self.head_dim

        def heads(w, n):                                   # -> BHSD
            return jnp.einsum("bte,ehd->bhtd", x,
                              w.astype(x.dtype).reshape(e, n, d))

        with jax.named_scope("proj"):
            q = heads(p["wq"], self.nhead)
            k = heads(p["wk"], self.nkvhead)
            v = heads(p["wv"], self.nkvhead)
        if self.qk_norm:
            with jax.named_scope("qknorm"):
                q = rms_norm(q, p["qnorm"], self.eps)
                k = rms_norm(k, p["knorm"], self.eps)
        if self.rope_theta:
            with jax.named_scope("rope"):
                q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
        with jax.named_scope("scores"):
            # imported here, as `mla` does: Pallas costs every process
            # that imports it 1.3-2 s of start-up
            from cxxnet_tpu.ops import pallas_attention
            if pallas_attention.use_flash(q):
                o = pallas_attention.flash_attention(
                    q, k, v, True, None, pallas_attention._FORCE_INTERPRET,
                    self.window)
                tiles = pallas_attention.tile_share(q, self.window)
            else:
                o = ops_attn.blockwise_attention(
                    q, k, v, causal=True, kv_block=self.kv_block,
                    window=self.window)
                tiles = 1.0
        with jax.named_scope("out"):
            out = jnp.einsum("bhtd,hde->bte", o, p["wo"].astype(o.dtype)
                             .reshape(self.nhead, d, e))
        return ([out.reshape(b, 1, t, e)], None,
                {"tiles": jnp.asarray(tiles, jnp.float32)})

    def apply(self, params, inputs, *, train, rng=None):
        return self.apply_with_stats(params, inputs, train=train,
                                     rng=rng)[0]


@register_layer
class LMHeadLayer(_TableLayer):
    type_name = "lm_head"
    has_aux = True

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.nvocab = 0
        self.loss_block = 1024

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nvocab":
            self.nvocab = int(val)
        if name == "loss_block":
            self.loss_block = int(val)

    def shapes(self, in_shapes):
        if len(in_shapes) != 2:
            raise ValueError("lm_head: inputs are the hidden node and "
                             "the token node")
        b, s, _ = _seq(in_shapes[0], "lm_head")
        if tuple(in_shapes[1]) != (b, 1, s, 1):
            raise ValueError("lm_head: second input must be the token "
                             f"node (batch, 1, seq, 1), got {in_shapes[1]}")
        if self.nvocab <= 0:
            raise ValueError("lm_head: must set nvocab")
        return [(b, 1, s, self.nvocab)]

    def table(self, in_shapes):
        return [("wmat", (in_shapes[0][3], self.nvocab), "normal")]

    def _loss(self, w, y, ids, mask):
        """Sum over rows of the mean over t < T-1 of CE(logits_t,
        id_{t+1}). y (b, T, e), ids (b, T)."""
        b, t, e = y.shape
        blk = min(self.loss_block, t)
        pad = (-t) % blk
        target = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        weight = jnp.broadcast_to(
            (jnp.arange(t) < t - 1).astype(jnp.float32), (b, t))
        if mask is not None:
            weight = weight * mask.astype(jnp.float32)[:, None]
        if pad:
            y = jnp.pad(y, ((0, 0), (0, pad), (0, 0)))
            target = jnp.pad(target, ((0, 0), (0, pad)))
            weight = jnp.pad(weight, ((0, 0), (0, pad)))
        n = (t + pad) // blk

        @jax.checkpoint
        def part(total, xs):
            yb, tb, wb = xs
            with jax.named_scope("logits"):
                logits = jnp.einsum(
                    "bte,ev->btv", yb, w.astype(yb.dtype),
                    preferred_element_type=jnp.float32)
            with jax.named_scope("loss"):
                logz = jax.nn.logsumexp(logits, axis=-1)
                got = jnp.take_along_axis(logits, tb[..., None],
                                          axis=-1)[..., 0]
                return total + jnp.sum((logz - got) * wb), None

        def blocks(a):
            return jnp.moveaxis(a.reshape((b, n, blk) + a.shape[2:]), 1, 0)

        total, _ = jax.lax.scan(part, jnp.zeros((), jnp.float32),
                                (blocks(y), blocks(target), blocks(weight)))
        return total / max(t - 1, 1)

    def apply_with_aux(self, params, inputs, *, train, rng=None, mask=None):
        b, _, t, e = inputs[0].shape
        y = inputs[0].reshape(b, t, e)
        ids = inputs[1].reshape(b, t)
        # the whole logits are this node's value; a train step that
        # reads no metric from them never computes them
        with jax.named_scope("logits"):
            logits = _lin(y, params["wmat"]).reshape(b, 1, t, self.nvocab)
        loss = (self._loss(params["wmat"], y, ids, mask) if train
                else jnp.zeros((), jnp.float32))
        return [logits], loss

    def apply(self, params, inputs, *, train, rng=None):
        return self.apply_with_aux(params, inputs, train=train, rng=rng)[0]
