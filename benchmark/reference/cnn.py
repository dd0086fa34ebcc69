"""Plain reference for a CNN that a cxxnet `.conf` describes: weights
from the seed, forward pass, loss, gradients and the SGD-momentum
update, in straightforward `jax.numpy`, float32 and precision
"highest". No kernel, no program code, nothing the program made.

It follows the reference implementation's published semantics, which
the conf states by naming the layers (cxxnet `src/layer/*`):

- conv, fullc: bias added; grouped conv by `ngroup`.
- max_pooling: the last window may hang over the edge; backward gives
  the window's gradient to EVERY position equal to the window's maximum
  (mshadow `unpool<maximum>`). Where the conf says `pool_grad = winner`
  it goes to one position a window, the first maximum in window order
  (the gradient of `lax.reduce_window` as it stands).
- avg_pooling divides by the full window. lrn sums squares over
  `local_size` channels, window `[c - n//2, c + n - n//2 - 1]`.
- batch_norm normalises by the statistics of the current batch (there
  are no running statistics), eps 1e-10.
- dropout: `x * (u < keep) / keep`. The conf fixes the stream by the
  trainer's rule: `u = uniform(fold_in(fold_in(PRNGKey(seed + 100),
  step), layer index), shape, conf dtype)`. The reference draws the
  same mask by that rule; two different masks would differ by more than
  any precision does.
- softmax: mean cross-entropy over the batch.
- weights: layer `i` draws from `fold_in(PRNGKey(seed), i)` by its
  `random_type` (gaussian `init_sigma`; xavier U(+-sqrt(3/(in+out)));
  kaiming N(0, 2/fan)), biases `init_bias`, batch-norm slope 1, bias 0.
- updater `sgd`: `m = momentum*m - lr*(g + wd*w); w += m`, lr by the
  conf's schedule at the count of updates so far, `wmat:`/`bias:`
  prefixes scoping a setting to weights or biases (batch-norm's slope
  counts as a weight).

`quant` turns it into the control: every operand of a conv or fullc
(activations and weights) is rounded to that type first, float8 e4m3
being the precision next below the confs' bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import confnet

HI = lax.Precision.HIGHEST
Params = Dict[str, Dict[str, jax.Array]]


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------
def _pool_hi(n: int, k: int, s: int) -> int:
    out = confnet.pool_out(n, k, s)
    return max(0, (out - 1) * s + k - n)


def max_pool_winner(x, k, s):
    """Max pooling as `lax.reduce_window` differentiates it: one winner
    a window."""
    hy, hx = _pool_hi(x.shape[2], k, s), _pool_hi(x.shape[3], k, s)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                             (1, 1, s, s),
                             ((0, 0), (0, 0), (0, hy), (0, hx)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def max_pool(x, k, s):
    """The same forward, with the ties rule in the backward pass."""
    return max_pool_winner(x, k, s)


def _max_pool_fwd(x, k, s):
    out = max_pool(x, k, s)
    return out, (x, out)


def _max_pool_bwd(k, s, res, g):
    x, out = res
    h, w = x.shape[2:]
    oh, ow = out.shape[2:]
    ph, pw = (oh - 1) * s + k, (ow - 1) * s + k
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, ph - h), (0, pw - w)),
                 constant_values=-jnp.inf)
    gin = jnp.zeros(xp.shape, g.dtype)
    for dy in range(k):
        for dx in range(k):
            src = xp[:, :, dy:dy + (oh - 1) * s + 1:s,
                     dx:dx + (ow - 1) * s + 1:s]
            part = jnp.where(src == out, g, 0.0)
            # put part[o] back at position o*s + d: pad, s-1 zeros between
            gin = gin + lax.pad(part, jnp.zeros((), g.dtype), (
                (0, 0, 0), (0, 0, 0),
                (dy, ph - dy - ((oh - 1) * s + 1), s - 1),
                (dx, pw - dx - ((ow - 1) * s + 1), s - 1)))
    return (gin[:, :, :h, :w],)


max_pool.defvjp(_max_pool_fwd, _max_pool_bwd)


def avg_pool(x, k, s):
    hy, hx = _pool_hi(x.shape[2], k, s), _pool_hi(x.shape[3], k, s)
    out = lax.reduce_window(x, 0.0, lax.add, (1, 1, k, k), (1, 1, s, s),
                            ((0, 0), (0, 0), (0, hy), (0, hx)))
    return out / (k * k)


def lrn(x, n, alpha, beta, knorm):
    lo = n // 2
    c = x.shape[1]
    sq = jnp.pad(x * x, ((0, 0), (lo, n - lo - 1), (0, 0), (0, 0)))
    win = sum(sq[:, j:j + c] for j in range(n))
    return x * jnp.power(knorm + (alpha / n) * win, -beta)


def batch_norm(x, slope, bias, eps):
    axes = (0, 2, 3) if x.ndim == 4 else (0,)
    shape = (1, -1, 1, 1) if x.ndim == 4 else (1, -1)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=axes, keepdims=True)
    xhat = (x - mean) * lax.rsqrt(var + eps)
    return xhat * slope.reshape(shape) + bias.reshape(shape)


# ---------------------------------------------------------------------------
# the net
# ---------------------------------------------------------------------------
class Reference:
    def __init__(self, conf_text: str, overrides: Dict[str, str],
                 quant: Optional[str] = None):
        self.net = confnet.build(confnet.parse_pairs(conf_text), overrides)
        self.batch = int(self.net.get("batch_size", "0"))
        self.mask_dtype = jnp.dtype(self.net.get("dtype", "float32"))
        if self.net.get("updater", "sgd") != "sgd":
            raise NotImplementedError("only the sgd updater")
        self._q: Callable = (lambda a: a)
        if quant:
            qt = jnp.dtype(quant)
            self._q = lambda a: a.astype(qt).astype(jnp.float32)
        self.dropouts = [l for l in self.net.layers if l.type == "dropout"]
        self.has_batch_norm = any(l.type == "batch_norm"
                                  for l in self.net.layers)
        self._block_fns: Dict[bool, Callable] = {}

    # -- weights ------------------------------------------------------
    def _rand(self, lay, key, shape, fan_in, fan_out):
        kind = lay.get("random_type", "gaussian")
        if kind == "gaussian":
            return float(lay.get("init_sigma", "0.01")) * jax.random.normal(
                key, shape, jnp.float32)
        if kind in ("uniform", "xavier"):
            a = float(lay.get("init_uniform", "-1"))
            if a <= 0:
                a = math.sqrt(3.0 / (fan_in + fan_out))
            return jax.random.uniform(key, shape, jnp.float32, -a, a)
        if kind == "kaiming":
            if lay.type == "fullc":
                fan = lay.out_shape[0]
            else:
                fan = lay.out_shape[0] * lay.kernel() ** 2
            return math.sqrt(2.0 / fan) * jax.random.normal(
                key, shape, jnp.float32)
        raise ValueError(f"random_type {kind!r}")

    def init(self, seed: int) -> Params:
        key = jax.random.PRNGKey(seed)
        params: Params = {}
        for lay in self.net.layers:
            k = jax.random.fold_in(key, lay.index)
            if lay.type == "conv":
                cin, g, ks = lay.in_shapes[0][0], lay.group(), lay.kernel()
                cout = lay.out_shape[0]
                p = {"wmat": self._rand(lay, k, (cout, cin // g, ks, ks),
                                        cin // g * ks * ks, cout // g)}
            elif lay.type == "fullc":
                nin, nout = lay.in_shapes[0][0], lay.out_shape[0]
                p = {"wmat": self._rand(lay, k, (nout, nin), nin, nout)}
            elif lay.type == "batch_norm":
                c = lay.out_shape[0]
                params[lay.name] = {
                    "slope": jnp.full((c,), float(
                        lay.get("init_slope", "1.0")), jnp.float32),
                    "bias": jnp.full((c,), float(
                        lay.get("init_bias", "0.0")), jnp.float32)}
                continue
            else:
                continue
            if int(lay.get("no_bias", "0")) == 0:
                p["bias"] = jnp.full((lay.out_shape[0],), float(
                    lay.get("init_bias", "0.0")), jnp.float32)
            params[lay.name] = p
        return params

    # -- updater ------------------------------------------------------
    def hyper(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """Per leaf: base lr, wd, momentum and the schedule's settings."""
        out: Dict[str, Dict[str, Dict[str, float]]] = {}
        for lay in self.net.layers:
            if lay.type not in ("conv", "fullc", "batch_norm"):
                continue
            out[lay.name] = {}
            for pname in ("wmat", "slope", "bias"):
                tag = "bias" if pname == "bias" else "wmat"
                h = {"lr": 0.01, "wd": 0.0, "momentum": 0.9,
                     "schedule": "constant", "gamma": 0.5, "alpha": 0.5,
                     "step": 1.0, "factor": 0.1, "minimum_lr": 1e-5}
                for key, val in lay.pairs:
                    if key.startswith(tag + ":"):
                        key = key[len(tag) + 1:]
                    if key in ("lr", "eta"):
                        h["lr"] = float(val)
                    elif key in ("wd", "momentum"):
                        h[key] = float(val)
                    elif key.startswith(("lr:", "eta:")):
                        sub = key.split(":", 1)[1]
                        if sub == "schedule":
                            h["schedule"] = val
                        elif sub in h:
                            h[sub] = float(val)
                out[lay.name][pname] = h
        return out

    @staticmethod
    def lr_at(h: Dict[str, float], epoch: int) -> float:
        kind = h["schedule"]
        if kind == "constant":
            lr = h["lr"]
        elif kind == "expdecay":
            lr = h["lr"] * h["gamma"] ** (epoch / h["step"])
        elif kind == "polydecay":
            lr = h["lr"] * (1 + (epoch // h["step"]) * h["gamma"]) ** (
                -h["alpha"])
        elif kind == "factor":
            lr = h["lr"] * h["factor"] ** (epoch // h["step"])
        else:
            raise ValueError(f"lr schedule {kind!r}")
        return max(lr, h["minimum_lr"])

    # -- forward and loss ---------------------------------------------
    def dropout_masks(self, seed: int, step: int, rows: int
                      ) -> Dict[int, jax.Array]:
        """{layer index: keep mask (rows, width), 0/1 in float32}."""
        rng = jax.random.fold_in(jax.random.PRNGKey(seed + 100), step)
        masks = {}
        for lay in self.dropouts:
            keep = 1.0 - float(lay.get("threshold", "0"))
            shape = lay.out_shape
            full = (rows, 1, 1, shape[0]) if len(shape) == 1 else (
                rows,) + tuple(shape)
            u = jax.random.uniform(jax.random.fold_in(rng, lay.index),
                                   full, self.mask_dtype)
            masks[lay.index] = (u < keep).astype(jnp.float32).reshape(
                (rows,) + tuple(shape))
        return masks

    def loss_sum(self, params: Params, x: jax.Array, labels: jax.Array,
                 masks: Dict[int, jax.Array], remat_from: int = 0
                 ) -> jax.Array:
        """Sum of the rows' cross-entropies. `x` float32 NCHW, `labels`
        int32 (rows,)."""
        segments = self._segments() if remat_from else [
            list(self.net.layers)]
        live = {"0": x, "in": x}
        total = jnp.zeros((), jnp.float32)
        for seg in segments:
            need = self._live_after(seg)
            fn = functools.partial(self._run, seg, need, labels=labels,
                                   masks=masks)
            if remat_from and len(segments) > 1:
                fn = jax.checkpoint(fn)
            live, part = fn(params, live)
            total = total + part
        return total

    def _segments(self) -> List[List[confnet.Layer]]:
        """Cut where one node alone is alive and four layers or more
        have gone by: the blocks of a residual net. Each segment is
        recomputed in the backward pass, so that float32 activations
        of a whole batch fit beside one another."""
        layers = self.net.layers
        segs, cur = [], []
        for i, lay in enumerate(layers):
            cur.append(lay)
            later = {n for l in layers[i + 1:] for n in l.ins}
            made = {n for l in layers[:i + 1] for n in l.outs} | {"0"}
            if len(cur) >= 4 and len(later & made) == 1:
                segs.append(cur)
                cur = []
        if cur:
            segs.append(cur)
        return segs

    def _live_after(self, seg) -> List[str]:
        last = seg[-1].index
        later = {n for l in self.net.layers[last + 1:] for n in l.ins}
        made = {n for l in self.net.layers[:last + 1] for n in l.outs}
        return sorted(later & (made | {"0"}))

    def _run(self, seg, need, params, live, *, labels, masks):
        q = self._q
        vals = dict(live)
        total = jnp.zeros((), jnp.float32)
        for lay in seg:
            x = vals[lay.ins[0]]
            p = params.get(lay.name, {})
            t = lay.type
            if t == "conv":
                st, pd = lay.stride(), lay.pad()
                y = lax.conv_general_dilated(
                    q(x), q(p["wmat"]), (st, st), ((pd, pd), (pd, pd)),
                    dimension_numbers=("NCHW", "OIHW", "NCHW"),
                    feature_group_count=lay.group(), precision=HI)
                if "bias" in p:
                    y = y + p["bias"][None, :, None, None]
            elif t == "fullc":
                y = jnp.dot(q(x), q(p["wmat"]).T, precision=HI)
                if "bias" in p:
                    y = y + p["bias"][None, :]
            elif t == "relu":
                y = jnp.maximum(x, 0.0)
            elif t == "max_pooling":
                pool = (max_pool_winner if lay.get("pool_grad", "ties")
                        == "winner" else max_pool)
                y = pool(x, lay.kernel(), lay.stride())
            elif t == "avg_pooling":
                y = avg_pool(x, lay.kernel(), lay.stride())
            elif t == "lrn":
                y = lrn(x, int(lay.get("local_size", "3")),
                        float(lay.get("alpha", "0.001")),
                        float(lay.get("beta", "0.75")),
                        float(lay.get("knorm", "1.0")))
            elif t == "batch_norm":
                y = batch_norm(x, p["slope"], p["bias"],
                               float(lay.get("eps", "1e-10")))
            elif t == "dropout":
                keep = 1.0 - float(lay.get("threshold", "0"))
                y = x * masks[lay.index] / keep if keep < 1.0 else x
            elif t == "flatten":
                y = x.reshape(x.shape[0], -1)
            elif t == "add":
                y = sum(vals[n] for n in lay.ins[1:]) + x
            elif t == "softmax":
                logz = jax.nn.logsumexp(x, axis=-1)
                picked = jnp.take_along_axis(x, labels[:, None], axis=1)
                total = total + jnp.sum(logz - picked[:, 0])
                y = x
            else:
                raise NotImplementedError(t)
            vals[lay.outs[0]] = y
        return {n: vals[n] for n in need}, total

    # -- a step -------------------------------------------------------
    def grads(self, params: Params, images_u8: np.ndarray, mean: float,
              labels: np.ndarray, seed: int, step: int, block: int
              ) -> Tuple[jax.Array, Params]:
        """Mean loss over the batch and its gradient. Without batch-norm
        the rows are independent and go in blocks of `block` images,
        the gradients summed; with it the whole batch goes at once and
        the blocks of layers are recomputed (`_segments`)."""
        rows = images_u8.shape[0]
        masks = self.dropout_masks(seed, step, rows)
        if self.has_batch_norm:
            block = rows
        fn = self._block_fn(bool(self.has_batch_norm))
        loss = jnp.zeros((), jnp.float32)
        grad = jax.tree.map(jnp.zeros_like, params)
        for lo in range(0, rows, block):
            sl = slice(lo, lo + block)
            l, g = fn(params, jnp.asarray(images_u8[sl]), mean,
                      jnp.asarray(labels[sl], jnp.int32),
                      {i: m[sl] for i, m in masks.items()})
            loss = loss + l
            grad = jax.tree.map(jnp.add, grad, g)
        scale = 1.0 / rows
        return loss * scale, jax.tree.map(lambda a: a * scale, grad)

    def _block_fn(self, remat: bool):
        if remat not in self._block_fns:
            def f(params, u8, mean, labels, masks):
                x = u8.astype(jnp.float32) - mean
                return self.loss_sum(params, x, labels, masks,
                                     remat_from=int(remat))
            self._block_fns[remat] = jax.jit(jax.value_and_grad(f))
        return self._block_fns[remat]

    def update(self, params: Params, mom: Params, grad: Params,
               epoch: int) -> Tuple[Params, Params]:
        hyper = self.hyper()
        new_p: Params = {}
        new_m: Params = {}
        for lk, d in params.items():
            new_p[lk], new_m[lk] = {}, {}
            for pn, w in d.items():
                h = hyper[lk][pn]
                lr = self.lr_at(h, epoch)
                m = h["momentum"] * mom[lk][pn] - lr * (
                    grad[lk][pn] + h["wd"] * w)
                new_m[lk][pn] = m
                new_p[lk][pn] = w + m
        return new_p, new_m
