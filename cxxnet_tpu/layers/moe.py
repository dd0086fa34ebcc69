"""Mixture-of-experts FFN layer: routed and shared experts, a dropless
route on one device and a dense route under expert parallelism.

Pure TPU-native extension (the reference predates MoE entirely): a
top-k routed FFN over (batch, 1, seq, embed) sequence nodes.

- every expert's FFN weights live in stacked tensors with a leading
  expert dim (w1 (G, H, e), w2 (G, e, H), with `moe_glu = 1` also w3
  (G, H, e): the expert is (act(x w1^T) * (x w3^T)) w2^T, act being
  SiLU or, with `moe_act = relu`, ReLU; else relu(x w1^T + b1) w2^T +
  b2); `expert_shard_dims` shards that dim
  over an 'expert' mesh axis the same way `model_shard_dims` drives
  tensor parallelism (parallel/sharding.py).
- the router. `moe_score = softmax` (default): probabilities over the
  experts, the top-k weighted by their probability (the
  Switch-Transformer estimator), or with `moe_norm_topk = 1` by the
  softmax over the chosen logits alone (the probabilities renormalised
  over the chosen: the same numbers). `moe_score = sigmoid`: scores
  s = sigmoid(x Wr^T) in float32, the top-k of s + b chosen, b being the
  selection bias `sbias`, a leaf that takes NO gradient step (seeded
  with `moe_bias_sigma` x N(0, 1), fixed); the chosen are weighted
  `moe_scale` x s_e / (sum over the chosen of s + `moe_norm_eps`).
- a second input (`layer[b,a->f] = moe`): the experts read the first
  node, the router the second (a model whose router reads the token
  mixer's input, not the FFN's). One input is both.
- `moe_shared = n`: a shared expert of n x nhidden beside the routed
  ones (s1, s3, s2; gated as `moe_glu` says), run on every token;
  `moe_shared_width = c` gives it c columns instead (a chip's share of
  a shared expert split by columns need not be a multiple of nhidden).
- `moe_latent = l`: the routed experts work in a latent width l: u =
  x lat_in^T, routed = (sum of the weighted experts on u) lat_out^T
  (LatentMoE). The router and the shared expert read x itself.
- `moe_act = relu2`: an expert without a gate is relu(x w1^T)^2 w2^T
  (squared ReLU; with `no_bias = 1` there is no bias).
- `moe_held = first,count`: this device holds experts
  [first, first + count) of the `nexpert` the router scores (the chip's
  share of a deployment whose experts are spread over chips). The
  router keeps its width and its top-k; the layer computes its own
  experts' part of the result for the tokens routed to them, and what
  the absent experts would add is left out. The stacked weights then
  hold `count` experts.
- two compute routes, chosen by what the code can see. **Dropless**
  (`moe_held` given, or a step that spans one device): the (token,
  choice) assignments that fall on held experts are sorted by expert,
  each expert's run padded to whole tiles of rows, and a loop over the
  tiles THAT HOLD ROWS gathers a tile's tokens, runs its expert and
  adds the weighted result back to its tokens (only index arrays are as
  long as the worst case; no buffer of rows x embed exists): work in proportion to the assignments
  held, no capacity, nothing dropped whatever the skew. The loop's trip
  count is a value of the step, so forward and backward are written out
  (`jax.custom_vjp`; the backward runs each tile's `jax.vjp`).
  **Dense** (a mesh with an 'expert' axis, until the exchange over
  chips exists): every expert runs on every token and the router's
  top-k one-hot masks the sum; each device computes its local experts
  for all tokens and one psum combines. Exact, and the oracle the
  dropless route is tested against. The capacity route of earlier
  versions (`moe_capacity`), which dropped tokens that overflowed an
  expert's buffer, is gone: the key raises.
- the standard load-balance auxiliary loss (E * sum_e fraction_e *
  mean_prob_e) is returned through the `apply_with_aux` protocol
  (nnet/network.py adds it into total_loss; `moe_aux` scales it, 0
  disables).
- counters (`apply_with_stats`, read after a step through
  `NetTrainer.fetch_counters`): `held` the assignments that fell on
  held experts, `load` the fullest held expert's count over the mean,
  `dropped` held assignments that found no row (0 by construction).

Config keys: nexpert, nhidden (per-expert FFN hidden), moe_top_k
(default 1), moe_aux (default 0.01), moe_score, moe_norm_topk, moe_scale,
moe_norm_eps (default 0), moe_glu, moe_act, moe_shared,
moe_shared_width, moe_latent, moe_held, moe_bias_sigma, no_bias.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from cxxnet_tpu.layers.base import Layer, Params, Shape, register_layer

# Rows a tile of the dropless route (16 at tiny sizes). A tile's cost is
# mostly its gathers, scatters and the loop's turn, not its products, so
# few large tiles beat many small ones; and at 1,024 every held expert
# of the Kimi cell (256 tokens expected, 550 the fullest seen) fits one
# tile, so the loop's trip count, and with it the step's time, does not
# follow a seed's routing: with 256 six runs spread by 0.41% of the rate
# (my chip runs, PR 29), which is most of what a new cell may spread.
TILE = 1024


def tile_rows(assignments: int, nexpert: int) -> int:
    """Rows a tile for a step of `assignments` (token, choice) pairs
    over `nexpert` experts: `TILE`, 16 at tiny sizes - unless a held
    expert's expected run, `assignments / nexpert` rows, ends within a
    fifth of a tile of a whole number of tiles. Then about half the
    experts would need one tile more than the others, which of them
    would follow the seed's routing, and the loop's trip count and a
    step's time with it (32,768 tokens choosing 4 of 64: 2,048 expected
    rows on two 1,024-row tiles; PERF.md section 6, PR 39). The tiles
    are then made longer, a multiple of 128 rows, so that the expected
    run ends in the middle of its last tile (1,408 there)."""
    if assignments < 4 * TILE:
        return 16
    run = assignments / nexpert
    tiles = round(run / TILE)
    if tiles < 1 or abs(run / TILE - tiles) >= 0.2:
        return TILE
    return -(-int(run / (tiles - 0.5)) // 128) * 128


# ---------------------------------------------------------------------------
# the dropless route
# ---------------------------------------------------------------------------
def plan_tiles(chosen, first: int, count: int, tile: int):
    """Where every held assignment goes. chosen (N, k) expert ids.

    Returns (tok, pos, tile_group, n_active, counts, dropped): `tok` (M,) the
    token each row of the sorted, tile-padded layout reads (N = none),
    `pos` (N, k) the row each assignment got (M = not held),
    `tile_group` the held expert of each tile, `n_active` the tiles that
    hold any row, the held experts' counts and the held assignments
    that found no row (0 by construction). M = N * min(k, count) + count * tile
    rows can hold every assignment whatever the skew: a token picks
    distinct experts, so at most min(k, count) of its choices are held,
    and padding each expert's run to whole tiles adds under a tile an
    expert."""
    n, k = chosen.shape
    m = -(-n * min(k, count) // tile) * tile + count * tile
    local = chosen.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)                  # not held: last
    counts = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    padded = -(-counts // tile) * tile
    start = jnp.cumsum(padded) - padded                  # rows, tile-padded
    # rank of an assignment among those of its expert, in token order
    order = jnp.argsort(key, stable=True)
    first_of = jnp.cumsum(counts) - counts
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32)
        - jnp.take(jnp.append(first_of, jnp.sum(counts)), key[order]))
    pos = jnp.where(held, jnp.take(start, jnp.minimum(key, count - 1))
                    + rank, m)
    tok = jnp.full((m,), n, jnp.int32).at[pos].set(
        jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    n_active = jnp.sum(padded) // tile
    tile_start = jnp.arange(m // tile, dtype=jnp.int32) * tile
    tile_group = jnp.clip(
        jnp.searchsorted(start + padded, tile_start, side="right"),
        0, count - 1).astype(jnp.int32)
    dropped = jnp.sum(held & (pos >= m))
    return tok, pos.reshape(n, k), tile_group, n_active, counts, dropped


def _tile_rows(x_ext, tok, i, tile):
    idx = lax.dynamic_slice(tok, (i * tile,), (tile,))
    return idx, jnp.take(x_ext, idx, axis=0)


def _expert_of(wts, g):
    return {k: lax.dynamic_index_in_dim(v, g, 0, keepdims=False)
            for k, v in wts.items()}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 7))
def dropless_apply(expert_fn: Callable, x, wts, row_weight, tok, tile_group,
                   n_active, tile: int):
    """out (N, e): token t gets the sum over the rows r with tok[r] = t
    of row_weight[r] x (expert `tile_group[r // tile]` applied to x[t]),
    over the first `n_active` tiles of rows. x (N, e); wts {name: (G,
    ...)}; expert_fn(rows, {name: (...)}). Nothing of the rows' length
    is ever `e` wide: a tile is gathered, computed and added back."""
    return _dropless_fwd(expert_fn, x, wts, row_weight, tok, tile_group,
                         n_active, tile)[0]


def _dropless_fwd(expert_fn, x, wts, row_weight, tok, tile_group, n_active,
                  tile):
    x_ext = jnp.concatenate([x, jnp.zeros_like(x[:1])])

    def body(i, out):
        idx, rows = _tile_rows(x_ext, tok, i, tile)
        y = expert_fn(rows, _expert_of(wts, tile_group[i]))
        w = lax.dynamic_slice(row_weight, (i * tile,), (tile,))
        return out.at[idx].add(y.astype(jnp.float32) * w[:, None])

    out = lax.fori_loop(0, n_active, body,
                        jnp.zeros(x_ext.shape, jnp.float32))
    return (out[:-1].astype(x.dtype),
            (x, wts, row_weight, tok, tile_group, n_active))


def _dropless_bwd(expert_fn, tile, res, dout):
    x, wts, row_weight, tok, tile_group, n_active = res
    x_ext = jnp.concatenate([x, jnp.zeros_like(x[:1])])
    d_ext = jnp.concatenate([dout, jnp.zeros_like(dout[:1])])

    def body(i, carry):
        dx, dw, drw = carry
        idx, rows = _tile_rows(x_ext, tok, i, tile)
        g = tile_group[i]
        y, vjp = jax.vjp(expert_fn, rows, _expert_of(wts, g))
        d = jnp.take(d_ext, idx, axis=0)
        w = lax.dynamic_slice(row_weight, (i * tile,), (tile,))
        drw = lax.dynamic_update_slice(drw, jnp.sum(
            d.astype(jnp.float32) * y.astype(jnp.float32), -1), (i * tile,))
        d_rows, d_w = vjp((d * w[:, None].astype(d.dtype)).astype(y.dtype))
        dx = dx.at[idx].add(d_rows.astype(dx.dtype))
        dw = {k: lax.dynamic_update_index_in_dim(
            v, lax.dynamic_index_in_dim(v, g, 0, keepdims=False)
            + d_w[k].astype(v.dtype), g, 0) for k, v in dw.items()}
        return dx, dw, drw

    dx, dw, drw = lax.fori_loop(
        0, n_active, body,
        (jnp.zeros(x_ext.shape, jnp.float32),
         {k: jnp.zeros(v.shape, jnp.float32) for k, v in wts.items()},
         jnp.zeros(row_weight.shape, jnp.float32)))
    return (dx[:-1].astype(x.dtype),
            {k: v.astype(wts[k].dtype) for k, v in dw.items()},
            drw.astype(row_weight.dtype), None, None, None)


dropless_apply.defvjp(_dropless_fwd, _dropless_bwd)


@register_layer
class MoELayer(Layer):
    """moe: top-k routed mixture-of-experts FFN on sequence nodes."""

    type_name = "moe"
    #: no checkpoint around this layer under `remat = 1`
    #: (nnet/network.py): `dropless_apply` keeps only its inputs either
    #: way (its backward runs each tile's expert again), so a checkpoint
    #: would leave 0.1 GB unsaved (shared expert, router) for a second
    #: forward of 6.8 ms, about 15 MB a ms
    remat_worthy = False

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.nexpert = 0
        self.top_k = 1
        self.aux_scale = 0.01
        self.score = "softmax"
        self.scale = 1.0
        self.norm_eps = 0.0       # sigmoid routing: added to the chosen's sum
        self.glu = 0
        self.act = "silu"         # the gate of a `moe_glu` expert
        self.norm_topk = 0
        self.shared = 0
        self.shared_width = 0     # columns of the shared expert, 0 = by `shared`
        self.latent = 0           # the routed experts' width, 0 = the input's
        self.held = None          # (first, count), None = all
        self.bias_sigma = 0.0

    def set_param(self, name: str, val: str) -> None:
        super().set_param(name, val)
        if name == "nexpert":
            self.nexpert = int(val)
        if name == "moe_top_k":
            self.top_k = int(val)
        if name == "moe_aux":
            self.aux_scale = float(val)
        if name == "moe_capacity":
            raise ValueError(
                "moe_capacity is gone: the capacity route dropped the "
                "tokens that overflowed an expert's buffer. The layer "
                "now runs a dropless route on one device (or with "
                "moe_held) and the dense route under an 'expert' mesh "
                "axis; remove the key (docs/layer.md)")
        if name == "moe_score":
            if val not in ("softmax", "sigmoid"):
                raise ValueError("moe_score must be softmax or sigmoid")
            self.score = val
        if name == "moe_scale":
            self.scale = float(val)
        if name == "moe_norm_topk":
            self.norm_topk = int(val)
        if name == "moe_norm_eps":
            self.norm_eps = float(val)
        if name == "moe_glu":
            self.glu = int(val)
        if name == "moe_act":
            if val not in ("silu", "relu", "relu2"):
                raise ValueError("moe_act must be silu, relu or relu2")
            self.act = val
        if name == "moe_shared":
            self.shared = int(val)
        if name == "moe_shared_width":
            self.shared_width = int(val)
        if name == "moe_latent":
            self.latent = int(val)
        if name == "moe_held":
            first, _, count = val.partition(",")
            self.held = (int(first), int(count))
        if name == "moe_bias_sigma":
            self.bias_sigma = float(val)

    @property
    def _first_count(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.nexpert)

    def infer_shapes(self, in_shapes: List[Shape]) -> List[Shape]:
        if len(in_shapes) not in (1, 2):
            raise ValueError("moe: one input, or the experts' and the "
                             "router's")
        b, c, s, e = in_shapes[0]
        if c != 1 or tuple(in_shapes[-1][:3]) != (b, 1, s):
            raise ValueError("moe: inputs must be sequence nodes of one "
                             "length")
        if self.nexpert < 2:
            raise ValueError("moe: must set nexpert >= 2")
        if self.param.num_hidden <= 0:
            raise ValueError("moe: must set nhidden correctly")
        if not (1 <= self.top_k <= self.nexpert):
            raise ValueError("moe: moe_top_k out of range")
        first, count = self._first_count
        if first < 0 or count < 1 or first + count > self.nexpert:
            raise ValueError("moe: moe_held = first,count must lie inside "
                             f"the {self.nexpert} experts")
        if min(self.latent, self.shared_width) < 0:
            raise ValueError("moe: moe_latent and moe_shared_width are 0 "
                             "or more")
        if self.glu and self.act == "relu2":
            raise ValueError("moe: moe_act = relu2 is for experts without "
                             "a gate (moe_glu = 0)")
        return [in_shapes[0]]

    @property
    def _shared_hidden(self) -> int:
        """The shared expert's columns: `moe_shared_width`, else
        `moe_shared` x nhidden (0: none)."""
        return self.shared_width or self.shared * self.param.num_hidden

    def init_params(self, key: jax.Array, in_shapes: List[Shape]) -> Params:
        ex = in_shapes[0][3]
        # the routed experts' width: the latent's, where there is one
        e = self.latent or ex
        h, g = self.param.num_hidden, self._first_count[1]
        kg, k1, k2 = jax.random.split(key, 3)
        rand = self.param.rand_init_weight
        er = in_shapes[-1][3]
        params = {
            "gate": rand(kg, (self.nexpert, er), in_num=er,
                         out_num=self.nexpert),
            "w1": rand(k1, (g, h, e), in_num=e, out_num=h),
            "w2": rand(k2, (g, e, h), in_num=h, out_num=e),
        }
        if self.glu:
            params["w3"] = rand(jax.random.fold_in(key, 3), (g, h, e),
                                in_num=e, out_num=h)
        elif self.param.no_bias == 0:
            params["b1"] = jnp.zeros((g, h), jnp.float32)
            params["b2"] = jnp.zeros((g, e), jnp.float32)
        hs = self._shared_hidden
        if hs:
            params["s1"] = rand(jax.random.fold_in(key, 4), (hs, ex),
                                in_num=ex, out_num=hs)
            if self.glu:
                params["s3"] = rand(jax.random.fold_in(key, 5), (hs, ex),
                                    in_num=ex, out_num=hs)
            params["s2"] = rand(jax.random.fold_in(key, 6), (ex, hs),
                                in_num=hs, out_num=ex)
        if self.score == "sigmoid":
            params["sbias"] = self.bias_sigma * jax.random.normal(
                jax.random.fold_in(key, 7), (self.nexpert,), jnp.float32)
        if self.latent:
            params["lat_in"] = rand(jax.random.fold_in(key, 8),
                                    (self.latent, ex), in_num=ex,
                                    out_num=self.latent)
            params["lat_out"] = rand(jax.random.fold_in(key, 9),
                                     (ex, self.latent), in_num=self.latent,
                                     out_num=ex)
        return params

    def param_tags(self) -> Dict[str, str]:
        # `sbias` has no tag: it gets no updater and takes no step
        return {"gate": "wmat", "w1": "wmat", "w2": "wmat", "w3": "wmat",
                "s1": "wmat", "s2": "wmat", "s3": "wmat",
                "lat_in": "wmat", "lat_out": "wmat",
                "b1": "bias", "b2": "bias"}

    def expert_shard_dims(self) -> Dict[str, int]:
        # the gate stays replicated: its (E, e) matrix is tiny and its
        # logits are needed for every token on every expert shard
        return {"w1": 0, "w2": 0, "w3": 0, "b1": 0, "b2": 0}

    # -- the router ---------------------------------------------------
    def _route(self, params, xs, mask=None):
        """(b, s, e) -> (weights (b, s, k) float32, chosen (b, s, k),
        aux scalar). Scores are float32 inside a bf16 step."""
        logits = jnp.einsum("bse,ge->bsg", xs,
                            params["gate"].astype(xs.dtype),
                            preferred_element_type=jnp.float32)
        if self.score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
            _, chosen = lax.top_k(
                probs + lax.stop_gradient(
                    params["sbias"].astype(jnp.float32)), self.top_k)
            picked = jnp.take_along_axis(probs, chosen, axis=-1)
            # (the product first: the order a step's text has had)
            scaled = self.scale * picked
            total = jnp.sum(picked, -1, keepdims=True)
            if self.norm_eps:
                total = total + self.norm_eps
            weights = scaled / total
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            weights, chosen = lax.top_k(probs, self.top_k)
            if self.norm_topk:
                weights = jax.nn.softmax(
                    jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)
            weights = self.scale * weights
        if not self.aux_scale:
            return weights, chosen, jnp.zeros((), jnp.float32)
        # load-balance loss (Switch Transformer eq. 4): fraction of
        # tokens routed to e (top-1 assignment) x mean router prob.
        # `mask` is the (b,) padded-batch validity mask: padding rows
        # must not skew the statistics (their task loss is masked the
        # same way - nnet/network.py)
        top1 = jax.nn.one_hot(chosen[..., 0], self.nexpert,
                              dtype=probs.dtype)
        if mask is not None:
            w = mask.astype(probs.dtype)[:, None, None]  # (b, 1, 1)
            total = jnp.maximum(jnp.sum(w) * probs.shape[1], 1.0)
            frac = jnp.sum(top1 * w, axis=(0, 1)) / total
            mean_p = jnp.sum(probs * w, axis=(0, 1)) / total
        else:
            frac = jnp.mean(top1, axis=(0, 1))
            mean_p = jnp.mean(probs, axis=(0, 1))
        return weights, chosen, self.nexpert * jnp.sum(frac * mean_p)

    has_aux = True

    # -- the experts --------------------------------------------------
    def _expert(self, rows, w):
        """One expert on (n, e) rows; `w` holds its own slices."""
        dt = rows.dtype
        a = jnp.einsum("ne,he->nh", rows, w["w1"].astype(dt))
        if self.glu:
            gate = jnp.maximum(a, 0.0) if self.act == "relu" \
                else jax.nn.silu(a)
            h1 = gate * jnp.einsum("ne,he->nh", rows, w["w3"].astype(dt))
        else:
            if "b1" in w:
                a = a + w["b1"].astype(dt)
            h1 = jnp.maximum(a, 0.0)
            if self.act == "relu2":
                h1 = h1 * h1
        y = jnp.einsum("nh,eh->ne", h1, w["w2"].astype(dt))
        if "b2" in w:
            y = y + w["b2"].astype(dt)
        return y

    def _stacked(self, params) -> Params:
        return {k: params[k] for k in ("w1", "w2", "w3", "b1", "b2")
                if k in params}

    def _dense_compute(self, params, xs, weights, chosen):
        """Every held expert on every token, masked by the combine
        weights: exact, no dispatch; per-device FLOPs = dense x E/n
        under expert sharding."""
        first, count = self._first_count
        combine = jnp.sum(
            jax.nn.one_hot(chosen - first, count, dtype=weights.dtype)
            * weights[..., None], axis=2)                 # (b, s, G)
        b, s, e = xs.shape
        ye = jax.vmap(lambda w: self._expert(xs.reshape(b * s, e), w))(
            self._stacked(params))                        # (G, b*s, e)
        return jnp.einsum("gne,ng->ne", ye, combine.reshape(
            b * s, count).astype(xs.dtype)).reshape(b, s, e)

    def _dropless_compute(self, params, xs, weights, chosen):
        b, s, e = xs.shape
        n = b * s
        first, count = self._first_count
        tile = tile_rows(n * self.top_k, self.nexpert)
        with jax.named_scope("dispatch"):
            tok, pos, tile_group, n_active, counts, dropped = plan_tiles(
                chosen.reshape(n, self.top_k), first, count, tile)
            # the weight of each row of the sorted layout (0: no row)
            row_weight = jnp.zeros((tok.shape[0],), jnp.float32).at[
                pos.reshape(-1)].set(weights.reshape(-1), mode="drop")
        with jax.named_scope("experts"):
            out = dropless_apply(self._expert, xs.reshape(n, e),
                                 self._stacked(params), row_weight, tok,
                                 tile_group, n_active, tile)
        return (out.astype(xs.dtype).reshape(b, s, e),
                self._stats(counts, dropped))

    #: what `apply_with_stats` counts, on either route
    stat_names = ("held", "load", "dropped")

    @staticmethod
    def _stats(counts, dropped) -> Dict[str, jax.Array]:
        """`held`: assignments on the experts held here; `load`: the
        fullest held expert's count over their mean; `dropped`: held
        assignments that were not computed."""
        total = jnp.sum(counts).astype(jnp.float32)
        return {"held": total,
                "load": jnp.max(counts) * counts.shape[0]
                / jnp.maximum(total, 1.0),
                "dropped": jnp.asarray(dropped, jnp.float32)}

    def _dropless(self) -> bool:
        """The route, from what the code can see: a share of the
        experts, or a step on one device, sorts and runs what is held;
        a step over an 'expert' mesh axis runs the dense route."""
        from cxxnet_tpu.parallel.mesh import active_device_span
        return self.held is not None or active_device_span() == 1

    def apply_with_stats(self, params, inputs, *, train, rng=None,
                         mask=None):
        x = inputs[0]
        b, _, s, e = x.shape
        xs = x.reshape(b, s, e)
        with jax.named_scope("route"):
            weights, chosen, aux = self._route(
                params, xs if len(inputs) == 1
                else inputs[1].reshape(b, s, -1), mask)
        u = xs
        if self.latent:
            # the routed experts work in the latent width
            with jax.named_scope("latent_in"):
                u = jnp.einsum("bse,le->bsl", xs,
                               params["lat_in"].astype(xs.dtype))
        if self._dropless():
            out, stats = self._dropless_compute(params, u, weights, chosen)
        else:
            with jax.named_scope("experts"):
                out = self._dense_compute(params, u, weights, chosen)
            first, count = self._first_count
            stats = self._stats(jnp.sum(jax.nn.one_hot(
                chosen - first, count, dtype=jnp.int32),
                axis=(0, 1, 2)), 0)
        if self.latent:
            with jax.named_scope("latent_out"):
                out = jnp.einsum("bsl,el->bse", out,
                                 params["lat_out"].astype(out.dtype))
        if self._shared_hidden:
            with jax.named_scope("shared"):
                w = {"w1": params["s1"], "w2": params["s2"]}
                if self.glu:
                    w["w3"] = params["s3"]
                out = out + self._expert(xs.reshape(b * s, e), w).reshape(
                    b, s, e)
        # scaled by batch so the trainer's 1/(batch*update_period)
        # normalization leaves the aux term batch-size-invariant
        aux_term = (self.aux_scale * b) * aux if self.aux_scale else \
            jnp.zeros((), jnp.float32)
        return ([out.reshape(b, 1, s, e)], aux_term.astype(jnp.float32),
                stats)

    def apply_with_aux(self, params, inputs, *, train, rng=None,
                       mask=None) -> Tuple[List[jax.Array], jax.Array]:
        return self.apply_with_stats(params, inputs, train=train, rng=rng,
                                     mask=mask)[:2]

    def apply(self, params, inputs, *, train, rng=None):
        return self.apply_with_stats(params, inputs, train=train,
                                     rng=rng)[0]
