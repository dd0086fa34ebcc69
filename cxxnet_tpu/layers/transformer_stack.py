"""transformer_stack: L identical transformer blocks + pipeline
parallelism over a 'pipe' mesh axis.

Pure TPU-native extension (the reference predates sequence models).
A stack of L pre-norm blocks

    x = x + Wproj . attn(layernorm(x))
    x = x + FFN(layernorm(x))            FFN = W2 . relu(W1 . _)

with every block's params stacked on a leading L dim, which buys two
things the per-layer config DAG cannot express:

- without a 'pipe' mesh axis: ONE lax.scan over the L stacked blocks -
  a single compiled block body instead of L inlined copies (compile
  time O(1) in depth; jax.checkpoint-friendly).
- with `mesh = ...,pipe:P` (L % P == 0): GPipe pipeline parallelism as
  one shard_map program. Device p holds only its L/P stage params
  (pipe_shard_dims -> HBM scales 1/P); the per-data-shard batch splits
  into M microbatches (config `microbatch`; an explicit value that
  does not divide the per-shard batch is an error, and the default
  picks the largest divisor <= P) that flow
  through the stages via lax.ppermute, M + P - 1 schedule ticks with
  the standard GPipe bubble (P-1)/(M+P-1). Autodiff through the
  schedule IS the reverse pipeline (ppermute transposes to the
  opposite rotation), so the same code trains.

The attention core inside the stack: ring attention when the mesh
has an eligible 'seq' axis and no pipeline route (scan-over-layers +
sequence parallelism compose), otherwise the XLA blockwise kernel
(ops/attention.py) - per-device and shard_map-safe inside the
pipelined schedule.

Config keys: nlayer, nhead, nhidden (FFN hidden), causal, microbatch,
kv_block, eps, seq_parallel (ring | ulysses | none - the non-pipelined
route's attention-core scheme, shared with the attention layer).
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from cxxnet_tpu.layers.attention import (
    heads_proj, layer_norm, qkv_heads)
from cxxnet_tpu.layers.base import Layer, Params, Shape, register_layer
from cxxnet_tpu.ops.attention import blockwise_attention

PIPE_AXIS = "pipe"


@register_layer
class TransformerStackLayer(Layer):
    """L stacked pre-norm transformer blocks on (b, 1, s, e) nodes."""

    type_name = "transformer_stack"

    def __init__(self, name: str = ""):
        super().__init__(name)
        self.nlayer = 1
        self.nhead = 1
        self.causal = 0
        self.microbatch = 0     # 0 = pipe-axis size
        self.kv_block = 512
        self.eps = 1e-5
        self.seq_parallel = "ring"

    def set_param(self, name: str, val: str) -> None:
        super().set_param(name, val)
        if name == "nlayer":
            self.nlayer = int(val)
        if name == "nhead":
            self.nhead = int(val)
        if name == "causal":
            self.causal = int(val)
        if name == "microbatch":
            self.microbatch = int(val)
        if name == "kv_block":
            self.kv_block = int(val)
        if name == "eps":
            self.eps = float(val)
        if name == "seq_parallel":
            from cxxnet_tpu.parallel.ring import SEQ_SCHEMES
            if val not in SEQ_SCHEMES:
                raise ValueError(
                    "seq_parallel must be ring, ulysses or none")
            self.seq_parallel = val

    def infer_shapes(self, in_shapes: List[Shape]) -> List[Shape]:
        self.check_one_to_one(in_shapes)
        b, c, s, e = in_shapes[0]
        if c != 1:
            raise ValueError(
                "transformer_stack: input must be a sequence node")
        if self.nlayer < 1:
            raise ValueError("transformer_stack: must set nlayer >= 1")
        if self.param.num_hidden <= 0:
            raise ValueError(
                "transformer_stack: must set nhidden correctly")
        if e % self.nhead != 0:
            raise ValueError(
                f"transformer_stack: embed {e} not divisible by "
                f"nhead {self.nhead}")
        return [in_shapes[0]]

    def init_params(self, key: jax.Array, in_shapes: List[Shape]) -> Params:
        e = in_shapes[0][3]
        h, L = self.param.num_hidden, self.nlayer
        ks = jax.random.split(key, 4)
        rw = self.param.rand_init_weight
        return {
            "ln1_s": jnp.ones((L, e), jnp.float32),
            "ln1_b": jnp.zeros((L, e), jnp.float32),
            "wqkv": rw(ks[0], (L, 3 * e, e), in_num=e, out_num=3 * e),
            "bqkv": jnp.zeros((L, 3 * e), jnp.float32),
            "wproj": rw(ks[1], (L, e, e), in_num=e, out_num=e),
            "ln2_s": jnp.ones((L, e), jnp.float32),
            "ln2_b": jnp.zeros((L, e), jnp.float32),
            "w1": rw(ks[2], (L, h, e), in_num=e, out_num=h),
            "b1": jnp.zeros((L, h), jnp.float32),
            "w2": rw(ks[3], (L, e, h), in_num=h, out_num=e),
            "b2": jnp.zeros((L, e), jnp.float32),
        }

    def param_tags(self) -> Dict[str, str]:
        return {"wqkv": "wmat", "wproj": "wmat", "w1": "wmat",
                "w2": "wmat", "ln1_s": "wmat", "ln2_s": "wmat",
                "bqkv": "bias", "b1": "bias", "b2": "bias",
                "ln1_b": "bias", "ln2_b": "bias"}

    def pipe_shard_dims(self) -> Dict[str, int]:
        # every stacked param's leading (layer) dim rides 'pipe'
        return {pn: 0 for pn in ("ln1_s", "ln1_b", "wqkv", "bqkv",
                                 "wproj", "ln2_s", "ln2_b", "w1", "b1",
                                 "w2", "b2")}

    # ------------------------------------------------------------------
    def _block(self, bp, x, seq_mesh=None):
        """One block; bp leaves have NO leading layer dim; x (b, s, e).
        Norm + QKV plumbing shared with the single-layer family
        (layers/attention.py helpers). With `seq_mesh`, the attention
        core runs the configured sequence-parallel scheme over its
        'seq' axis (parallel/ring.py) instead of letting GSPMD
        all-gather the seq-sharded K/V."""
        from cxxnet_tpu.parallel.ring import seq_parallel_attention
        h = layer_norm(x, bp["ln1_s"], bp["ln1_b"], self.eps)
        q, k, v = qkv_heads(h, bp["wqkv"], bp["bqkv"], self.nhead)
        o = None
        if seq_mesh is not None:
            o = seq_parallel_attention(q, k, v, seq_mesh,
                                       self.seq_parallel,
                                       causal=bool(self.causal),
                                       kv_block=self.kv_block)
        if o is None:
            o = blockwise_attention(q, k, v, causal=bool(self.causal),
                                    kv_block=self.kv_block)
        x = x + heads_proj(o, bp["wproj"])
        h2 = layer_norm(x, bp["ln2_s"], bp["ln2_b"], self.eps)
        f = jnp.einsum("bse,he->bsh", h2, bp["w1"].astype(x.dtype))
        f = jnp.maximum(f + bp["b1"].astype(x.dtype)[None, None], 0.0)
        f = jnp.einsum("bsh,eh->bse", f, bp["w2"].astype(x.dtype))
        return x + f + bp["b2"].astype(x.dtype)[None, None]

    def _scan_blocks(self, params, x, seq_mesh=None):
        """Sequential route: scan over the stacked layer dim."""
        def step(c, bp):
            return self._block(bp, c, seq_mesh), None
        out, _ = lax.scan(step, x, params)
        return out

    # ------------------------------------------------------------------
    def _pipe_route(self, mesh) -> int:
        """Pipeline-parallel eligibility: returns P (the pipe-axis size)
        or 0 for the sequential route."""
        if mesh is None:
            return 0
        P = mesh.shape.get(PIPE_AXIS, 1)
        if P <= 1 or self.nlayer % P != 0:
            return 0
        return P

    def _pipelined(self, params, x, mesh, P):
        """GPipe schedule as one shard_map program; x (b, s, e) global."""
        names = mesh.axis_names
        data = "data" if "data" in names else None
        dsize = mesh.shape.get("data", 1) if data else 1
        b = x.shape[0]
        b_local = b // dsize
        if self.microbatch:
            # an explicit microbatch that cannot divide the per-shard
            # batch must fail loudly, not silently de-pipeline
            if b % dsize != 0 or b_local % self.microbatch != 0:
                raise ValueError(
                    f"transformer_stack: microbatch={self.microbatch} "
                    f"does not divide the per-data-shard batch "
                    f"{b_local} (batch {b} over data:{dsize})")
            M = self.microbatch
        else:
            if b % dsize != 0 or b_local == 0:
                # degenerate direct-layer use (the trainer's mesh
                # builder enforces batch divisibility): sequential route
                return self._scan_blocks(params, x)
            # default: as close to P microbatches as divides the
            # per-shard batch (M=1 still pipelines - full bubble, but
            # stage params stay sharded 1/P)
            M = next(m for m in range(min(P, b_local), 0, -1)
                     if b_local % m == 0)
        xspec = jax.sharding.PartitionSpec(data, None, None)
        pspec = jax.tree.map(
            lambda _: jax.sharding.PartitionSpec(PIPE_AXIS), params)
        vary = tuple(a for a in (data, PIPE_AXIS) if a)

        def local_fn(bp, xl):
            # bp leaves: (L/P, ...) local stage params; xl (b_l, s, e)
            stage = lax.axis_index(PIPE_AXIS)
            bl, s, e = xl.shape
            mb = bl // M
            xs = xl.reshape(M, mb, s, e)
            perm = [(i, (i + 1) % P) for i in range(P)]

            def stage_apply(c):
                out, _ = lax.scan(
                    lambda cc, p: (self._block(p, cc), None), c, bp)
                return out

            def tick(carry, t):
                recv, ys = carry
                inject = lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
                cur = jnp.where(stage == 0, inject, recv)
                y = stage_apply(cur)
                recv_n = lax.ppermute(y, PIPE_AXIS, perm)
                oidx = t - (P - 1)
                take = jnp.logical_and(stage == P - 1, oidx >= 0)
                upd = jnp.where(take, y, 0.0)
                ys = lax.dynamic_update_index_in_dim(
                    ys, lax.dynamic_index_in_dim(
                        ys, jnp.clip(oidx, 0, M - 1), 0,
                        keepdims=False) + upd,
                    jnp.clip(oidx, 0, M - 1), 0)
                return (recv_n, ys), None

            init = (jnp.zeros((mb, s, e), xl.dtype),
                    jnp.zeros((M, mb, s, e), xl.dtype))
            init = jax.tree.map(
                lambda a: lax.pcast(a, vary, to="varying"), init)
            (_, ys), _ = lax.scan(tick, init, jnp.arange(M + P - 1))
            # only the last stage wrote ys; broadcast it around the ring
            ys = lax.psum(ys, PIPE_AXIS)
            return ys.reshape(bl, s, e)

        return jax.shard_map(
            local_fn, mesh=mesh, in_specs=(pspec, xspec),
            out_specs=xspec)(params, x)

    def apply(self, params, inputs, *, train, rng=None):
        from cxxnet_tpu.parallel.mesh import get_active_mesh
        x = inputs[0]
        b, _, s, e = x.shape
        xs = x.reshape(b, s, e)
        mesh = get_active_mesh()
        P = self._pipe_route(mesh)
        if P:
            # pipelined: the stages themselves are the sharded dim; the
            # attention core stays per-device blockwise (a nested 'seq'
            # shard_map inside the pipe schedule is out of scope)
            out = self._pipelined(params, xs, mesh, P)
        else:
            out = self._scan_blocks(params, xs, mesh)
        return [out.reshape(b, 1, s, e)]
