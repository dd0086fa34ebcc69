"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference scales by data parallelism only (mshadow-ps over the batch
dim - SURVEY.md par.2.7); long-context models need the SEQUENCE dim
sharded because activation memory grows with S and attention FLOPs with
S^2. This module adds the two standard TPU-native schemes over a 'seq'
mesh axis:

ring_attention    K/V blocks rotate around the ring with lax.ppermute
                  while each device's resident Q block accumulates
                  online-softmax partials (ops/attention.py). Peak
                  activation memory per device is O(S/n); each of the n
                  steps overlaps its ppermute with the partial-attention
                  compute (XLA's latency-hiding scheduler on ICI).
ulysses_attention lax.all_to_all reshards [B, H, S/n, D] -> [B, H/n, S, D]
                  so each device runs FULL-sequence attention for H/n
                  heads, then reshards back. Two all-to-alls of the
                  activation size per call; requires heads % n == 0.

Both are shard_map'd over the full mesh: batch rides 'data', heads ride
'model' (when present and divisible), sequence rides 'seq'. Gradients
flow through shard_map/ppermute/all_to_all transposes, so the same code
path serves training - no separate backward.

Choosing: ring has no head-count constraint and its comm (2 x S/n x D
per step, n steps) rides neighbor ICI links; Ulysses moves the same
total bytes in 2 all-to-alls but needs n <= heads. docs/parallel.md
"Sequence parallelism" quantifies both.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from cxxnet_tpu.ops.attention import (
    attention_partial, blockwise_attention, empty_partial,
    finalize_partial, merge_partials)

SEQ_AXIS = "seq"


def seq_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return mesh.shape.get(SEQ_AXIS, 1)


def _bhsd_spec(mesh: Mesh, heads: int) -> P:
    """[B, H, S, D] partition spec over every mesh axis present: batch on
    'data', heads on 'model' (only when divisible - replication across
    'model' is the legal fallback), seq on 'seq'."""
    names = mesh.axis_names
    data = "data" if "data" in names else None
    model = None
    if "model" in names and heads % mesh.shape["model"] == 0:
        model = "model"
    return P(data, model, SEQ_AXIS, None)


def ring_eligible(mesh: Optional[Mesh], seq_len: int) -> bool:
    """A real 'seq' axis whose size divides the sequence length."""
    n = seq_axis_size(mesh)
    return n > 1 and seq_len % n == 0


@partial(jax.jit, static_argnames=("mesh", "causal", "scale"))
def _ring_jit(q, k, v, mesh, causal, scale):
    spec = _bhsd_spec(mesh, q.shape[1])
    n = mesh.shape[SEQ_AXIS]

    def local_fn(q, k, v):
        idx = lax.axis_index(SEQ_AXIS)
        s_local = q.shape[2]
        # rotate kv to the next rank each step: after t steps this
        # device holds the block that started on rank (idx - t) mod n
        perm = [(j, (j + 1) % n) for j in range(n)]

        def partial_at(part, k_cur, v_cur, t):
            blk = (idx - t) % n

            def compute(part):
                p = attention_partial(q, k_cur, v_cur, scale=scale,
                                      causal=causal,
                                      q_offset=idx * s_local,
                                      kv_offset=blk * s_local)
                return merge_partials(part, p)

            if not causal:
                return compute(part)
            # causal: a K/V block from a strictly-later rank is entirely
            # in this Q block's masked future - skip its partial (the
            # naive schedule burns ~2x the needed FLOPs; the rotation
            # still happens, so correctness is carry-identical)
            return lax.cond(blk > idx, lambda p: p, compute, part)

        def step(carry, t):
            k_cur, v_cur, part = carry
            part = partial_at(part, k_cur, v_cur, t)
            k_nxt = lax.ppermute(k_cur, SEQ_AXIS, perm)
            v_nxt = lax.ppermute(v_cur, SEQ_AXIS, perm)
            return (k_nxt, v_nxt, part), None

        # the empty partial is built from constants; mark it as varying
        # over exactly the axes the inputs vary on (the in_specs' axes -
        # NOT every mesh axis: an unmentioned axis, e.g. 'expert', must
        # stay replicated or the out_specs vma check rejects the body)
        part0 = empty_partial(q)
        axes = tuple(a for a in spec if a is not None)
        part0 = jax.tree.map(
            lambda x: lax.pcast(x, axes, to="varying"), part0)
        # n-1 rotate-and-accumulate steps, then the final block WITHOUT
        # the rotation (its K/V would only feed the discarded carry -
        # one whole ring pass of wasted ICI traffic per call otherwise)
        (k_l, v_l, part), _ = lax.scan(step, (k, v, part0),
                                       jnp.arange(n - 1))
        acc, _, l = partial_at(part, k_l, v_l, n - 1)
        return finalize_partial(acc, l, q.dtype)

    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over the mesh's 'seq' axis; [B, H, S, D] global
    arrays in, semantics == ops.attention.naive_attention."""
    return _ring_jit(q, k, v, mesh, causal, scale)


@partial(jax.jit, static_argnames=("mesh", "causal", "scale", "kv_block"))
def _ulysses_jit(q, k, v, mesh, causal, scale, kv_block):
    nseq = mesh.shape[SEQ_AXIS]
    spec = _bhsd_spec(mesh, q.shape[1])
    # heads per model-shard must split across the seq axis too
    local_heads = q.shape[1] // (mesh.shape["model"]
                                 if spec[1] == "model" else 1)
    if local_heads % nseq != 0:
        raise ValueError(
            f"ulysses needs heads per shard ({local_heads}) divisible by "
            f"the seq axis ({nseq}); use ring_attention instead")

    def local_fn(q, k, v):
        # [B, H, S/n, D] -> [B, H/n, S, D]: trade the head dim for the
        # full sequence on every device
        a2a = partial(lax.all_to_all, axis_name=SEQ_AXIS, split_axis=1,
                      concat_axis=2, tiled=True)
        qg, kg, vg = a2a(q), a2a(k), a2a(v)
        o = blockwise_attention(qg, kg, vg, causal=causal, scale=scale,
                                kv_block=kv_block)
        return lax.all_to_all(o, axis_name=SEQ_AXIS, split_axis=2,
                              concat_axis=1, tiled=True)

    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, *, causal: bool = False,
                      scale: Optional[float] = None, kv_block: int = 512):
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism; [B, H, S,
    D] global arrays in, semantics == naive_attention. Requires the
    per-model-shard head count to be divisible by the 'seq' axis size."""
    return _ulysses_jit(q, k, v, mesh, causal, scale, kv_block)


SEQ_SCHEMES = ("ring", "ulysses", "none")


def seq_parallel_attention(q, k, v, mesh, scheme: str, *,
                           causal: bool = False, kv_block: int = 512):
    """Shared sp dispatch for the attention-bearing layers
    (layers/attention.py, layers/transformer_stack.py): ring or Ulysses
    over an eligible 'seq' mesh, or None for the caller's per-device
    fallback (scheme == 'none', no mesh, or ineligible seq length)."""
    if scheme == "none" or mesh is None or not ring_eligible(
            mesh, q.shape[2]):
        return None
    if scheme == "ulysses":
        return ulysses_attention(q, k, v, mesh, causal=causal,
                                 kv_block=kv_block)
    return ring_attention(q, k, v, mesh, causal=causal)
