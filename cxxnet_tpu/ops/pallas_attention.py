"""Fused flash-attention Pallas TPU kernels (forward + backward).

The attention core (ops/attention.py) is where a sequence model's FLOPs
and HBM traffic live; this is its Pallas fast path, same integration
pattern as the LRN kernel (ops/pallas_lrn.py): TPU-only `pallas_call`
with an XLA fallback and an interpret-mode test hook.

Design (the standard flash-attention schedule on TPU):

- forward: grid (B, H, nQ, nKV), innermost KV dim sequential
  ("arbitrary") so f32 VMEM scratch (acc, m, l) carries the
  online-softmax state across KV blocks of one Q block; the last KV
  step writes o = acc/l and the logsumexp row stats (lse = m + log l).
  Only (BQ, BK) score tiles ever materialize - O(S) memory instead of
  O(S^2), MXU-sized tiles instead of one giant softmax.
- backward: recompute p = exp(q.k*scale - lse) per tile from the saved
  lse (no S x S residuals). With delta = rowsum(do * o):
      ds = p * (do . v^T - delta)
      dq += ds . k * scale     (grid (B, H, nQ, nKV), KV innermost)
      dk += ds^T . q * scale   (grid (B, H, nKV, nQ), Q innermost)
      dv += p^T . do
  exposed as one jax.custom_vjp around the forward.
- causal masking is done in global coordinates from program ids;
  fully-future tiles are skipped with @pl.when (forward) so the causal
  schedule does ~half the work, matching the math of
  ops/attention.py exactly (differential tests, test_pallas_attention).

Softmax arithmetic is f32 regardless of input dtype (bf16 inputs feed
the MXU as bf16, accumulate f32 via preferred_element_type).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cxxnet_tpu.ops.attention import _scale

_NEG = -1e30

# default tile sizes, set by an on-chip sweep (v5e, b4 h8 s4096 d128
# fwd+grads): (1024, 1024) runs 56.7 TFLOP/s
# non-causal = 4.04x the XLA blockwise path, where the old MXU-exact
# (128, 128) managed only 0.93x - at 128 the (b, h, s/bq, s/bk) grid
# is 32k programs whose per-program overhead dominates; 1024-tiles
# amortize it 64x and Mosaic still sub-tiles the 1024x1024 f32 score
# block through the MXU. Shrunk automatically for short sequences
# (_blocks picks the largest divisor of s <= BLOCK).
BLOCK_Q = 1024
BLOCK_K = 1024


def _block_limits(d: int) -> Tuple[int, int]:
    """The tiles for head size `d`. Over 128 wide a head takes two lane
    tiles (`mla` runs 192, laid out as 256), and the backward's dk/dv
    kernel at 1024 x 1024 then asks for 17 MB of the 16 MB of VMEM a
    kernel may use (the chip's compiler, at 8,192 positions): such
    heads take 512-wide tiles."""
    return (BLOCK_Q, BLOCK_K) if d <= 128 else (BLOCK_Q // 2, BLOCK_K // 2)

# Mosaic requires the last two dims of every block shape to be
# (sublane, lane)-tileable: divisible by (8, 128) or equal to the
# array dims. A per-row stat laid out as (b, h, s) with block
# (1, 1, bq) violates that (second-to-last block dim 1 vs array dim
# h), so lse/delta ride a trailing broadcast dim of 8 - block
# (1, 1, bq, 8) is (128, 8)-tiled, and 8 == the array dim satisfies
# the lane rule (same trick as jax's reference flash kernel, which
# uses a trailing MIN_BLOCK_SIZE=128; 8 costs 16x less HBM for the
# saved residual).
_STAT_LANES = 8


def _sublane(dtype) -> int:
    return 16 if dtype == jnp.bfloat16 else 8


def _blocks(s: int, block: int, sub: int = 1) -> int:
    """Largest divisor of s that is <= block and a multiple of the
    sublane tile (preferred); falls back to any divisor (interpret mode
    has no tiling constraint)."""
    for b in range(min(block, s), 0, -1):
        if s % b == 0 and b % sub == 0:
            return b
    b = min(block, s)
    while s % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *,
                scale, causal, bq, bk, nkv):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, _NEG)
        l[:] = jnp.zeros_like(l)

    qi = pl.program_id(2)
    q_off = qi * bq
    kv_off = ki * bk

    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kv_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _NEG)
        m_new = jnp.maximum(m[:], jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(s <= _NEG * 0.5, 0.0, p)
        corr = jnp.exp(m[:] - m_new)
        l[:] = l[:] * corr + jnp.sum(p, axis=1)
        acc[:] = acc[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m[:] = m_new

    if causal:
        pl.when(kv_off <= q_off + bq - 1)(_tile)
    else:
        _tile()

    @pl.when(ki == nkv - 1)
    def _out():
        safe = jnp.where(l[:] > 0, l[:], 1.0)
        o_ref[0, 0] = (acc[:] / safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            (m[:] + jnp.log(safe))[:, None], (bq, _STAT_LANES))


def _fwd(q, k, v, scale, causal, interpret) -> Tuple[jax.Array, jax.Array]:
    b, h, s, d = q.shape
    sub = _sublane(q.dtype)
    lq, lk = _block_limits(d)
    bq, bk = _blocks(s, lq, sub), _blocks(k.shape[2], lk, sub)
    nq, nkv = s // bq, k.shape[2] // bk
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nkv=nkv)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0))
    kspec = pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h, ki, 0))
    o, lse = pl.pallas_call(
        kern,
        grid=(b, h, nq, nkv),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, bq, _STAT_LANES),
                                lambda b, h, qi, ki: (b, h, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, _STAT_LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc, *, scale, causal, bq, bk, nkv):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    qi = pl.program_id(2)
    q_off, kv_off = qi * bq, ki * bk
    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kv_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _NEG)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])
        dov = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dov - delta_ref[0, 0][:, :1])
        acc[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(kv_off <= q_off + bq - 1)(_tile)
    else:
        _tile()

    @pl.when(ki == nkv - 1)
    def _out():
        dq_ref[0, 0] = acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, acck, accv, *, scale, causal, bq, bk, nq):
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        acck[:] = jnp.zeros_like(acck)
        accv[:] = jnp.zeros_like(accv)

    ki = pl.program_id(2)
    q_off, kv_off = qi * bq, ki * bk
    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kv_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _NEG)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])            # (bq, bk)
        do = do_ref[0, 0]
        accv[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)
        dov = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dov - delta_ref[0, 0][:, :1])          # (bq, bk)
        acck[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)

    if causal:
        pl.when(kv_off <= q_off + bq - 1)(_tile)
    else:
        _tile()

    @pl.when(qi == nq - 1)
    def _out():
        dk_ref[0, 0] = acck[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = accv[:].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, scale, causal, interpret):
    b, h, s, d = q.shape
    sk = k.shape[2]
    sub = _sublane(q.dtype)
    lq, lk = _block_limits(d)
    bq, bk = _blocks(s, lq, sub), _blocks(sk, lk, sub)
    nq, nkv = s // bq, sk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # (b, h, s)
    delta = jnp.broadcast_to(delta[..., None],
                             (*delta.shape, _STAT_LANES))

    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0))
    kspec = pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h, ki, 0))
    rspec = pl.BlockSpec((1, 1, bq, _STAT_LANES),
                         lambda b, h, qi, ki: (b, h, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nkv=nkv),
        grid=(b, h, nq, nkv),
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    # swapped grid: kv outer, q inner (sequential) so dk/dv accumulate
    qspec2 = pl.BlockSpec((1, 1, bq, d), lambda b, h, ki, qi: (b, h, qi, 0))
    kspec2 = pl.BlockSpec((1, 1, bk, d), lambda b, h, ki, qi: (b, h, ki, 0))
    rspec2 = pl.BlockSpec((1, 1, bq, _STAT_LANES),
                          lambda b, h, ki, qi: (b, h, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq),
        grid=(b, h, nkv, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rspec2, rspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry: custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    interpret: bool = False):
    """Fused TPU attention; semantics == ops.attention.naive_attention.
    [B, H, S, D] in/out; O(S) memory; causal skips future tiles."""
    sc = _scale(q, scale)
    o, _ = _fwd(q, k, v, sc, causal, interpret)
    return o


def _vjp_fwd(q, k, v, causal, scale, interpret):
    sc = _scale(q, scale)
    o, lse = _fwd(q, k, v, sc, causal, interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, scale, interpret, res, do):
    q, k, v, o, lse = res
    sc = _scale(q, scale)
    return _bwd_impl(q, k, v, o, lse, do, sc, causal, interpret)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

# test hook, same convention as ops/pallas_lrn.py: force the kernel on
# non-TPU backends in interpret mode
_FORCE_INTERPRET = False


def _backend_ok() -> bool:
    return jax.default_backend() == "tpu" or _FORCE_INTERPRET


def _tile_ok(q, sk: int) -> bool:
    """Mosaic tileability: both score-tile dims must land on sublane
    multiples (the fallback-divisor path is for interpret mode only)
    and tiny dims would underfill the MXU for no win."""
    sub = _sublane(q.dtype)
    return (q.shape[2] >= sub and sk >= sub and q.shape[3] >= 8
            and _blocks(q.shape[2], _block_limits(q.shape[3])[0], sub)
            % sub == 0
            and _blocks(sk, _block_limits(q.shape[3])[1], sub) % sub == 0)


def use_flash(q) -> bool:
    """Single-device eligibility: TPU backend, the traced step spans
    one device (parallel/mesh.py active_device_span), tileable shapes.
    On a multi-device mesh use the shard_map route below - pallas_call
    alone has no GSPMD partitioning rule (same split as
    ops/pallas_lrn.py)."""
    from cxxnet_tpu.parallel.mesh import active_device_span
    return (_backend_ok() and active_device_span() == 1
            and _tile_ok(q, q.shape[2]))


def use_flash_sharded(q, mesh) -> bool:
    """shard_map-route eligibility: attention is independent per
    (batch, head), so sharding batch over 'data' (and heads over
    'model') needs no cross-device communication; each device runs the
    kernel on its local shard. The full sequence stays per-device - a
    'seq'-sharded input takes the ring route instead
    (layers/attention.py)."""
    from cxxnet_tpu.parallel.mesh import batch_shardable
    return (_backend_ok() and mesh is not None
            and batch_shardable(mesh, q.shape[0])
            and _tile_ok(q, q.shape[2]))


def flash_attention_sharded(q, k, v, mesh, causal: bool = False,
                            scale: Optional[float] = None):
    """flash_attention over a multi-device mesh: batch on 'data', heads
    on 'model' when divisible (replicated-head compute otherwise, same
    fallback as the LRN kernel's TP note)."""
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names
    model = ("model" if "model" in names
             and q.shape[1] % mesh.shape["model"] == 0 else None)
    spec = P("data" if "data" in names else None, model, None, None)
    fn = jax.shard_map(
        lambda qs, ks, vs: flash_attention(qs, ks, vs, causal, scale,
                                           _FORCE_INTERPRET),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # per-shard kernel, no collectives: nothing for vma to verify
        check_vma=False)
    return fn(q, k, v)
