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

from cxxnet_tpu.ops.attention import _check_window, _scale

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

# The row statistics (lse, delta) are (b, h, 1, s) float32, the
# positions on lanes: the chip tiles the last two dims of an array by
# (8, 128), so a short trailing dim is padded to 128 lanes ((b, h, s, 8)
# was 224 MiB a `gqa` layer of 28 heads at 16,384 positions for
# 1.75 MiB of data, and eight such layers did not fit unless they were
# checkpointed: PERF.md section 6, PR 37), while XLA keeps this shape
# unpadded (tiled T(1,128)). Mosaic wants the last two dims of a block
# divisible by (8, 128) or equal to the array's: the block is
# (1, 1, 1, bq), the 1 the array's and the query tile a multiple of 128
# or the whole sequence (`_q_block`).
_LANE = 128


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
# which tiles run
# ---------------------------------------------------------------------------
# A causal layer with a `window` (a query sees the `window` positions up
# to its own) needs the score tiles of a band, not of the whole lower
# triangle. The grids below do not walk the tiles outside it: the inner
# grid dimension counts STEPS, as many as a band row (or column) can
# hold tiles, and step j of query tile qi reads key tile
# `_kv_tile(qi, j)` (the backward's dk/dv pass: step j of key tile ki
# reads query tile `_q_tile(ki, j)`). A step that lands outside the
# sequence is skipped and its block index clamped, so no tile is fetched
# for it. Without a window a step is a tile, as it always was, and a
# wholly future tile is fetched and skipped.

def _tile_live(q_off, kv_off, bq: int, bk: int, window: int):
    """Whether the tile holds a (query, key) pair that is causal and
    inside the window. Python ints or traced scalars."""
    live = kv_off <= q_off + bq - 1
    if window:
        live = live & (kv_off + bk - 1 > q_off - window)
    return live


def _run_live(tile, causal: bool, window: int, in_range, q_off, kv_off,
              bq: int, bk: int) -> None:
    """Run a kernel's `tile` where it holds a pair the mask keeps;
    `in_range`: the step of a window's grid landed inside the
    sequence."""
    if window:
        pl.when(in_range & _tile_live(q_off, kv_off, bq, bk, window))(tile)
    elif causal:
        pl.when(_tile_live(q_off, kv_off, bq, bk, 0))(tile)
    else:
        tile()


def _kv_steps(nq: int, nkv: int, bq: int, bk: int, window: int) -> int:
    if not window:
        return nkv
    return max((qi * bq + bq - 1) // bk
               - max((qi * bq - window + 1) // bk, 0) + 1
               for qi in range(nq))


def _kv_tile(qi, j, bq: int, bk: int, steps: int, window: int):
    """The key tile of step j: the band row ends at the diagonal."""
    if not window:
        return j
    return (qi * bq + bq - 1) // bk - (steps - 1) + j


def _q_steps(nq: int, nkv: int, bq: int, bk: int, window: int) -> int:
    if not window:
        return nq
    return max(min((ki * bk + bk + window - 2) // bq, nq - 1)
               - (ki * bk) // bq + 1 for ki in range(nkv))


def _q_tile(ki, j, bq: int, bk: int, window: int):
    """The query tile of step j: the band column starts at the
    diagonal."""
    if not window:
        return j
    return (ki * bk) // bq + j


def _q_block(s: int, block: int, sub: int) -> int:
    """The query tile: it is also the lane dim of a row statistic's
    block, so the whole sequence or a multiple of 128."""
    if s <= block and s % sub == 0:
        return s
    return _blocks(s, block, _LANE)


def _tiles_of(q, sk: int) -> Tuple[int, int]:
    sub = _sublane(q.dtype)
    lq, lk = _block_limits(q.shape[3])
    return _q_block(q.shape[2], lq, sub), _blocks(sk, lk, sub)


def tile_share(q, window: int) -> float:
    """The score tiles the forward kernel runs for `q` (b, h, s, d)
    under causal attention with this window, over those it runs without
    one: 1 for a full layer. Static: what a layer's `tiles` counter
    says (layers/lm.py)."""
    bq, bk = _tiles_of(q, q.shape[2])
    n = q.shape[2]

    def run(w):
        return sum(bool(_tile_live(qo, ko, bq, bk, w))
                   for qo in range(0, n, bq) for ko in range(0, n, bk))

    return run(window) / run(0)


def _name(kernel: str, window: int) -> str:
    """The device event's name: a window kernel is told apart."""
    return f"flash_win_{kernel}" if window else f"flash_{kernel}"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mask(s, q_off, kv_off, bq: int, bk: int, window: int,
          by_key: bool = False):
    """Scores (bq, bk), or `by_key` their transpose (bk, bq), with the
    pairs a query does not see at _NEG."""
    shape, qdim = ((bk, bq), 1) if by_key else ((bq, bk), 0)
    qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, shape, qdim)
    kpos = kv_off + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - qdim)
    seen = kpos <= qpos
    if window:
        seen = seen & (qpos - kpos < window)
    return jnp.where(seen, s, _NEG)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *,
                scale, causal, bq, bk, steps, window):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, _NEG)
        l[:] = jnp.zeros_like(l)

    qi = pl.program_id(2)
    ki = _kv_tile(qi, j, bq, bk, steps, window)
    q_off = qi * bq
    kv_off = ki * bk

    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, q_off, kv_off, bq, bk, window)
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

    _run_live(_tile, causal, window, ki >= 0, q_off, kv_off, bq, bk)

    @pl.when(j == steps - 1)
    def _out():
        safe = jnp.where(l[:] > 0, l[:], 1.0)
        o_ref[0, 0] = (acc[:] / safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m[:] + jnp.log(safe))[None, :]


def _specs(q, k, window: int, tiles: Tuple[int, int]):
    """The steps of the inner grid dimension and the block specs of a
    (b, h, nq, steps) grid: query-side blocks follow the query tile,
    key-side blocks the step's key tile in the head that the query
    head's group shares."""
    d = q.shape[3]
    bq, bk = tiles
    nq, nkv = q.shape[2] // bq, k.shape[2] // bk
    group = q.shape[1] // k.shape[1]
    steps = _kv_steps(nq, nkv, bq, bk, window)

    def kv_at(b, h, qi, j):
        ki = _kv_tile(qi, j, bq, bk, steps, window)
        if window:
            ki = jnp.maximum(ki, 0)
        return (b, h // group if group > 1 else h, ki, 0)

    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, j: (b, h, qi, 0))
    kspec = pl.BlockSpec((1, 1, bk, d), kv_at)
    rspec = pl.BlockSpec((1, 1, 1, bq),
                         lambda b, h, qi, j: (b, h, 0, qi))
    return bq, bk, nq, nkv, group, steps, qspec, kspec, rspec


_BY_QUERY = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
# ONE jitted function a direction (as ops/pallas_kda.py): the layers of
# a step that call it with one shape, one window and the same tiles
# share one lowered kernel body, which is set-up no compile cache saves
_STATIC = ("scale", "causal", "interpret", "window", "tiles")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd(q, k, v, scale, causal, interpret, window, tiles
         ) -> Tuple[jax.Array, jax.Array]:
    b, h, s, d = q.shape
    bq, bk, nq, _, _, steps, qspec, kspec, rspec = _specs(q, k, window,
                                                          tiles)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, steps=steps, window=window)
    o, lse = pl.pallas_call(
        kern,
        grid=(b, h, nq, steps),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, rspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32)],
        compiler_params=_BY_QUERY,
        interpret=interpret,
        name=_name("fwd", window),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc, lse, delta, *, scale, causal, bq, bk, steps, window):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        # the tile's rows of statistics, turned once into the columns
        # that every step of j subtracts from its (bq, bk) scores
        lse[:] = jnp.expand_dims(lse_ref[0, 0, 0], -1)
        delta[:] = jnp.expand_dims(delta_ref[0, 0, 0], -1)

    qi = pl.program_id(2)
    ki = _kv_tile(qi, j, bq, bk, steps, window)
    q_off, kv_off = qi * bq, ki * bk

    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, q_off, kv_off, bq, bk, window)
        p = jnp.exp(s - lse[:])
        dov = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dov - delta[:])
        acc[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _run_live(_tile, causal, window, ki >= 0, q_off, kv_off, bq, bk)

    @pl.when(j == steps - 1)
    def _out():
        dq_ref[0, 0] = acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, acck, accv, *, scale, causal, bq, bk, nq,
                steps, group, window):
    """One key tile of one key/value head: the inner dimension walks the
    query tiles of its band, once for each query head of the group, and
    sums. The scores are computed transposed, (bk, bq): a row of
    statistics then broadcasts over the sublanes as it lies, and dk and
    dv are plain products."""
    t = pl.program_id(3)

    @pl.when(t == 0)
    def _init():
        acck[:] = jnp.zeros_like(acck)
        accv[:] = jnp.zeros_like(accv)

    ki = pl.program_id(2)
    qi = _q_tile(ki, t % steps if group > 1 else t, bq, bk, window)
    q_off, kv_off = qi * bq, ki * bk

    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bk, bq)
        if causal:
            s = _mask(s, q_off, kv_off, bq, bk, window, by_key=True)
        p = jnp.exp(s - lse_ref[0, 0])
        do = do_ref[0, 0]
        accv[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)
        dov = jax.lax.dot_general(
            v_ref[0, 0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dov - delta_ref[0, 0])                 # (bk, bq)
        acck[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)

    _run_live(_tile, causal, window, qi < nq, q_off, kv_off, bq, bk)

    @pl.when(t == group * steps - 1)
    def _out():
        dk_ref[0, 0] = acck[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = accv[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_impl(q, k, v, o, lse, do, scale, causal, interpret, window, tiles):
    b, h, s, d = q.shape
    bq, bk, nq, nkv, group, steps, qspec, kspec, rspec = _specs(
        q, k, window, tiles)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]  # (b, h, 1, s), as lse

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, steps=steps, window=window),
        grid=(b, h, nq, steps),
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=_BY_QUERY,
        interpret=interpret,
        name=_name("dq", window),
    )(q, k, v, do, lse, delta)

    # swapped grid: a key/value head's key tiles outer, the query tiles
    # of their band inner (sequential, once a query head of the group)
    # so dk/dv accumulate
    qsteps = _q_steps(nq, nkv, bq, bk, window)

    def q_at(b, hk, ki, t):
        qi = _q_tile(ki, t % qsteps if group > 1 else t, bq, bk, window)
        if window:
            qi = jnp.minimum(qi, nq - 1)
        return b, hk * group + t // qsteps if group > 1 else hk, qi

    def stat_at(*at):
        b, h, qi = q_at(*at)
        return b, h, 0, qi

    qspec2 = pl.BlockSpec((1, 1, bq, d), lambda *at: q_at(*at) + (0,))
    kspec2 = pl.BlockSpec((1, 1, bk, d), lambda b, hk, ki, t: (b, hk, ki, 0))
    rspec2 = pl.BlockSpec((1, 1, 1, bq), stat_at)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, steps=qsteps, group=group,
                          window=window),
        grid=(b, k.shape[1], nkv, group * qsteps),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rspec2, rspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_BY_QUERY,
        interpret=interpret,
        name=_name("dkv", window),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry: custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    interpret: bool = False, window: int = 0):
    """Fused TPU attention; semantics == ops.attention.naive_attention.
    [B, H, S, D] in/out; O(S) memory; causal skips future tiles, and
    with a `window` (causal only) the tiles left of it are never
    walked. k and v may hold fewer heads than q: query head h reads
    head h // (H // Hkv) through the index map (no repeated copy), and
    the backward sums a group's query heads into its dk and dv."""
    return _vjp_fwd(q, k, v, causal, scale, interpret, window)[0]


def _check(q, k, causal: bool, window: int) -> None:
    _check_window(window, causal)
    if window and q.shape[2] != k.shape[2]:
        raise ValueError("a window needs queries and keys of one length")
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} "
                         "key/value heads")


def _vjp_fwd(q, k, v, causal, scale, interpret, window):
    _check(q, k, causal, window)
    o, lse = _fwd(q, k, v, _scale(q, scale), causal, interpret, window,
                  _tiles_of(q, k.shape[2]))
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, scale, interpret, window, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, _scale(q, scale), causal,
                     interpret, window, _tiles_of(q, k.shape[2]))


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
    multiples and the query tile, a row statistic's lanes, on the whole
    sequence or a multiple of 128 (the fallback-divisor path is for
    interpret mode only); tiny dims would underfill the MXU for no
    win."""
    sub = _sublane(q.dtype)
    bq, bk = _tiles_of(q, sk)
    return (q.shape[2] >= sub and sk >= sub and q.shape[3] >= 8
            and bq % sub == 0 and bk % sub == 0
            and (bq == q.shape[2] or bq % _LANE == 0))


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
