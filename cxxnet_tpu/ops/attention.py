"""Scaled-dot-product attention ops: naive, blockwise (flash-style), and
the partial/merge primitives ring attention is built from.

The reference has no attention (cxxnet predates it - SURVEY.md notes
sequence models are absent), so this module is pure TPU-native extension
surface: it exists so the framework's long-context story (ring /
all-to-all sequence parallelism, parallel/ring.py) has a single-device
ground truth and a memory-efficient local kernel.

Layout convention: [batch, heads, seq, head_dim] (BHSD). All softmax
arithmetic runs in float32 regardless of input dtype (bf16 scores lose
the softmax's dynamic range on TPU); the output is cast back to the
query dtype.

The blockwise form is the standard online-softmax recurrence: partial
results are (acc, m, l) - unnormalized weighted values, running row max,
running denominator - merged associatively, which is exactly what lets
the ring variant accumulate across K/V blocks that arrive one ppermute
step at a time.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Finite stand-in for -inf in masked score entries: exp(x - m) with both
# at -1e30 is exp(0)=1 only when ALL entries of a row are masked, and
# such rows carry l=0 and are resolved by the caller (or cannot occur -
# causal rows always see their own position). -inf itself would produce
# inf-inf=nan in the max-subtraction.
_NEG = -1e30


def _scale(q, scale: Optional[float]) -> float:
    return (1.0 / (q.shape[-1] ** 0.5)) if scale is None else scale


def _causal_bias(sq: int, sk: int, q_offset, kv_offset,
                 window: int = 0) -> jax.Array:
    """(sq, sk) additive bias: 0 where key position <= query position in
    GLOBAL coordinates (and, with a `window`, less than `window`
    positions behind it), _NEG elsewhere. Offsets may be traced values
    (ring attention passes lax.axis_index-derived block offsets)."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = kv_offset + jnp.arange(sk)[None, :]
    seen = kpos <= qpos
    if window:
        seen = seen & (qpos - kpos < window)
    return jnp.where(seen, 0.0, _NEG)


def _check_window(window: int, causal: bool) -> None:
    if window and not causal:
        raise ValueError("a window needs causal attention")


def _per_query_head(q, k, v):
    """Grouped-query heads: k and v hold `q.shape[1] // k.shape[1]`
    times fewer heads than q, and query head h reads head h // group.
    Written out here (the XLA routes are the ground truth, not the fast
    path; the flash kernel takes the shared head through its index
    map)."""
    group, rest = divmod(q.shape[1], k.shape[1])
    if rest:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} "
                         "key/value heads")
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def naive_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, window: int = 0):
    """Reference semantics: softmax(q.k^T * scale [+ causal mask]).v with
    the full (sq, sk) score matrix materialized. Ground truth for the
    blockwise/ring variants' differential tests. `window` (causal only):
    a query sees the `window` positions up to its own. k and v may hold
    fewer heads than q (`_per_query_head`)."""
    _check_window(window, causal)
    k, v = _per_query_head(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s.astype(jnp.float32) * _scale(q, scale)
    if causal:
        s = s + _causal_bias(q.shape[2], k.shape[2], 0, 0,
                             window)[None, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def attention_partial(q, k, v, *, scale: Optional[float] = None,
                      causal: bool = False, q_offset=0, kv_offset=0,
                      kv_valid: Optional[int] = None, window: int = 0,
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One K/V block's contribution as an online-softmax partial.

    Returns (acc [B,H,Sq,D] f32 unnormalized, m [B,H,Sq] f32 row max,
    l [B,H,Sq] f32 denominator). Offsets place the blocks on the global
    sequence for causal masking (traced values allowed). `kv_valid`
    masks key GLOBAL positions >= kv_valid - the tail-padding mask for
    callers that pad K/V up to a block-size multiple."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s.astype(jnp.float32) * _scale(q, scale)
    if causal:
        s = s + _causal_bias(q.shape[2], k.shape[2],
                             q_offset, kv_offset, window)[None, None]
    if kv_valid is not None:
        kpos = kv_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where((kpos < kv_valid)[None, None], s, _NEG)
    m = jnp.max(s, axis=-1)
    # keep fully-masked rows finite: their p rows are exp(_NEG - _NEG)=1
    # scaled below by where(), so force p=0 via the mask itself
    p = jnp.exp(s - m[..., None])
    p = jnp.where(s <= _NEG * 0.5, 0.0, p)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc, m, l


def merge_partials(a: Tuple[jax.Array, jax.Array, jax.Array],
                   b: Tuple[jax.Array, jax.Array, jax.Array],
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Associative merge of two online-softmax partials."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    acc = acc_a * ca[..., None] + acc_b * cb[..., None]
    l = l_a * ca + l_b * cb
    return acc, m, l


def finalize_partial(acc, l, dtype) -> jax.Array:
    """acc/l with fully-masked rows (l=0) resolved to 0."""
    safe = jnp.where(l > 0, l, 1.0)
    return (acc / safe[..., None]).astype(dtype)


def empty_partial(q) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, h, sq, d = q.shape
    return (jnp.zeros((b, h, sq, d), jnp.float32),
            jnp.full((b, h, sq), _NEG, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32))


def blockwise_attention(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None,
                        kv_block: int = 512, window: int = 0):
    """Flash-style memory-efficient attention: lax.scan over K/V blocks
    with the online-softmax recurrence; peak score memory is
    (Sq, kv_block) instead of (Sq, Sk). Semantics == naive_attention.

    The scan carries f32 (acc, m, l); XLA keeps the whole loop on-chip.
    The backward keeps each block's scores: wrap the call in
    jax.checkpoint where the O(S) memory has to hold there too.
    `window` and grouped heads as `naive_attention`; the window is
    masked here, block by block, not skipped."""
    _check_window(window, causal)
    k, v = _per_query_head(q, k, v)
    sk = k.shape[2]
    kv_block = min(kv_block, sk)
    if nblk_pad := (-sk) % kv_block:
        # static shapes: pad K/V up to the next block multiple and mask
        # the tail (kv_valid). A divisor fallback would degrade to
        # kv_block=1 - an S-iteration serial scan - on prime/odd
        # lengths, exactly the long sequences this exists for.
        pad = ((0, 0), (0, 0), (0, nblk_pad), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    kv_valid = sk if nblk_pad else None
    nblk = k.shape[2] // kv_block
    if nblk == 1:
        acc, m, l = attention_partial(q, k, v, scale=scale, causal=causal,
                                      kv_valid=kv_valid, window=window)
        return finalize_partial(acc, l, q.dtype)

    kb = k.reshape(k.shape[0], k.shape[1], nblk, kv_block, k.shape[3])
    vb = v.reshape(v.shape[0], v.shape[1], nblk, kv_block, v.shape[3])
    kb = jnp.moveaxis(kb, 2, 0)   # [nblk, B, H, kv_block, D]
    vb = jnp.moveaxis(vb, 2, 0)

    def step(carry, xs):
        kv_i, k_i, v_i = xs
        part = attention_partial(q, k_i, v_i, scale=scale, causal=causal,
                                 q_offset=0, kv_offset=kv_i * kv_block,
                                 kv_valid=kv_valid, window=window)
        return merge_partials(carry, part), None

    init = empty_partial(q)
    (acc, _, l), _ = lax.scan(step, init, (jnp.arange(nblk), kb, vb))
    return finalize_partial(acc, l, q.dtype)
