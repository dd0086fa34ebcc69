"""Int8 post-training-quantized inference kernels (docs/GRAPH_PASSES.md
"quantize_int8").

The quantize_int8 graph pass (nnet/passes.py) stamps eligible
conv/fullc layers with a per-channel symmetric weight scale and a
per-tensor activation scale, both FROZEN at calibration time exactly
like fold_conv_bn's (mean, rstd) - so the steady-state executable
carries no max-reductions over weights or activations, only one fused
round/clip/convert pass per quantized tensor. This module is the
execution vocabulary of that pass:

- ``per_channel_scale`` / ``quantize_weight``: symmetric per-output-
  channel weight quantization. The scale is computed HOST-side from
  the transformed float weights at calibration (trainer
  `_fill_quant_scales`); the int8 values are computed IN-JIT from the
  live params, so a checkpoint load or set_weight is picked up
  (the frozen scale goes stale instead and the epoch-bump eviction
  recalibrates, the fold-stats invalidation rule).
- ``quantize_act``: per-tensor symmetric activation quantization
  against the frozen calibration scale (absmax / 127).
- ``int8_matmul``: `(m, k) x (n, k) -> (m, n)` int8 x int8 -> int32
  contraction - a Pallas TPU kernel tiling onto the MXU (int8 native
  rate, int32 accumulators) when the shape tiles cleanly, else
  `lax.dot_general` with ``preferred_element_type=int32`` (the CPU
  fallback the jaxpr quant-audit traces: int8 operands, int32
  accumulation, no f32 data-path dot either way).
- ``int8_conv2d``: NCHW int8 convolution with int32 accumulation via
  `lax.conv_general_dilated` (XLA lowers it onto the TPU MXU
  directly; no space-to-depth rewrite on the int8 path).

Cost model (docs/PERFORMANCE.md): the int8 win is weight-bandwidth +
MXU rate, so the small-batch weight-bound serving regime (a wide
fullc at batch 16) is where it should pay, while large batches
(>= 64 rows, compute-bound) and CPU convolutions are where it should
not - which is what the per-layer ``layer_quant`` tuning axis exists
to pin per platform (docs/GRAPH_PASSES.md "when int8 loses"). No
benchmark cell holds the kernel yet: its rate on the chip is not
measured.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# contraction over the last dim of both operands: x (m, k) . w (n, k)
_DN = (((1,), (1,)), ((), ()))

# smallest representable scale guard: an all-zero channel/tensor must
# quantize to zeros, not divide by zero
_SCALE_FLOOR = 1e-8

# int8 MXU tiling units (pallas_guide.md): sublane 32, lane 128
_SUBLANE, _LANE = 32, 128
# per-operand VMEM block budget (bytes); conservative vs the ~16 MB
# per-core VMEM so x/w/out blocks + double buffering fit
_VMEM_BLOCK_BYTES = 4 * 2 ** 20

# test hook: force the Pallas kernel on non-TPU backends in interpret
# mode (the pallas_lrn _FORCE_INTERPRET idiom) so CI exercises the
# kernel path without a TPU
_FORCE_INTERPRET = False


def per_channel_scale(w: np.ndarray) -> np.ndarray:
    """Symmetric per-output-channel (dim 0) scale of a weight:
    absmax / 127 per channel, floored so an all-zero channel gets a
    representable scale. HOST-side numpy - called once at calibration
    (the frozen constant the in-jit quantize divides by)."""
    w = np.asarray(w, np.float32)
    amax = np.abs(w.reshape(w.shape[0], -1)).max(axis=1)
    return (np.maximum(amax, _SCALE_FLOOR) / 127.0).astype(np.float32)


def quantize_weight(w: jax.Array, scale) -> jax.Array:
    """In-jit weight quantization against a FROZEN per-channel scale:
    one fused multiply/round/clip/convert pass over the live weight
    (no max-reduction - that happened at calibration). `scale` is
    (out_channels,); broadcasts over the remaining dims."""
    scale = jnp.asarray(scale, jnp.float32)
    inv = (1.0 / scale).reshape((-1,) + (1,) * (w.ndim - 1))
    q = jnp.clip(jnp.round(w.astype(jnp.float32) * inv), -127, 127)
    return q.astype(jnp.int8)


def quantize_act(x: jax.Array, scale) -> jax.Array:
    """Per-tensor activation quantization against the frozen
    calibration scale (a scalar): clip(round(x / s)) to [-127, 127]."""
    s = jnp.asarray(scale, jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8)


def dequantize(acc: jax.Array, act_scale, w_scale) -> jax.Array:
    """int32 accumulator -> f32: acc * (act_scale * w_scale) with the
    per-channel weight scale broadcast over the trailing dims for
    conv (n, c, h, w) or the feature dim for matmul (m, n)."""
    s = (jnp.asarray(act_scale, jnp.float32)
         * jnp.asarray(w_scale, jnp.float32))
    if acc.ndim == 4:
        return acc.astype(jnp.float32) * s[None, :, None, None]
    return acc.astype(jnp.float32) * s[None, :]


# ---------------------------------------------------------------------------
# the int8 dot: Pallas TPU kernel + lax fallback
# ---------------------------------------------------------------------------
def _mm_kernel(x_ref, w_ref, o_ref):
    # one (bm, k) x (bn, k) -> (bm, bn) MXU contraction per grid cell;
    # int32 accumulation is the kernel's whole point - never let the
    # dot default to a narrower accumulator
    o_ref[:, :] = lax.dot_general(
        x_ref[:, :], w_ref[:, :], _DN,
        preferred_element_type=jnp.int32)


def _block(dim: int, unit: int, cap: int = 512) -> int:
    """Largest divisor of `dim` that is a multiple of `unit` and at
    most `cap`; 0 when none exists (the shape does not tile)."""
    best = 0
    b = unit
    while b <= min(dim, cap):
        if dim % b == 0:
            best = b
        b += unit
    return best


def _pallas_blocks(m: int, k: int, n: int):
    """(bm, bn) Pallas block sizes, or None when the shape violates
    the int8 tiling constraints / VMEM budget and the lax fallback
    must run."""
    if k % _LANE:
        return None
    bm, bn = _block(m, _SUBLANE), _block(n, _LANE)
    if not bm or not bn:
        return None
    if max(bm, bn) * k > _VMEM_BLOCK_BYTES:
        return None
    return bm, bn


def use_pallas_int8(m: int, k: int, n: int) -> bool:
    """Kernel-route eligibility: TPU backend (or the interpret-mode
    test hook), a traced step that spans one device (parallel/mesh.py
    active_device_span; pallas_call has no GSPMD partitioning rule -
    multi-device meshes take the lax path, which GSPMD partitions),
    and clean int8 tiling."""
    from cxxnet_tpu.parallel.mesh import active_device_span
    if not (jax.default_backend() == "tpu" or _FORCE_INTERPRET):
        return False
    if active_device_span() != 1:
        return False
    return _pallas_blocks(m, k, n) is not None


def _matmul_pallas(xq: jax.Array, wq: jax.Array) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401
    m, k = xq.shape
    n = wq.shape[0]
    bm, bn = _pallas_blocks(m, k, n)
    return pl.pallas_call(
        _mm_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, k), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=_FORCE_INTERPRET,
        name="int8_matmul",
    )(xq, wq)


def int8_matmul(xq: jax.Array, wq: jax.Array) -> jax.Array:
    """`xq (m, k) . wq (n, k)^T -> (m, n)` with int8 operands and
    int32 accumulation: the Pallas MXU kernel when eligible, else the
    lax.dot_general preferred-element-type fallback (same jaxpr-level
    contract either way - the quant-audit asserts it)."""
    m, k = xq.shape
    if use_pallas_int8(m, k, wq.shape[0]):
        return _matmul_pallas(xq, wq)
    return lax.dot_general(xq, wq, _DN,
                           preferred_element_type=jnp.int32)


def int8_conv2d(xq: jax.Array, wq: jax.Array, stride: int, pad_y: int,
                pad_x: int, num_group: int = 1) -> jax.Array:
    """Grouped NCHW int8 convolution with int32 accumulation. The
    space-to-depth rewrite does not apply on the int8 path (the
    direct lowering is value-identical; s2d exists for f32/bf16 MXU
    density, which int8 gets from its native rate)."""
    return lax.conv_general_dilated(
        xq, wq,
        window_strides=(stride, stride),
        padding=((pad_y, pad_y), (pad_x, pad_x)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=num_group,
        preferred_element_type=jnp.int32,
    )
