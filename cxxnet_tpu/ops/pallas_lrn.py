"""Fused Pallas TPU kernel for cross-channel LRN (fwd + analytic bwd).

The XLA lowering of LRN (ops/nn.py: reduce_window over channels + power)
materializes the squared tensor and the window sum in HBM; on AlexNet the
two LRN layers cost ~9% of the train step, all bandwidth + transcendental
VPU work. This kernel fuses square -> channel-window sum -> pow(-beta)
-> scale into one VMEM pass (the role cudnn fast paths play in the
reference - cudnn_convolution_layer-inl.hpp:13-171), with the analytic
backward of lrn_layer-inl.hpp:59-77 as a second kernel under custom_vjp:

    norm_c  = knorm + alpha/n * sum_{j in win(c)} x_j^2
    out_c   = x_c * norm_c^-beta
    gin_c   = g_c * norm_c^-beta
              - (2 alpha beta / n) * x_c * rsum_c
    rsum_c  = sum_{j : c in win(j)} g_j * x_j * norm_j^(-beta-1)

win(c) = [c-lo, c+hi] with lo = n//2, hi = n-lo-1 (the reference chpool
convention); the backward sum runs over the reversed window [c-hi, c+lo].

Kernels tile (B, C, H*W) as (1, C, T) VMEM blocks over a (B, ceil(HW/T))
grid; channel shifts are static concat+slice, unrolled over the window
(local_size is a config constant). Falls back to the XLA path off-TPU or
when C violates the sublane tiling constraint.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU lowering)

_LANE_TILE = 512


def _shift_down(a: jax.Array, d: int) -> jax.Array:
    """result[c] = a[c-d] (zeros shifted in at the top)."""
    z = jnp.zeros((d, a.shape[1]), a.dtype)
    return jnp.concatenate([z, a[:-d]], axis=0)


def _shift_up(a: jax.Array, d: int) -> jax.Array:
    """result[c] = a[c+d] (zeros shifted in at the bottom)."""
    z = jnp.zeros((d, a.shape[1]), a.dtype)
    return jnp.concatenate([a[d:], z], axis=0)


def _window_sum(a: jax.Array, up: int, down: int) -> jax.Array:
    """sum_{j = c-down}^{c+up} a[j] along axis 0, zero padded."""
    s = a
    for d in range(1, up + 1):
        s = s + _shift_up(a, d)
    for d in range(1, down + 1):
        s = s + _shift_down(a, d)
    return s


def _fwd_kernel(x_ref, o_ref, *, n, alpha, beta, knorm):
    x = x_ref[0].astype(jnp.float32)
    lo, hi = n // 2, n - n // 2 - 1
    # norm_c sums x_j^2 over the window j in [c-lo, c+hi]
    s = _window_sum(x * x, hi, lo)
    norm = knorm + (alpha / n) * s
    o_ref[0] = (x * jnp.power(norm, -beta)).astype(o_ref.dtype)


def _bwd_kernel(x_ref, g_ref, o_ref, *, n, alpha, beta, knorm):
    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    lo, hi = n // 2, n - n // 2 - 1
    norm = knorm + (alpha / n) * _window_sum(x * x, hi, lo)
    u = g * x * jnp.power(norm, -beta - 1.0)
    # reversed window [c-hi, c+lo]
    rsum = _window_sum(u, lo, hi)
    gin = g * jnp.power(norm, -beta) - (2.0 * alpha * beta / n) * x * rsum
    o_ref[0] = gin.astype(o_ref.dtype)


def _tile_ok(x: jax.Array) -> bool:
    c = x.shape[1]
    sub = 16 if x.dtype == jnp.bfloat16 else 8
    return c % sub == 0 and c * _LANE_TILE * 4 * 3 < 12 * 2 ** 20


def _call(kernel, name, args, x, interpret):
    b, c, h, w = x.shape
    hw = h * w
    t = min(_LANE_TILE, hw)
    grid = (b, pl.cdiv(hw, t))
    spec = pl.BlockSpec((1, c, t), lambda i, j: (i, 0, j))
    flat = [a.reshape(b, c, hw) for a in args]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, c, hw), x.dtype),
        grid=grid,
        in_specs=[spec] * len(flat),
        out_specs=spec,
        interpret=interpret,
        # stable name: trace reductions and chip_smoke.py find the
        # kernel in the step by it
        name=name,
    )(*flat)
    return out.reshape(b, c, h, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_pallas(x, local_size, alpha, beta, knorm, interpret=False):
    """Fused LRN; numerically identical to ops.nn.lrn (tested to 1e-5)."""
    kern = functools.partial(_fwd_kernel, n=local_size, alpha=alpha,
                             beta=beta, knorm=knorm)
    return _call(kern, "lrn_fwd", [x], x, interpret)


def _vjp_fwd(x, local_size, alpha, beta, knorm, interpret=False):
    return lrn_pallas(x, local_size, alpha, beta, knorm, interpret), x


def _vjp_bwd(local_size, alpha, beta, knorm, interpret, x, g):
    kern = functools.partial(_bwd_kernel, n=local_size, alpha=alpha,
                             beta=beta, knorm=knorm)
    return (_call(kern, "lrn_bwd", [x, g], x, interpret),)


lrn_pallas.defvjp(_vjp_fwd, _vjp_bwd)


def use_pallas_lrn(x: jax.Array) -> bool:
    """Single-device eligibility: TPU backend, the traced step spans
    one device (parallel/mesh.py active_device_span - the mesh, not
    the host's device count), and the channel dim tiles cleanly. On a
    multi-device mesh use the shard_map route below - pallas_call
    alone has no GSPMD partitioning rule."""
    from cxxnet_tpu.parallel.mesh import active_device_span
    return (_backend_ok() and active_device_span() == 1
            and _tile_ok(x))


# test hook: force the kernel on non-TPU backends in interpret mode so
# the shard_map route is exercised on the virtual CPU mesh
_FORCE_INTERPRET = False


def _backend_ok() -> bool:
    return jax.default_backend() == "tpu" or _FORCE_INTERPRET


def use_pallas_lrn_sharded(x: jax.Array, mesh) -> bool:
    """shard_map-route eligibility over `mesh`: LRN is per-sample, so
    sharding the batch over the 'data' axis needs no cross-device
    communication; each device runs the kernel on its local shard.
    Requires the per-shard batch to be whole and the channel tiling
    constraint on the (unchanged) per-shard channel dim."""
    from cxxnet_tpu.parallel.mesh import batch_shardable
    return (_backend_ok() and batch_shardable(mesh, x.shape[0])
            and _tile_ok(x))


def lrn_pallas_sharded(x, mesh, local_size, alpha, beta, knorm):
    """lrn_pallas over a multi-device mesh: batch dim sharded on 'data',
    channels/spatial replicated within each shard. If the operand arrives
    channel-sharded (tensor parallelism), GSPMD gathers channels first -
    the same all-gather the XLA reduce_window path would need for its
    cross-channel window.
    """
    from jax.sharding import PartitionSpec as P
    spec = P("data", *(None,) * (x.ndim - 1))
    fn = jax.shard_map(
        lambda xs: lrn_pallas(xs, local_size, alpha, beta, knorm,
                              _FORCE_INTERPRET),
        mesh=mesh, in_specs=spec, out_specs=spec,
        # pallas_call's out_shape carries no varying-mesh-axes info;
        # the per-shard computation touches no collectives, so the
        # vma check has nothing to verify anyway
        check_vma=False)
    return fn(x)
