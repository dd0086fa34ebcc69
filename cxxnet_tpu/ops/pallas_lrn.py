"""Fused Pallas TPU kernel for cross-channel LRN (fwd + analytic bwd).

One pass over HBM a direction: square -> channel-window sum -> ^-beta
-> scale in VMEM (the role cudnn fast paths play in the reference -
cudnn_convolution_layer-inl.hpp:13-171), with the analytic backward of
lrn_layer-inl.hpp:59-77 as a second kernel under custom_vjp:

    norm_c  = knorm + alpha/n * sum_{j in win(c)} x_j^2
    out_c   = x_c * norm_c^-beta
    gin_c   = g_c * norm_c^-beta
              - (2 alpha beta / n) * x_c * rsum_c
    rsum_c  = sum_{j : c in win(j)} g_j * x_j * norm_j^(-beta-1)

win(c) = [c-lo, c+hi] with lo = n//2, hi = n-lo-1 (the reference chpool
convention); the backward sum runs over the reversed window [c-hi, c+lo].

Layout (`_plan`). Channels sit on sublanes, whole in every block. What
sits on lanes follows the batch:

- a batch that is a multiple of 128 goes on lanes, `(h*w, c, b)`. That
  is the order in which the convs and pools around an LRN layer keep a
  training batch (batch minor, channels next, positions above: the
  compiled AlexNet step, PERF.md section 5), so the reshape and
  transpose outside the `pallas_call` are bitcasts and no copy stands
  around it;
- any other batch (Server buckets, `task = pred` on a few rows) leaves
  the positions on lanes, `(b, c, h*w)`: NCHW's own order.

Blocks are `(outer, c, lanes)` of about `_BLOCK_BYTES` an operand on a
two-dimensional grid; the body walks a block in chunks of `(c, 128)`,
so a block's float32 arithmetic stays in vregs and is not kept in VMEM.
A channel shift is a circular roll (XLU) of the chunk extended by one
tile of zeros, which brings zeros in at both ends without a mask. The
arithmetic an element sees is the same in either order, whatever the
batch. Float32 inside, the operand's dtype in and out.

`norm^-beta` is `exp(-beta * log(norm))` where the layer's constants
make `norm` positive (`knorm > 0`, `alpha >= 0`), and the backward's
`norm^(-beta-1)` a second `exp` on the same `log` (a float32 divide is
a reciprocal refined on the VPU, which bounds the kernel; the exp is
the EUP's: PERF.md section 6, PR 28); otherwise `jnp.power`, which
knows the sign, zero and infinity cases.

Falls back to ops.nn.lrn_xla off the TPU, and where the channel count
is no multiple of the dtype's sublane tile or too large for a chunk
(`_tile_ok`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_BYTES = 1 << 20      # of one operand's block
_MAX_LANE_TILE = 2048       # lanes of one block
_MAX_CHANNELS = 1024        # a (c, 128) float32 chunk is c / 8 vregs


def _round_up(a: int, m: int) -> int:
    return -(-a // m) * m


def _window_sum(a: jax.Array, up: int, down: int) -> jax.Array:
    """sum_{j = c-down}^{c+up} a[j] along axis 0, zeros beyond the ends.
    `a` is extended by whole sublane tiles of zeros, so a circular roll
    by at most that many rows shifts zeros in at either end."""
    reach = max(up, down)
    if reach == 0:
        return a
    c, lanes = a.shape
    ext = jnp.concatenate(
        [a, jnp.zeros((_round_up(reach, 8), lanes), a.dtype)], axis=0)
    s = ext
    for d in range(1, down + 1):        # ext[c-d]
        s = s + pltpu.roll(ext, d, 0)
    for d in range(1, up + 1):          # ext[c+d]
        s = s + pltpu.roll(ext, ext.shape[0] - d, 0)
    return s[:c]


def _powers(norm, exponents, positive):
    """norm^e for each e. Of a positive norm, exp(e * log(norm)) on one
    log: the EUP's work, where `jnp.power` adds its sign, zero and
    infinity cases as selects on the VPU, the unit that bounds these
    kernels."""
    if positive:
        log = jnp.log(norm)
        return [jnp.exp(e * log) for e in exponents]
    return [jnp.power(norm, e) for e in exponents]


def _fwd_math(x, *, n, alpha, beta, knorm):
    lo, hi = n // 2, n - n // 2 - 1
    # norm_c sums x_j^2 over the window j in [c-lo, c+hi]
    norm = knorm + (alpha / n) * _window_sum(x * x, hi, lo)
    scale, = _powers(norm, [-beta], knorm > 0 and alpha >= 0)
    return x * scale


def _bwd_math(x, g, *, n, alpha, beta, knorm):
    lo, hi = n // 2, n - n // 2 - 1
    norm = knorm + (alpha / n) * _window_sum(x * x, hi, lo)
    p, q = _powers(norm, [-beta, -beta - 1.0], knorm > 0 and alpha >= 0)
    # reversed window [c-hi, c+lo]
    rsum = _window_sum(g * x * q, lo, hi)
    return g * p - (2.0 * alpha * beta / n) * x * rsum


def _kernel(*refs, math, lane_chunk):
    """Walk the `(outer, c, lanes)` block in `(c, lane_chunk)` chunks:
    load, float32 math, store."""
    *ins, out = refs
    outer, _, lanes = out.shape
    per = lanes // lane_chunk

    def body(i, carry):
        if per == 1:
            at = (i, slice(None), slice(None))
        else:
            at = (i // per, slice(None), pl.ds(
                pl.multiple_of(i % per * lane_chunk, lane_chunk),
                lane_chunk))
        out[at] = math(*(r[at].astype(jnp.float32) for r in ins)
                       ).astype(out.dtype)
        return carry

    lax.fori_loop(0, outer * per, body, 0)


class _Plan(NamedTuple):
    perm: Tuple[int, int, int]      # (b, c, h*w) -> (outer, c, lanes)
    block: Tuple[int, int, int]
    lane_chunk: int


def _plan(shape, dtype) -> _Plan:
    b, c, h, w = shape
    batch_on_lanes = b % _LANES == 0
    outer, lanes = (h * w, b) if batch_on_lanes else (b, h * w)
    item = jnp.dtype(dtype).itemsize
    if lanes < _LANES:
        tile = chunk = lanes            # the whole, ragged dimension
    else:
        tile = min(_round_up(lanes, _LANES), _MAX_LANE_TILE,
                   max(_LANES, _BLOCK_BYTES // (c * item) // _LANES * _LANES))
        chunk = _LANES
    per_outer = c * tile * item
    return _Plan((2, 1, 0) if batch_on_lanes else (0, 1, 2),
                 (min(outer, max(1, _BLOCK_BYTES // per_outer)), c, tile),
                 chunk)


def _call(math, name, args, x, interpret):
    b, c, h, w = x.shape
    plan = _plan(x.shape, x.dtype)
    # h*w merged first: a transpose next to a conv would be folded into
    # it, and the conv would carry this layer's name in the step's text
    views = [a.reshape(b, c, h * w).transpose(plan.perm) for a in args]
    spec = pl.BlockSpec(plan.block, lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, math=math, lane_chunk=plan.lane_chunk),
        out_shape=jax.ShapeDtypeStruct(views[0].shape, x.dtype),
        grid=(pl.cdiv(views[0].shape[0], plan.block[0]),
              pl.cdiv(views[0].shape[2], plan.block[2])),
        in_specs=[spec] * len(views),
        out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        # stable name: trace reductions and chip_smoke.py find the
        # kernel in the step by it
        name=name,
    )(*views)
    # either order is its own inverse
    return out.transpose(plan.perm).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_pallas(x, local_size, alpha, beta, knorm, interpret=False):
    """Fused LRN; numerically identical to ops.nn.lrn (tested to 1e-5)."""
    math = functools.partial(_fwd_math, n=local_size, alpha=alpha,
                             beta=beta, knorm=knorm)
    return _call(math, "lrn_fwd", [x], x, interpret)


def _vjp_fwd(x, local_size, alpha, beta, knorm, interpret=False):
    return lrn_pallas(x, local_size, alpha, beta, knorm, interpret), x


def _vjp_bwd(local_size, alpha, beta, knorm, interpret, x, g):
    math = functools.partial(_bwd_math, n=local_size, alpha=alpha,
                             beta=beta, knorm=knorm)
    return (_call(math, "lrn_bwd", [x, g], x, interpret),)


lrn_pallas.defvjp(_vjp_fwd, _vjp_bwd)


def _tile_ok(x) -> bool:
    """Whether the chunks of `_kernel` suit an operand of this `shape`
    and `dtype`: whole sublane tiles of channels, few enough for a
    `(c, 128)` float32 chunk and its temporaries."""
    c = x.shape[1]
    sub = 16 if x.dtype == jnp.bfloat16 else 8
    return c % sub == 0 and c <= _MAX_CHANNELS


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
