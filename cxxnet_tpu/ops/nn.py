"""Elementwise activations, softmax and LRN.

Parity with the reference op functors (src/layer/op.h:15-101) and the LRN
layer (src/layer/lrn_layer-inl.hpp:12-93). Backward passes come from
autodiff; note jax's grads of these match the reference's
"grad-from-output" formulations (sigmoid_grad a*(1-a) etc.) analytically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def relu(x):
    return jnp.maximum(x, 0.0)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def softplus(x):
    return jax.nn.softplus(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def xelu(x, b):
    """Leaky relu variant: x > 0 ? x : x / b (op.h:50-55)."""
    return jnp.where(x > 0, x, x / b)


def mxelu(x, b):
    """Multiplicative leaky relu: x > 0 ? x : x * b (prelu_layer-inl.hpp:11-15)."""
    return jnp.where(x > 0, x, x * b)


def softmax(x):
    """Row softmax over the last dim (mshadow::Softmax equivalent)."""
    return jax.nn.softmax(x, axis=-1)


def lrn_xla(x, local_size: int, alpha: float, beta: float, knorm: float):
    """LRN in plain XLA ops (reduce_window over channels + power): the
    route of last resort and the reference the Pallas kernels are
    compared against (tests, chip_smoke.py)."""
    sq = x * x
    pad_lo = local_size // 2
    pad_hi = local_size - pad_lo - 1
    window_sum = lax.reduce_window(
        sq, 0.0, lax.add,
        window_dimensions=(1, local_size, 1, 1),
        window_strides=(1, 1, 1, 1),
        padding=((0, 0), (pad_lo, pad_hi), (0, 0), (0, 0)))
    norm = knorm + (alpha / local_size) * window_sum
    return x * jnp.power(norm, -beta)


def lrn(x, local_size: int, alpha: float, beta: float, knorm: float):
    """Cross-channel local response normalization on NCHW.

    out = x * (knorm + alpha/n * sum_{window n}(x^2)) ^ (-beta)
    (lrn_layer-inl.hpp:36-56: tmp_norm = chpool<sum>(x^2) * (alpha/n) + knorm,
    out = x * tmp_norm^(-beta)). Routed by the mesh the step runs over
    (parallel/mesh.py active_device_span) and the channel count
    (ops/pallas_lrn.py _tile_ok): the Pallas kernel on one device, its
    shard_map route over a 'data' axis, else lrn_xla.
    """
    from cxxnet_tpu.ops import pallas_lrn as pk
    # the scope says, in the step's text and in any trace, which route
    # this layer took
    if pk.use_pallas_lrn(x):
        with jax.named_scope("route.pallas"):
            return pk.lrn_pallas(x, local_size, alpha, beta, knorm,
                                 pk._FORCE_INTERPRET)
    from cxxnet_tpu.parallel.mesh import get_active_mesh
    mesh = get_active_mesh()
    if mesh is not None and mesh.devices.size > 1 \
            and pk.use_pallas_lrn_sharded(x, mesh):
        with jax.named_scope("route.sharded"):
            return pk.lrn_pallas_sharded(x, mesh, local_size, alpha, beta,
                                         knorm)
    with jax.named_scope("route.xla"):
        return lrn_xla(x, local_size, alpha, beta, knorm)
