"""PairTest: differential testing of layer implementations.

Parity with the reference's pairtest harness (pairtest_layer-inl.hpp:15-203;
type encoding layer.h:314-315,354-358): `layer[...] = pairtest-A-B` runs a
master implementation A and a slave implementation B of the same logical op
side by side on identical inputs and parameters, and reports relative errors
above a tolerance (reference threshold 1e-5) for forward outputs. Because
backprop here is autodiff, gradient comparison (the reference's
input-gradient and weight-gradient checks, Cmp/CmpResult :160-198) is done
eagerly by :func:`run_pairtest`, which differentiates through both
implementations and returns all max relative errors.

The module also registers `conv_im2col`, an im2col-GEMM convolution — the
reference's own conv algorithm (convolution_layer-inl.hpp:70-106) — which
serves as the trusted slave for the production `lax.conv` path, the same
role the plain template conv played for the cudnn path.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from cxxnet_tpu.layers.base import (
    Layer, Params, Shape, create_layer, register_layer)
from cxxnet_tpu.layers.common import ConvolutionLayer


@register_layer
class ConvIm2ColLayer(ConvolutionLayer):
    """Grouped conv via explicit im2col + GEMM (the reference algorithm:
    unpack_patch2col → per-group dot — convolution_layer-inl.hpp:70-106).

    Numerically the same op as `conv`; exists as the differential-test
    slave (`pairtest-conv-conv_im2col`) and as an MXU-friendly
    demonstration that the patch+matmul formulation also lowers to HLO.
    """

    type_name = "conv_im2col"

    def apply(self, params, inputs, *, train, rng=None):
        p = self.param
        x = inputs[0]
        w = params["wmat"]
        ky, kx, s = p.kernel_height, p.kernel_width, p.stride
        g = p.num_group
        out_ch = p.num_channel
        ipg = x.shape[1] // g
        # (b, c*ky*kx, oh, ow), flattened channel-major: c outer, ky, kx
        col = lax.conv_general_dilated_patches(
            x, filter_shape=(ky, kx), window_strides=(s, s),
            padding=((p.pad_y, p.pad_y), (p.pad_x, p.pad_x)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        b, _, oh, ow = col.shape
        col = col.reshape(b, g, ipg * ky * kx, oh * ow)
        wg = w.reshape(g, out_ch // g, ipg * ky * kx)
        out = jnp.einsum("goi,bgix->bgox", wg, col)
        out = out.reshape(b, out_ch, oh, ow)
        if "bias" in params:
            out = out + params["bias"][None, :, None, None]
        return [out]


def _max_rel_err(a: jax.Array, b: jax.Array,
                 eps: float = 1e-6) -> jax.Array:
    """Max abs difference relative to the reference tensor's scale — the
    robust form of the reference's Cmp relative-error metric
    (pairtest_layer-inl.hpp:160-180; elementwise |a-b|/|b| blows up on
    near-zero elements, so normalize by max|b| instead)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + eps)


class PairTestLayer(Layer):
    """Runs master and slave on the same inputs/params and forwards the
    MASTER's outputs (pairtest_layer-inl.hpp:61-78).

    With `pairtest_print = 1` it additionally emits an in-step warning
    (jax.debug.print) when forward outputs diverge beyond tol. This is
    off by default: debug.print is a host callback inside the jitted
    step. The full check set including gradients is
    :func:`run_pairtest`, which is eager."""

    type_name = "pairtest"

    def __init__(self, master_type: str, slave_type: str, name: str = ""):
        super().__init__(name)
        self.master = create_layer(master_type, name)
        self.slave = create_layer(slave_type, name)
        self.tol = 1e-5  # reference threshold (pairtest_layer-inl.hpp:168)
        self.print_divergence = False

    # `master:key` / `slave:key` routing (pairtest_layer-inl.hpp:128-137);
    # unprefixed keys go to both.
    def set_param(self, name: str, val: str) -> None:
        if name == "pairtest_tol":
            self.tol = float(val)
            return
        if name == "pairtest_print":
            self.print_divergence = bool(int(val))
            return
        if name.startswith("master:"):
            self.master.set_param(name[len("master:"):], val)
        elif name.startswith("slave:"):
            self.slave.set_param(name[len("slave:"):], val)
        else:
            self.master.set_param(name, val)
            self.slave.set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape]) -> List[Shape]:
        m = self.master.infer_shapes(list(in_shapes))
        s = self.slave.infer_shapes(list(in_shapes))
        if m != s:
            raise ValueError(
                f"pairtest: master/slave shape mismatch {m} vs {s}")
        return m

    def init_params(self, key: jax.Array, in_shapes: List[Shape]) -> Params:
        # one param set, mirrored into both (SyncWeight role,
        # pairtest_layer-inl.hpp:84-101)
        mp = self.master.init_params(key, list(in_shapes))
        sp = self.slave.init_params(key, list(in_shapes))
        if jax.tree.structure(mp) != jax.tree.structure(sp):
            raise ValueError("pairtest: master/slave param mismatch")
        return mp

    def param_tags(self) -> Dict[str, str]:
        return self.master.param_tags()

    def model_shard_dims(self) -> Dict[str, int]:
        return self.master.model_shard_dims()

    def apply(self, params, inputs, *, train, rng=None):
        m_out = self.master.apply(params, inputs, train=train, rng=rng)
        s_out = self.slave.apply(params, inputs, train=train, rng=rng)
        if self.print_divergence:
            for i, (a, b) in enumerate(zip(m_out, s_out)):
                err = _max_rel_err(a, b)
                jax.lax.cond(
                    err > self.tol,
                    lambda e: jax.debug.print(
                        "PairTest[" + self.name + "] out[" + str(i) +
                        "] max rel err {e}", e=e),
                    lambda e: None,
                    err)
        return m_out


def run_pairtest(layer: PairTestLayer, in_shapes: List[Shape],
                 key: Optional[jax.Array] = None,
                 train: bool = True) -> Dict[str, float]:
    """Eager differential test: forward + input-grad + weight-grad max
    relative errors between master and slave (the full check set of
    pairtest_layer-inl.hpp:61-126).

    Returns {"out[i]": err, "in_grad[i]": err, "wgrad/<name>": err}.

    Runs under jax.default_matmul_precision("highest"): on TPU the MXU
    defaults to bfloat16 inputs, and two algorithms rounding differently
    at bf16 would report ~1e-3 divergence that says nothing about either
    implementation's correctness.
    """
    with jax.default_matmul_precision("highest"):
        return _run_pairtest(layer, in_shapes, key, train)


def _run_pairtest(layer: PairTestLayer, in_shapes: List[Shape],
                  key: Optional[jax.Array], train: bool) -> Dict[str, float]:
    if key is None:
        key = jax.random.PRNGKey(0)
    k_param, k_data, k_rng = jax.random.split(key, 3)
    layer.infer_shapes(list(in_shapes))
    params = layer.init_params(k_param, list(in_shapes))
    xs = [jax.random.normal(jax.random.fold_in(k_data, i), s,
                            dtype=jnp.float32)
          for i, s in enumerate(in_shapes)]
    rng = k_rng

    def scalar(impl, params, xs):
        outs = impl.apply(params, xs, train=train, rng=rng)
        return sum(jnp.sum(o * (i + 1.0)) for i, o in enumerate(outs)), outs

    report: Dict[str, float] = {}
    (_, m_out), m_grads = jax.value_and_grad(
        lambda p, x: scalar(layer.master, p, x), argnums=(0, 1),
        has_aux=True)(params, xs)
    (_, s_out), s_grads = jax.value_and_grad(
        lambda p, x: scalar(layer.slave, p, x), argnums=(0, 1),
        has_aux=True)(params, xs)

    for i, (a, b) in enumerate(zip(m_out, s_out)):
        report[f"out[{i}]"] = float(_max_rel_err(a, b))
    for i, (a, b) in enumerate(zip(m_grads[1], s_grads[1])):
        report[f"in_grad[{i}]"] = float(_max_rel_err(a, b))
    flat_m = jax.tree_util.tree_flatten_with_path(m_grads[0])[0]
    flat_s = jax.tree.leaves(s_grads[0])
    for (path, a), b in zip(flat_m, flat_s):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        report[f"wgrad/{name}"] = float(_max_rel_err(a, b))
    return report
