"""Network: NetConfig DAG -> functional forward + loss.

Replaces the reference's NeuralNet (neural_net-inl.hpp:23-297). The
in-place node/gradient machinery disappears: forward is a pure function
from (params, inputs, rng) to node values, connections run in declaration
order exactly like the reference (Forward :107-132), and the training loss
is differentiated by jax.grad - which reproduces the reference's reverse
declaration-order Backprop including gradient summing at forks.

Weight sharing (kSharedLayer): a shared connection reuses the primary
layer's entry in the params pytree, so autodiff automatically sums the
gradient contributions of every connection that uses it - the behavior the
reference gets from accumulating `gwmat_ +=` across connections.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from cxxnet_tpu.layers import create_layer
from cxxnet_tpu.layers.base import Layer, Shape
from cxxnet_tpu.layers.common import SplitLayer
from cxxnet_tpu.layers.loss import LossLayer
from cxxnet_tpu.nnet.net_config import NetConfig


def param_key(cfg: NetConfig, layer_index: int) -> str:
    """Stable pytree key for a layer's params: its name, else its index."""
    info = cfg.layers[layer_index]
    return info.name if info.name else f"layer_{layer_index}"


def layer_scope(cfg: NetConfig, layer_index: int) -> str:
    """`<type>.<key>`, the scope a layer's operations are traced under.
    A shared layer takes its primary's type and its own index's key, so
    that two uses of one set of weights are two names."""
    info = cfg.layers[layer_index]
    if info.is_shared:
        info = cfg.layers[info.primary_layer_index]
    return f"{info.type_name}.{param_key(cfg, layer_index)}"


class Network:
    """Holds layer objects + inferred node shapes; provides pure forward."""

    def __init__(self, cfg: NetConfig, batch_size: int):
        self.cfg = cfg
        self.batch_size = batch_size
        self.layer_objs: List[Layer] = []
        self.node_shapes: List[Optional[Shape]] = [None] * cfg.num_nodes
        # per-layer compute-dtype plan stamped by the autocast graph
        # pass (nnet/passes.py); None = no plan, historic behavior
        # (the trainer casts wholesale to its compute dtype)
        self.dtype_plan: Optional[Dict[int, jnp.dtype]] = None
        # `remat = 1` (trainer): one `jax.checkpoint` around each layer
        # whose class says `remat_worthy` (kda, glu_ffn: the kinds whose
        # second forward buys the most memory a millisecond; gqa: the
        # kind whose maps do not fit at the length it is run at): the
        # backward keeps that layer's inputs and recomputes the rest.
        # `checkpointed` lists them
        self.remat = False

        # node 0 is the data input; in_1..in_k are extra data
        c, y, x = cfg.input_shape
        if c * y * x == 0:
            raise ValueError("input_shape must be set")
        self.node_shapes[0] = (batch_size, c, y, x)
        for i in range(cfg.extra_data_num):
            ec, ey, ex = cfg.extra_shape[3 * i: 3 * i + 3]
            self.node_shapes[i + 1] = (batch_size, ec, ey, ex)

        # build layer objects and run shape inference in declaration order
        for idx, info in enumerate(cfg.layers):
            if info.is_shared:
                layer = self.layer_objs[info.primary_layer_index]
                if cfg.layercfg[idx]:
                    # a connection with keys of its own (lm_head's
                    # `target_offset`): a copy over the same weights
                    layer = layer.for_connection(cfg.layercfg[idx])
            else:
                layer = create_layer(info.type_name, info.name)
                for k, v in cfg.defcfg:
                    layer.set_param(k, v)
                for k, v in cfg.layercfg[idx]:
                    layer.set_param(k, v)
            self.layer_objs.append(layer)

            if isinstance(layer, SplitLayer):
                layer.num_out = len(info.nindex_out)
            if isinstance(layer, LossLayer):
                if info.nindex_in != info.nindex_out:
                    raise ValueError(
                        f"{info.type_name}: loss layer must be a self-loop")
                if layer.target not in cfg.label_name_map:
                    raise ValueError(
                        f"LossLayer: unknown target={layer.target}")

            in_shapes = []
            for j in info.nindex_in:
                if self.node_shapes[j] is None:
                    raise ValueError(
                        f"node {cfg.node_names[j]} used before it is "
                        "produced")
                in_shapes.append(self.node_shapes[j])
            out_shapes = layer.infer_shapes(list(in_shapes))
            if len(out_shapes) != len(info.nindex_out):
                raise ValueError(
                    f"{info.type_name}: produced {len(out_shapes)} outputs "
                    f"for {len(info.nindex_out)} output nodes")
            for j, s in zip(info.nindex_out, out_shapes):
                self.node_shapes[j] = s

        # node 0 holds token ids where an `embed` layer reads it: the
        # trainer then stages the batch as integers (trainer._host_input)
        self.integer_input = any(
            l.type_name == "embed" and 0 in cfg.layers[i].nindex_in
            for i, l in enumerate(self.layer_objs))
        self.loss_indices = [
            i for i, l in enumerate(self.layer_objs)
            if isinstance(l, LossLayer) and not cfg.layers[i].is_shared]

    # ------------------------------------------------------------------
    def init_params(self, key: jax.Array) -> Dict[str, Dict[str, jax.Array]]:
        params: Dict[str, Dict[str, jax.Array]] = {}
        for idx, info in enumerate(self.cfg.layers):
            if info.is_shared:
                continue
            in_shapes = [self.node_shapes[j] for j in info.nindex_in]
            p = self.layer_objs[idx].init_params(
                jax.random.fold_in(key, idx), list(in_shapes))
            if p:
                params[param_key(self.cfg, idx)] = p
        return params

    def param_tags(self) -> Dict[str, Dict[str, str]]:
        """pytree of updater scoping tags parallel to init_params()."""
        tags: Dict[str, Dict[str, str]] = {}
        for idx, info in enumerate(self.cfg.layers):
            if info.is_shared:
                continue
            t = self.layer_objs[idx].param_tags()
            if t:
                tags[param_key(self.cfg, idx)] = t
        return tags

    # ------------------------------------------------------------------
    def forward(
        self,
        params: Dict[str, Dict[str, jax.Array]],
        inputs: Dict[int, jax.Array],
        *,
        train: bool,
        rng: Optional[jax.Array] = None,
        labels: Optional[Dict[str, jax.Array]] = None,
        mask: Optional[jax.Array] = None,
        taps: Optional[Dict[int, Optional[jax.Array]]] = None,
        counters: Optional[Dict[str, jax.Array]] = None,
    ) -> Tuple[List[jax.Array], jax.Array]:
        """Run all connections in declaration order.

        taps: optional {layer_index: None} dict, filled in place with
        each listed layer's (first) INPUT as that layer receives it -
        i.e. BEFORE a self-loop layer overwrites its node. The fold
        calibration (trainer._calibrate_staged) needs the batch_norm
        input, and reading `values[node]` after the forward would see
        the post-BN value for `layer[+0] = batch_norm` self-loops.

        counters: optional dict, filled in place with
        `<layer key>.<name>` -> scalar for every layer that counts
        something while it runs (`apply_with_stats`, layers/moe.py).

        inputs: node index -> array (node 0 data + extra-data nodes).
        labels: label field name -> (b, width) array; required when any
        loss layer runs with train semantics.
        mask: optional (b,) validity mask for padded short batches; the
        per-example losses of padding rows are zeroed (the functional
        replacement of AdjustBatchSize - neural_net-inl.hpp:266-277).

        Returns (node_values, total_loss) where total_loss is the sum over
        loss layers of grad_scale * sum(masked per-example loss). The
        trainer scales by 1/(batch_size*update_period) to match the
        reference's gradient scaling (loss_layer_base-inl.hpp:60-63).
        """
        cfg = self.cfg
        values: List[Optional[jax.Array]] = [None] * cfg.num_nodes
        for j, v in inputs.items():
            values[j] = v
        total_loss = jnp.zeros((), dtype=jnp.float32)

        for idx, info in enumerate(cfg.layers):
            layer = self.layer_objs[idx]
            pkey = param_key(
                cfg, info.primary_layer_index if info.is_shared else idx)
            p = params.get(pkey, {})
            xs = [values[j] for j in info.nindex_in]
            # the layer's name in the traced program: every device
            # operation of this layer carries it in its `op_name`, under
            # `jvp(...)` forward and `transpose(jvp(...))` backward
            # (docs/OBSERVABILITY.md "Reading a device trace")
            with jax.named_scope(layer_scope(cfg, idx)):
                if self.dtype_plan is not None:
                    want = self.dtype_plan.get(idx)
                    if want is not None:
                        # autocast plan (nnet/passes.py): cast this
                        # layer's inputs + params to its stamped compute
                        # dtype; f32-stamped layers under a bf16 net thus
                        # run their math in f32 (the next bf16 layer
                        # casts back down)
                        xs = [x.astype(want)
                              if jnp.issubdtype(x.dtype, jnp.floating)
                              else x for x in xs]
                        p = {k: (v.astype(want)
                                 if jnp.issubdtype(v.dtype, jnp.floating)
                                 else v) for k, v in p.items()}
                if taps is not None and idx in taps:
                    # post-cast snapshot: exactly what the layer's apply
                    # receives (the docstring's tap contract)
                    taps[idx] = xs[0]
                layer_rng = (jax.random.fold_in(rng, idx)
                             if rng is not None else None)

                if isinstance(layer, LossLayer):
                    x = xs[0]
                    b = x.shape[0]
                    flat = x.reshape(b, -1)
                    if labels is not None:
                        lbl = labels[layer.target]
                        per_ex = layer.per_example_loss(flat, lbl)
                        if mask is not None:
                            per_ex = per_ex * mask
                        total_loss = total_loss + layer.grad_scale * jnp.sum(
                            per_ex)
                    out = layer.forward_transform(flat).reshape(x.shape)
                    values[info.nindex_out[0]] = out
                    continue

                outs, aux, stats = self._run_layer(
                    layer, p, xs, train, layer_rng, mask)
                if train and aux is not None:
                    # layers with an auxiliary loss term (the MoE
                    # load-balance loss, the lm_head's token loss) fold
                    # it into the same total the loss layers accumulate
                    # (contract on Layer.has_aux, layers/base.py)
                    total_loss = total_loss + aux
                if counters is not None:
                    # under the connection's own key: two uses of one
                    # set of weights count apart
                    counters.update({f"{param_key(cfg, idx)}.{k}": v
                                     for k, v in stats.items()})
                for j, o in zip(info.nindex_out, outs):
                    values[j] = o

        return values, total_loss

    def _checkpoints(self, layer) -> bool:
        return self.remat and getattr(layer, "remat_worthy", False)

    @property
    def checkpointed(self) -> List[str]:
        """The scopes (`<type>.<key>`, declaration order) of the layers
        a training forward runs under a checkpoint; empty without
        `remat`."""
        return [layer_scope(self.cfg, i)
                for i, layer in enumerate(self.layer_objs)
                if self._checkpoints(layer)]

    def _run_layer(self, layer, p, xs, train, rng, mask):
        """(outputs, aux loss term or None, counters) of one layer,
        under one checkpoint where `remat` asks and the layer is worth
        it."""
        def run(p, xs):
            if hasattr(layer, "apply_with_stats"):
                return layer.apply_with_stats(p, xs, train=train, rng=rng,
                                              mask=mask)
            if layer.has_aux:
                return layer.apply_with_aux(p, xs, train=train, rng=rng,
                                            mask=mask) + ({},)
            return layer.apply(p, xs, train=train, rng=rng), None, {}

        if train and self._checkpoints(layer):
            run = jax.checkpoint(run)
        return run(p, xs)

    # ------------------------------------------------------------------
    def node_index(self, name: str) -> int:
        """Resolve a node reference: name, or `top[-k]` counting from the
        last node (ExtractFeature syntax, nnet_impl-inl.hpp:200-223)."""
        if name.startswith("top[-") and name.endswith("]"):
            k = int(name[5:-1])
            return self.cfg.num_nodes - k
        if name in self.cfg.node_name_map:
            return self.cfg.node_name_map[name]
        raise KeyError(f"unknown node name {name}")
