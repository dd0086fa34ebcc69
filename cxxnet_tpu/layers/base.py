"""Layer system core: LayerParam, the Layer protocol, and the type registry.

Design mapping from the reference (SURVEY.md par.1 critical idea #1):
a reference `ILayer` is a stateful object with Forward/Backprop and owned
weights; here a Layer is a *pure function bundle*:

    layer.infer_shapes(in_shapes)          shape inference (InitConnection)
    layer.init_params(key, in_shapes)      weight init      (InitModel)
    layer.apply(params, inputs, train, rng) forward          (Forward)

Backprop does not exist: the trainer differentiates through apply. The
Node/Connection split survives at the net level: a layer holds no per-node
state, so one layer's params can serve several connections (weight sharing,
kSharedLayer - layer.h:283-284).

Shapes are full NCHW tuples (batch, channel, y, x); "matrix" nodes are
(batch, 1, 1, n) exactly like the reference Node convention (layer.h:33-54).
"""

from __future__ import annotations

import copy
import math
from typing import Dict, List, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp

Shape = Tuple[int, int, int, int]
Params = Dict[str, jax.Array]


def is_mat(shape: Sequence[int]) -> bool:
    """A node is a matrix when channel and y dims are 1 (layer.h:48-54)."""
    return shape[1] == 1 and shape[2] == 1


class LayerParam:
    """Common layer hyperparameters (src/layer/param.h:15-111)."""

    def __init__(self) -> None:
        self.init_sigma = 0.01
        self.init_uniform = -1.0
        self.init_sparse = 10
        self.init_bias = 0.0
        self.random_type = 0  # 0 gaussian, 1 uniform/xavier, 2 kaiming
        self.num_hidden = 0
        self.num_channel = 0
        self.num_group = 1
        self.kernel_width = 0
        self.kernel_height = 0
        self.stride = 1
        self.pad_x = 0
        self.pad_y = 0
        self.no_bias = 0
        self.silent = 0
        self.num_input_channel = 0
        self.num_input_node = 0
        # per-layer compute-dtype pin consumed by the autocast graph
        # pass (nnet/passes.py): overrides the policy for this layer
        # ("" = follow the policy). Stored here so the config schema
        # registry harvests the key.
        self.layer_dtype = ""
        # per-layer quantization pin consumed by the quantize_int8
        # graph pass (nnet/passes.py): "float" excludes the layer,
        # "int8" documents the default policy choice, "" follows the
        # policy. Stored here so the schema registry harvests the key.
        self.layer_quant = ""

    def set_param(self, name: str, val: str) -> None:
        if name == "init_sigma":
            self.init_sigma = float(val)
        if name == "init_uniform":
            self.init_uniform = float(val)
        if name == "init_bias":
            self.init_bias = float(val)
        if name == "init_sparse":
            self.init_sparse = int(val)
        if name == "random_type":
            if val == "gaussian":
                self.random_type = 0
            elif val in ("uniform", "xavier"):
                self.random_type = 1
            elif val == "kaiming":
                self.random_type = 2
            else:
                raise ValueError(f"invalid random_type {val}")
        if name == "nhidden":
            self.num_hidden = int(val)
        if name == "nchannel":
            self.num_channel = int(val)
        if name == "ngroup":
            self.num_group = int(val)
        if name == "kernel_size":
            self.kernel_width = self.kernel_height = int(val)
        if name == "kernel_height":
            self.kernel_height = int(val)
        if name == "kernel_width":
            self.kernel_width = int(val)
        if name == "stride":
            self.stride = int(val)
        if name == "pad":
            self.pad_y = self.pad_x = int(val)
        if name == "pad_y":
            self.pad_y = int(val)
        if name == "pad_x":
            self.pad_x = int(val)
        if name == "no_bias":
            self.no_bias = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "layer_dtype":
            if val not in ("", "float32", "bfloat16"):
                raise ValueError(
                    f"layer_dtype must be float32 or bfloat16, "
                    f"got {val!r}")
            self.layer_dtype = val
        if name == "layer_quant":
            if val not in ("", "int8", "float"):
                raise ValueError(
                    f"layer_quant must be int8 or float, got {val!r}")
            self.layer_quant = val

    def rand_init_weight(self, key: jax.Array, shape: Sequence[int],
                         in_num: int, out_num: int) -> jax.Array:
        """Weight init parity with RandInitWeight (param.h:113-138)."""
        shape = tuple(shape)
        if self.random_type == 0:
            return self.init_sigma * jax.random.normal(key, shape,
                                                       dtype=jnp.float32)
        if self.random_type == 1:
            a = math.sqrt(3.0 / (in_num + out_num))
            if self.init_uniform > 0:
                a = self.init_uniform
            return jax.random.uniform(key, shape, minval=-a, maxval=a,
                                      dtype=jnp.float32)
        if self.random_type == 2:
            if self.num_hidden > 0:
                sigma = math.sqrt(2.0 / self.num_hidden)
            else:
                sigma = math.sqrt(
                    2.0 / (self.num_channel * self.kernel_width
                           * self.kernel_height))
            return sigma * jax.random.normal(key, shape, dtype=jnp.float32)
        raise ValueError(f"invalid random_type {self.random_type}")


class Layer:
    """Base layer: stateless transform with optional trainable params."""

    type_name: str = ""

    def __init__(self, name: str = ""):
        self.name = name
        self.param = LayerParam()

    #: keys a shared connection (`share[tag]`) may set for itself: it
    #: then runs a copy of its primary with those keys set, over the
    #: primary's weights (nnet/net_config.py); none for most types
    connection_keys: Tuple[str, ...] = ()

    # --- configuration ---------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        self.param.set_param(name, val)

    def for_connection(self, pairs: List[Tuple[str, str]]) -> "Layer":
        """A copy of this layer with a shared connection's own keys
        set; the weights stay the primary's."""
        bad = [k for k, _ in pairs if k not in self.connection_keys]
        if bad:
            raise ValueError(f"a shared {self.type_name} connection may "
                             f"set only {self.connection_keys}, not {bad}")
        twin = copy.copy(self)
        twin.param = copy.copy(self.param)
        for k, v in pairs:
            twin.set_param(k, v)
        return twin

    # --- structure -------------------------------------------------------
    def infer_shapes(self, in_shapes: List[Shape]) -> List[Shape]:
        raise NotImplementedError

    def init_params(self, key: jax.Array,
                    in_shapes: List[Shape]) -> Params:
        """Return the layer's trainable params ({} when it has none)."""
        return {}

    def param_tags(self) -> Dict[str, str]:
        """Updater scoping tag per param, mirroring ApplyVisitor names
        (e.g. fullc: wmat->'wmat', bias->'bias'; prelu slope->'bias')."""
        return {}

    def model_shard_dims(self) -> Dict[str, int]:
        """Tensor-parallel rule: param name -> dim sharded over the
        'model' mesh axis (parallel/sharding.py). {} = replicate all."""
        return {}

    def expert_shard_dims(self) -> Dict[str, int]:
        """Expert-parallel rule: param name -> dim sharded over the
        'expert' mesh axis (layers/moe.py). {} = replicate all."""
        return {}

    def pipe_shard_dims(self) -> Dict[str, int]:
        """Pipeline-parallel rule: param name -> dim sharded over the
        'pipe' mesh axis (layers/transformer_stack.py). {} = replicate
        all."""
        return {}

    # --- compute ---------------------------------------------------------
    def apply(self, params: Params, inputs: List[jax.Array], *,
              train: bool, rng: Optional[jax.Array] = None,
              ) -> List[jax.Array]:
        raise NotImplementedError

    #: layers contributing an auxiliary loss term (e.g. MoE load
    #: balancing) set this True and implement
    #:   apply_with_aux(params, inputs, *, train, rng=None, mask=None)
    #:     -> (outputs, aux_scalar)
    #: Network.forward adds aux_scalar into the same total the loss
    #: layers accumulate (scaled 1/(batch*update_period) by the
    #: trainer); `mask` is the (b,) padded-batch validity mask and must
    #: exclude padding rows from any statistics the aux term uses.
    has_aux: bool = False

    # --- checkpoint helpers ----------------------------------------------
    def check_one_to_one(self, in_shapes: List[Shape]) -> None:
        if len(in_shapes) != 1:
            raise ValueError(
                f"{self.type_name}: layer only supports 1-1 connection")


# ---------------------------------------------------------------------------
# ambient training-step binding
# ---------------------------------------------------------------------------

_ACTIVE_STEP: List[Optional[jax.Array]] = [None]


class active_step:
    """Context binding 'the (traced) update counter this forward runs
    at' so layers whose behavior is a function of training progress
    (insanity's per-forward anneal, insanity_layer-inl.hpp:52-63) can
    read it without threading a step argument through every
    Layer.apply. The trainer enters it around net.forward inside the
    traced train step (same pattern as parallel.mesh.active_mesh)."""

    def __init__(self, step: Optional[jax.Array]):
        self.step = step

    def __enter__(self):
        _ACTIVE_STEP.append(self.step)
        return self.step

    def __exit__(self, *exc):
        _ACTIVE_STEP.pop()
        return False


def get_active_step() -> Optional[jax.Array]:
    return _ACTIVE_STEP[-1]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

LAYER_REGISTRY: Dict[str, Type[Layer]] = {}

# layer types that are self-loops converting activations to gradients
LOSS_TYPES = ("softmax", "l2_loss", "multi_logistic")


def register_layer(cls: Type[Layer]) -> Type[Layer]:
    assert cls.type_name, "layer class must define type_name"
    LAYER_REGISTRY[cls.type_name] = cls
    return cls


def create_layer(type_name: str, name: str = "") -> Layer:
    """Factory: config layer type string -> Layer instance.

    Mirrors GetLayerType (layer.h:322-361) + CreateLayer_
    (layer_impl-inl.hpp:36-76). `share[...]` is handled by the net config;
    `pairtest-A-B` builds a differential-testing wrapper (layer.h:354-358).
    """
    if type_name.startswith("pairtest-"):
        from cxxnet_tpu.layers.pairtest import PairTestLayer
        parts = type_name.split("-", 2)
        if len(parts) != 3 or not parts[1] or not parts[2]:
            raise ValueError(
                f'unknown layer type: "{type_name}" '
                "(pairtest syntax is pairtest-<master>-<slave>)")
        return PairTestLayer(parts[1], parts[2], name)
    if type_name == "torch":
        # plugin layers register on first use (the analog of the
        # reference's compile-time CXXNET_USE_CAFFE_ADAPTOR gate)
        import cxxnet_tpu.plugin.torch_adapter  # noqa: F401
    if type_name not in LAYER_REGISTRY:
        raise ValueError(f'unknown layer type: "{type_name}"')
    return LAYER_REGISTRY[type_name](name)


def known_layer_types() -> List[str]:
    return sorted(LAYER_REGISTRY)
