"""Per-parameter sharding rules: tensor parallelism over the 'model' axis.

The reference has no tensor parallelism (SURVEY.md par.2.7 - every device
holds a full replica); this module is the TPU-native extension that makes
`mesh = data:8,model:4` meaningful. The design follows the GSPMD recipe:
annotate *parameter* shardings only, and let XLA propagate activation
shardings and insert the collectives (all-gather on the fullc output
feature dim, reduce-scatter/all-reduce on contractions) over ICI.

Rules (each layer declares which dim of each param rides 'model' via
`Layer.model_shard_dims()`):
- fullc wmat (nhidden, nin): shard nhidden (Megatron column-parallel);
  bias (nhidden,) likewise. The following layer's contraction makes XLA
  all-gather or keep the sharding, whichever its cost model prefers.
- conv wmat OIHW: shard O (out channels); bias likewise. Channel-wise
  params downstream of a sharded conv (prelu slope, batch-norm
  slope/bias) shard the same dim so no resharding is needed.
- Any param whose shard dim is not divisible by the model-axis size is
  replicated (falling back is always legal - GSPMD handles mixtures).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cxxnet_tpu.nnet.network import Network, param_key

MODEL_AXIS = "model"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"
DATA_AXIS = "data"


def shard_map_manual(fn, mesh: Mesh, manual_axes, in_specs, out_specs):
    """shard_map manual over `manual_axes`, every OTHER mesh axis left
    to GSPMD (auto), value replication unchecked (the zero region's
    in/out specs assert the layouts the trainer compiles against; a
    varying-axes check would reject the deliberately-unreduced
    gradients)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=set(manual_axes), check_vma=False)


def param_pspecs(net: Network, shapes=None) -> Dict[str, Dict[str, P]]:
    """PartitionSpec per parameter; P() (replicated) unless the layer
    declares a model- and/or expert-shard dim. A param may ride both
    axes on different dims (none of the shipped layers do, but the
    combination is legal GSPMD)."""
    if shapes is None:
        shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    specs: Dict[str, Dict[str, P]] = {}
    for idx, info in enumerate(net.cfg.layers):
        if info.is_shared:
            continue
        lk = param_key(net.cfg, idx)
        if lk not in shapes:
            continue
        layer = net.layer_objs[idx]
        by_axis = ((MODEL_AXIS, layer.model_shard_dims()),
                   (EXPERT_AXIS, layer.expert_shard_dims()),
                   (PIPE_AXIS, layer.pipe_shard_dims()))
        specs[lk] = {}
        for pn, sd in shapes[lk].items():
            spec = [None] * len(sd.shape)
            for axis, dims in by_axis:
                d = dims.get(pn)
                if d is not None and spec[d] is None:
                    spec[d] = axis
            specs[lk][pn] = P(*spec) if any(spec) else P()
    return specs


def zero1_eligible_dim(spec, shape, dsize):
    """Index of the first still-unsharded dim divisible by the
    data-axis size - the dim zero1_shardings additionally shards over
    'data' - or None when the weight keeps its parameter sharding.
    THE eligibility rule; the multichip dryrun asserts against it."""
    full = list(spec) + [None] * (len(shape) - len(spec))
    for i, (ax, dim) in enumerate(zip(full, shape)):
        if ax is None and dim % dsize == 0:
            return i
    return None


def zero_partition_dims(
        mesh: Mesh, net: Network,
        pshard: Dict[str, Dict[str, NamedSharding]],
        shapes=None,
) -> Dict[str, Dict[str, Optional[int]]]:
    """zero1_eligible_dim per parameter: the dim each ZeRO stage cuts
    over 'data' (None = ineligible, the weight stays at its parameter
    sharding). One tree drives all three stages so optimizer state
    (stage 1), gradients/accumulator (stage 2) and parameters between
    steps (stage 3) always agree on the cut. `shapes` (an init_params
    eval_shape tree) may be passed to avoid re-tracing - the abstract
    init trace scales with the model, and ZeRO targets big models."""
    dsize = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        DATA_AXIS, 1)
    if shapes is None:
        shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    out: Dict[str, Dict[str, Optional[int]]] = {}
    for lk, d in pshard.items():
        out[lk] = {}
        for pn, ns in d.items():
            if dsize <= 1:
                out[lk][pn] = None
                continue
            out[lk][pn] = zero1_eligible_dim(
                ns.spec, shapes[lk][pn].shape, dsize)
    return out


def _zero_shard_tree(
        mesh: Mesh, net: Network,
        pshard: Dict[str, Dict[str, NamedSharding]],
        shapes=None, dims=None,
) -> Dict[str, Dict[str, NamedSharding]]:
    """Parameter shardings with the eligible dim additionally riding
    'data' (ineligible weights keep their parameter sharding)."""
    if shapes is None:
        shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    if dims is None:
        dims = zero_partition_dims(mesh, net, pshard, shapes)
    out: Dict[str, Dict[str, NamedSharding]] = {}
    for lk, d in pshard.items():
        out[lk] = {}
        for pn, ns in d.items():
            i = dims[lk][pn]
            if i is None:
                out[lk][pn] = ns
                continue
            shape = shapes[lk][pn].shape
            spec = list(ns.spec) + [None] * (len(shape) - len(ns.spec))
            spec[i] = DATA_AXIS
            out[lk][pn] = NamedSharding(mesh, P(*spec))
    return out


def zero1_shardings(
        mesh: Mesh, net: Network,
        pshard: Dict[str, Dict[str, NamedSharding]],
        shapes=None, dims=None,
) -> Dict[str, Dict[str, NamedSharding]]:
    """ZeRO-1-style optimizer-state shardings: the update_on_server
    analog (nnet_ps_server.cpp:20-170 moves the updater to the server so
    workers don't replicate its state; here the state is sharded over
    the 'data' axis and GSPMD partitions the update math + all-gathers
    the fresh weights).

    Starting from each weight's parameter sharding, the first
    still-unsharded dim divisible by the data-axis size additionally
    rides 'data'. Weights with no such dim keep the parameter sharding
    (replication over data is always legal).
    """
    return _zero_shard_tree(mesh, net, pshard, shapes, dims)


def zero2_shardings(
        mesh: Mesh, net: Network,
        pshard: Dict[str, Dict[str, NamedSharding]],
        shapes=None, dims=None,
) -> Dict[str, Dict[str, NamedSharding]]:
    """ZeRO-2 gradient/accumulator shardings (arXiv:2004.13336 the rest
    of the way): the same per-weight cut as the stage-1 optimizer state,
    so the reduce-scattered gradient lands exactly on the shard its
    updater state lives on and the update math needs no resharding. The
    trainer stores the update_period>1 accumulator in this layout too
    (peak gradient HBM / data-axis size between microsteps)."""
    return _zero_shard_tree(mesh, net, pshard, shapes, dims)


def zero3_shardings(
        mesh: Mesh, net: Network,
        pshard: Dict[str, Dict[str, NamedSharding]],
        shapes=None, dims=None,
) -> Dict[str, Dict[str, NamedSharding]]:
    """ZeRO-3 parameter shardings BETWEEN steps: same cut again, now
    applied to the weights themselves - each device keeps only its
    shard and the forward all-gathers a weight just in time for its
    layer (trainer's zero region). Checkpoints still store full
    tensors (gather-on-save / reshard-on-load, nnet/checkpoint.py)."""
    return _zero_shard_tree(mesh, net, pshard, shapes, dims)


def zero_region_specs(
        mesh: Mesh, net: Network,
        pshard: Dict[str, Dict[str, NamedSharding]],
        shapes=None, dims=None,
) -> Tuple[Dict[str, Dict[str, P]], Dict[str, Dict[str, P]]]:
    """(scatter_specs, gather_specs) for the trainer's manual-'data'
    fwd/bwd region (shard_map with every other mesh axis auto): per
    weight, the PartitionSpec naming ONLY the 'data' placement of its
    zero cut. scatter_specs describe the psum_scatter'd gradient
    outputs (and the stage-3 parameter inputs); gather_specs are P()
    everywhere - the full-weight view the per-layer all_gather
    restores (auto axes must not be named in manual specs, so the
    tensor-parallel 'model' placement rides along via GSPMD)."""
    if shapes is None:
        shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    if dims is None:
        dims = zero_partition_dims(mesh, net, pshard, shapes)
    scatter: Dict[str, Dict[str, P]] = {}
    gather: Dict[str, Dict[str, P]] = {}
    for lk, d in dims.items():
        scatter[lk], gather[lk] = {}, {}
        for pn, i in d.items():
            gather[lk][pn] = P()
            if i is None:
                scatter[lk][pn] = P()
                continue
            spec = [None] * len(shapes[lk][pn].shape)
            spec[i] = DATA_AXIS
            scatter[lk][pn] = P(*spec)
    return scatter, gather


def shardings_for(mesh: Mesh,
                  net: Network) -> Dict[str, Dict[str, NamedSharding]]:
    """NamedSharding tree parallel to the params pytree (two levels).

    Each declared axis ('model', 'expert') is dropped back to
    replication independently when it is absent from the mesh, has size
    1, or the sharded dim does not divide its size.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    pspecs = param_pspecs(net, shapes)
    out: Dict[str, Dict[str, NamedSharding]] = {}
    for lk, d in pspecs.items():
        out[lk] = {}
        for pn, spec in d.items():
            kept = []
            for i, ax in enumerate(tuple(spec)):
                n = sizes.get(ax, 1) if ax is not None else 1
                ok = (ax is not None and n > 1
                      and shapes[lk][pn].shape[i] % n == 0)
                kept.append(ax if ok else None)
            out[lk][pn] = NamedSharding(
                mesh, P(*kept) if any(kept) else P())
    return out
