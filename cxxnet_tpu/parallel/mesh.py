"""Device-spec parsing and Mesh construction.

Config surface parity (nnet_impl-inl.hpp:32-51): `dev = gpu:0-3`,
`dev = cpu:0,2`, `dev = tpu:0-63`. An accelerator *kind* is binding:
`dev = tpu[:...]` resolves to TPU devices or raises, naming the platform
JAX found (`resolve_devices`) - a run that asked for a chip never carries
on on the host. The one exemption is an explicit `JAX_PLATFORMS` that
names `cpu` (tests, CI, the CPU verify recipe): an instruction from
outside the program, not a fallback. The index list picks devices by
position.

Extension over the reference: `mesh = data:8,model:4` declares a 2-D mesh
for combined data/tensor parallelism. Without it, all selected devices form
a 1-D 'data' mesh (pure data parallelism - the reference's only mode).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from cxxnet_tpu import telemetry


_ACTIVE_MESH: List[Optional[Mesh]] = [None]


class active_mesh:
    """Context manager binding 'the mesh this forward runs over' so ops
    deep in the layer stack (e.g. the Pallas LRN shard_map route,
    ops/pallas_lrn.py) can partition themselves without the mesh being
    threaded through every Layer.apply signature. The trainer enters it
    around net.forward inside the traced step, so the binding is active
    exactly while that trainer's trace runs (re-entrant per trainer)."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        _ACTIVE_MESH.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _ACTIVE_MESH.pop()
        return False


def get_active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[-1]


def active_device_span() -> int:
    """How many devices the computation being traced runs over - the
    ONE rule the Pallas kernel routes key on (ops/nn.py lrn,
    layers/attention.py _core, ops/int8.py int8_matmul). pallas_call
    has no GSPMD partitioning rule, so 1 takes the single-device
    kernel, > 1 the op's shard_map route (or XLA, which GSPMD
    partitions), 0 declines every kernel route.

    - a mesh bound around the traced step (trainer, server): its size,
      whatever `jax.device_count()` says - a one-chip mesh on a
      four-chip host is 1;
    - nothing bound: an op called directly (tests, kernel tools); a jit
      without shardings computes on one device: 1;
    - None bound: the zero_stage>=2 region, manual over 'data' only -
      Mosaic refuses to lower inside a partially-manual region: 0."""
    if len(_ACTIVE_MESH) == 1:
        return 1
    mesh = _ACTIVE_MESH[-1]
    return 0 if mesh is None else mesh.devices.size


def data_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return mesh.shape.get("data", 1)


def batch_shardable(mesh: Optional[Mesh], batch: int) -> bool:
    """Shared eligibility for shard_map-over-'data' op routes (Pallas
    LRN, per-shard batch_norm): a real data axis whose size divides the
    batch dim."""
    n = data_axis_size(mesh)
    return n > 1 and batch % n == 0


@dataclass
class MeshSpec:
    device_indices: Optional[List[int]] = None  # None = single device
    axes: List[Tuple[str, int]] = field(default_factory=list)
    kind: str = ""  # platform `dev =` named ("" = whatever JAX has)

    @property
    def num_devices(self) -> int:
        if self.axes:
            n = 1
            for _, k in self.axes:
                n *= k
            return n
        return len(self.device_indices) if self.device_indices else 1


def parse_device_spec(val: str) -> Tuple[str, Optional[List[int]]]:
    """`dev =` value -> (kind, indices): `tpu` -> ("tpu", None) (single
    default device); `tpu:0-3` -> ("tpu", [0,1,2,3]);
    `cpu:0,2` -> ("cpu", [0,2])."""
    kind, _, spec = val.partition(":")
    kind = kind.strip().lower()
    if not spec:
        return kind, None
    if "-" in spec:
        a, b = spec.split("-")
        return kind, list(range(int(a), int(b) + 1))
    return kind, [int(t) for t in spec.split(",")]


def resolve_devices(kind: str) -> List[jax.Device]:
    """The process's devices, checked against the platform `dev =`
    named. An accelerator kind (`tpu`, `gpu`) must BE the platform JAX
    initialised: with JAX_PLATFORMS unset a libtpu that fails to start
    leaves JAX on the CPU with a warning, and a `dev = tpu` run would
    train on the host and exit 0. `cpu` and "" take what JAX has (the
    CLI pins the host platform for `dev = cpu` before any backend
    starts - main.py)."""
    devices = jax.devices()
    found = devices[0].platform
    if kind in ("", "cpu", found):
        return devices
    env = os.environ.get("JAX_PLATFORMS", "")
    if "cpu" in [p.strip() for p in env.lower().split(",")]:
        return devices
    raise RuntimeError(
        f"dev = {kind}: JAX found no {kind} device - the platform is "
        f"'{found}' ({len(devices)} x {devices[0].device_kind}). Run "
        f"where a {kind} is attached, or set JAX_PLATFORMS=cpu to run "
        "on the host on purpose.")


def parse_mesh_spec(val: str) -> List[Tuple[str, int]]:
    """`data:8` or `data:8,model:4` -> [(axis, size), ...]."""
    axes = []
    for part in val.split(","):
        name, size = part.split(":")
        axes.append((name.strip(), int(size)))
    return axes


def build_mesh(spec: MeshSpec, batch_size: int,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the mesh, pruning the data axis to divide batch_size.

    The reference prunes its device list when the batch is too small
    (nnet_impl-inl.hpp:141-150); here the constraint is divisibility:
    the data axis is shrunk to the largest size that divides
    batch_size, and the shrink is reported on stderr (asked / got) - a
    `dev = tpu:0-3` run on fewer chips than asked must say so.
    """
    devices = list(devices if devices is not None
                   else resolve_devices(spec.kind))
    if spec.axes:
        if spec.device_indices is not None:
            # `dev = tpu:4-7` + `mesh = ...` composes: the mesh is laid
            # out over the SELECTED devices, not silently over the
            # first N of the full list
            if max(spec.device_indices) >= len(devices):
                raise ValueError(
                    f"device spec requests index "
                    f"{max(spec.device_indices)} but only "
                    f"{len(devices)} devices are available")
            devices = [devices[i] for i in spec.device_indices]
        names = [a for a, _ in spec.axes]
        sizes = [k for _, k in spec.axes]
    else:
        idx = spec.device_indices
        if idx is None:
            # single-controller default: one device. Multi-controller
            # (param_server=dist): every process must own part of the
            # mesh, so default to data-parallel over ALL global devices.
            if jax.process_count() == 1:
                devices = devices[:1]
        else:
            if max(idx) >= len(devices):
                raise ValueError(
                    f"device spec requests index {max(idx)} but only "
                    f"{len(devices)} devices are available")
            devices = [devices[i] for i in idx]
        names = ["data"]
        sizes = [len(devices)]

    # prune the data axis to divide the batch (single-controller only:
    # under multi-controller SPMD, dropping devices would orphan some
    # processes' chips, so an indivisible batch is an error instead)
    if "data" in names:
        di = names.index("data")
        if jax.process_count() > 1:
            if batch_size % sizes[di] != 0:
                raise ValueError(
                    f"batch_size {batch_size} must be divisible by the "
                    f"data axis ({sizes[di]}) in multi-controller mode")
        else:
            asked = sizes[di]
            while batch_size % sizes[di] != 0:
                sizes[di] -= 1
            if sizes[di] != asked:
                telemetry.stderr(
                    f"mesh: data axis pruned from {asked} to "
                    f"{sizes[di]} devices so it divides batch_size "
                    f"{batch_size}\n",
                    event_kind="config", type="mesh_pruned",
                    asked=asked, got=sizes[di], batch_size=batch_size)

    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError(
            f"mesh of {n} devices requested, {len(devices)} available")
    dev_array = np.asarray(devices[:n]).reshape(sizes)
    return Mesh(dev_array, tuple(names))
