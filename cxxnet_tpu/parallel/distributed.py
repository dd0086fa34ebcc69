"""Multi-host distributed runtime: the mshadow-ps "dist" replacement.

The reference scales across machines with an async parameter server
(mshadow-ps over ps-lite/ZMQ: bin/cxxnet.ps + nnet_ps_server.cpp,
SURVEY.md par.2.7). The TPU-native equivalent is multi-controller SPMD:
every host runs the SAME program under its own JAX process, the global
device mesh spans all hosts, and gradient reduction is a synchronous XLA
AllReduce over ICI/DCN inside the compiled step - no server processes,
no push/pull, no worker/server distinction.

Config surface parity:
    param_server = dist          -> multi-controller mode
    dist_coordinator = host:port -> coordinator (env CXN_COORDINATOR)
    dist_num_worker = N          -> process count (env CXN_NUM_WORKER)
    dist_worker_rank = i         -> this process   (env CXN_WORKER_RANK)
and the data side reuses the reference's per-worker shard keys on the
iterators (dist_num_worker/dist_worker_rank - iter_img.py, mirroring
iter_thread_imbin-inl.hpp:189-220).

`check_replicated` is the test_on_server/CheckWeight_ analog
(async_updater-inl.hpp:144-153): verify that what should be identical
on every device/process actually is.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax

from cxxnet_tpu.utils.config import ConfigError
from cxxnet_tpu.utils.fault import retry


_initialized = False

# bounded init retry defaults (overridable per call / via the
# dist_init_* config keys): a peer that is still binding its
# coordinator port, or a control-plane record written a beat late,
# costs a backoff, not the pod - but the wait is CAPPED, because an
# address that is simply wrong must become a clear error, not an
# infinite connect loop
INIT_ATTEMPTS = 5
INIT_BACKOFF = 0.5
INIT_DEADLINE = 120.0


def _enable_cpu_collectives() -> None:
    """Select the gloo TCP collectives for multi-process CPU jobs.

    jax's CPU client is built with NO cross-process collective
    implementation by default - a multi-controller job on the cpu
    platform compiles fine and then dies at the first AllReduce with
    "Multiprocess computations aren't implemented on the CPU backend".
    The implementation is chosen when the backend client is CREATED,
    so the flag must be set here (before jax.distributed.initialize;
    the client does not exist yet or initialize itself would fail).
    Scoped to cpu platforms: TPU pods keep their native ICI
    collectives and never see this flag."""
    platforms = (os.environ.get("JAX_PLATFORMS", "")
                 or jax.config.jax_platforms or "")
    if "cpu" in platforms.lower():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def init_distributed(coordinator: Optional[str] = None,
                     num_workers: Optional[int] = None,
                     rank: Optional[int] = None,
                     attempts: int = INIT_ATTEMPTS,
                     backoff: float = INIT_BACKOFF,
                     deadline: float = INIT_DEADLINE) -> None:
    """Join the multi-controller job (idempotent).

    Arguments fall back to CXN_COORDINATOR / CXN_NUM_WORKER /
    CXN_WORKER_RANK env vars (the launcher sets them). Single-worker
    jobs are a no-op, like the reference's local parameter server.

    The gloo/distributed handshake is retried with exponential backoff
    + jitter (the PR 1 ``retry`` decorator): a slow-starting peer used
    to be an immediate crash. Total wait is capped by ``deadline``
    seconds; exhaustion raises ``ConfigError`` naming the coordinator.
    """
    global _initialized
    if _initialized:
        return
    coordinator = coordinator or os.environ.get("CXN_COORDINATOR", "")
    num_workers = num_workers if num_workers is not None else int(
        os.environ.get("CXN_NUM_WORKER", "1"))
    rank = rank if rank is not None else int(
        os.environ.get("CXN_WORKER_RANK", "0"))
    if num_workers <= 1:
        return
    if not coordinator:
        raise ValueError(
            "param_server=dist needs dist_coordinator (or "
            "CXN_COORDINATOR) when dist_num_worker > 1")
    _enable_cpu_collectives()

    # RuntimeError is what jax.distributed surfaces for a refused /
    # unreachable coordinator; OSError covers raw socket failures.
    # ValueError (bad arguments) propagates immediately - retrying a
    # typo'd rank cannot help.
    @retry(attempts=max(1, attempts), backoff=backoff,
           jitter=backoff / 2, retry_on=(RuntimeError, OSError),
           deadline=deadline)
    def _connect():
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_workers,
                                   process_id=rank)

    try:
        _connect()
    except (RuntimeError, OSError) as e:
        raise ConfigError(
            f"param_server=dist: could not join the job at "
            f"{coordinator} as rank {rank}/{num_workers} after "
            f"{attempts} attempts (deadline {deadline:g}s): {e}"
        ) from e
    _initialized = True


def init_from_config(pairs: List[Tuple[str, str]]) -> None:
    """Pull the dist_* keys out of a config pair list and initialize."""
    cfg: Dict[str, str] = {}
    for k, v in pairs:
        cfg[k] = v
    if cfg.get("param_server", "local") != "dist":
        return
    init_distributed(
        coordinator=cfg.get("dist_coordinator"),
        num_workers=int(cfg["dist_num_worker"])
        if "dist_num_worker" in cfg else None,
        rank=int(cfg["dist_worker_rank"])
        if "dist_worker_rank" in cfg else None,
        attempts=int(cfg.get("dist_init_retries", INIT_ATTEMPTS)),
        backoff=float(cfg.get("dist_init_backoff", INIT_BACKOFF)),
        deadline=float(cfg.get("dist_init_deadline", INIT_DEADLINE)))


def read_membership(coord_dir: str, attempts: int = INIT_ATTEMPTS,
                    backoff: float = INIT_BACKOFF,
                    deadline: float = INIT_DEADLINE) -> Dict[str, Any]:
    """The pod membership record (``generation.json`` - written by the
    elastic supervisor before each launch, parallel/coordinator.py),
    read with the same bounded retry discipline as the gloo init: the
    record may lag the worker by a beat at generation start, and on a
    network filesystem a read can transiently fail - but a coord_dir
    that never produces a record must become a clear ConfigError, not
    a silent hang or a crash on the first ENOENT."""
    path = os.path.join(coord_dir, "generation.json")

    @retry(attempts=max(1, attempts), backoff=backoff,
           jitter=backoff / 2, retry_on=(OSError,), deadline=deadline)
    def _read() -> Dict[str, Any]:
        with open(path, "r", encoding="utf-8") as f:
            try:
                rec = json.load(f)
            except ValueError as e:
                # torn read on close-to-open-consistency filesystems:
                # transient, retry-absorbable like the OSError path
                raise OSError(f"unparseable membership record: {e}")
        if not isinstance(rec, dict) or "members" not in rec:
            raise OSError(f"membership record missing 'members': {rec}")
        return rec

    try:
        return _read()
    except OSError as e:
        raise ConfigError(
            f"elastic: cannot read pod membership record {path} "
            f"after {attempts} attempts (deadline {deadline:g}s): {e}"
        ) from e


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


# ---------------------------------------------------------------------------
# global-array construction / host readback (multi-process safe)
# ---------------------------------------------------------------------------

def put_global(arr: np.ndarray, sharding) -> jax.Array:
    """Host array -> global jax.Array under a BATCH-DIM-ONLY sharding
    (labels, masks, replicated scalars).

    Single process: plain device_put. Multi-process: `arr` is this
    process's local batch rows (or the full identical value for
    replicated leaves); make_array_from_process_local_data assembles
    the global view. Input tensors whose NON-batch dims may shard
    across processes (the 'seq' mesh axis) go through put_global_rows
    instead - trainer._put_data.
    """
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, arr)


def put_global_rows(arr: np.ndarray, sharding, global_shape,
                    row_start: int) -> jax.Array:
    """Host value covering THIS process's batch rows (dim 0 starting at
    `row_start` of the global batch) and the FULL extent of every other
    dim -> global array under any sharding.

    Unlike put_global, correct when NON-batch dims shard across
    processes (e.g. a cross-host 'seq' mesh axis - parallel/ring.py):
    each device's callback slices its seq portion out of the full-seq
    host rows instead of treating the host array as one pre-cut shard.
    """
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    global_shape = tuple(global_shape)

    def cb(idx):
        if not idx:  # 0-d leaf (scalar state, via put_global_full)
            return arr
        r0, r1, _ = idx[0].indices(global_shape[0])
        return arr[(slice(r0 - row_start, r1 - row_start),)
                   + tuple(idx[1:])]

    return jax.make_array_from_callback(global_shape, sharding, cb)


def put_global_full(arr: np.ndarray, sharding) -> jax.Array:
    """FULL (global-shaped) host value -> global array under any
    sharding (e.g. ZeRO-1 optimizer state split over devices owned by
    several processes): the row_start=0 full-coverage special case of
    put_global_rows."""
    arr = np.asarray(arr)
    return put_global_rows(arr, sharding, arr.shape, 0)


def fetch_local(arr: jax.Array) -> np.ndarray:
    """Global array -> this process's host view.

    Fully-addressable arrays round-trip exactly. For multi-process
    batch-sharded outputs the result is the concatenation of this
    process's shards (rows of the local batch); replicated outputs
    return the full value.
    """
    if arr.is_fully_addressable:
        return np.asarray(arr)
    if arr.sharding.is_fully_replicated:
        return np.asarray(arr.addressable_data(0))
    shards = sorted(arr.addressable_shards, key=lambda s: s.index)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


# ---------------------------------------------------------------------------
# consistency checking (test_on_server analog)
# ---------------------------------------------------------------------------

def check_replicated(tree: Any, name: str = "params") -> List[str]:
    """Verify replicated leaves are bit-identical on every local device
    (and, across processes, that checksums agree). Returns a list of
    human-readable mismatch descriptions; [] = consistent."""
    bad: List[str] = []
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    sums = []
    for path, leaf in leaves:
        if not isinstance(leaf, jax.Array):
            continue
        if not leaf.sharding.is_fully_replicated:
            continue  # sharded-by-design leaves have nothing to compare
        shards = leaf.addressable_shards
        base = np.asarray(shards[0].data)
        for s in shards[1:]:
            if not np.array_equal(base, np.asarray(s.data),
                                  equal_nan=True):
                bad.append(
                    f"{name}{jax.tree_util.keystr(path)}: device "
                    f"{s.device} diverges from {shards[0].device}")
                break
        sums.append(float(np.float64(np.abs(base).sum())))
    if jax.process_count() > 1 and sums:
        # gather every device's view of the checksums through one XLA
        # all-gather over the global device list (same collective setup
        # the train step itself uses)
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mine = np.asarray(sums, np.float32)
        devs = jax.devices()
        mesh = Mesh(np.asarray(devs), ("dev",))
        local = np.tile(mine[None, :], (len(jax.local_devices()), 1))
        g = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("dev")), local,
            (len(devs), mine.size))
        rep = jax.jit(lambda x: x,
                      out_shardings=NamedSharding(mesh, P()))(g)
        allv = np.asarray(rep.addressable_data(0))
        for d in range(allv.shape[0]):
            if not np.allclose(allv[d], mine, rtol=1e-6):
                bad.append(
                    f"{name}: device {devs[d]} checksums diverge from "
                    f"process {jax.process_index()}")
    return bad
