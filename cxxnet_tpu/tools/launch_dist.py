#!/usr/bin/env python3
"""launch_dist: spawn an N-worker multi-controller job on this host.

The localhost analog of the reference's ps-lite launcher + mpi.conf
(example/MNIST/mpi.conf: num_servers/num_workers on one machine) - except
there are no server processes to launch: every worker runs the same SPMD
program and gradients ride XLA collectives (parallel/distributed.py).

Usage:
  launch_dist.py -n 4 [--coordinator 127.0.0.1:29500] -- \\
      python -m cxxnet_tpu.main train.conf param_server=dist

Each worker gets CXN_COORDINATOR / CXN_NUM_WORKER / CXN_WORKER_RANK in
its environment; config keys dist_num_worker/dist_worker_rank on the
iterators pick up the worker's data shard.

Where it applies: the N workers start with IDENTICAL environments, so
on one machine they would all claim the same chips - and a chip
belongs to one process at a time. Run N > 1 here on the CPU only
(JAX_PLATFORMS=cpu: the multi-controller tests and smokes); on TPUs
it is one worker per HOST (each host's launcher starts its single
worker, which drives all of that host's chips). This parent never
touches JAX, so it holds no chip itself.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List


def launch(cmd: List[str], num_workers: int,
           coordinator: str = "127.0.0.1:29500",
           extra_env: dict | None = None) -> int:
    import time
    procs = []
    for rank in range(num_workers):
        env = dict(os.environ)
        env["CXN_COORDINATOR"] = coordinator
        env["CXN_NUM_WORKER"] = str(num_workers)
        env["CXN_WORKER_RANK"] = str(rank)
        if extra_env:
            env.update(extra_env)
        procs.append(subprocess.Popen(cmd, env=env))
    # poll all workers: one crashing must tear the job down, or the
    # survivors hang forever inside collectives waiting for the peer
    rc = 0
    live = list(procs)
    while live and rc == 0:
        time.sleep(0.2)
        for p in list(live):
            code = p.poll()
            if code is not None:
                live.remove(p)
                rc = rc or code
    if rc:
        for p in procs:
            if p.poll() is None:
                p.terminate()
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def cli_main() -> None:
    args = sys.argv[1:]
    num_workers = 2
    coordinator = "127.0.0.1:29500"
    cmd: List[str] = []
    i = 0
    while i < len(args):
        a = args[i]
        if a in ("-n", "--num-workers"):
            num_workers = int(args[i + 1])
            i += 2
        elif a == "--coordinator":
            coordinator = args[i + 1]
            i += 2
        elif a == "--":
            cmd = args[i + 1:]
            break
        else:
            print(__doc__)
            sys.exit(1)
    if not cmd:
        print(__doc__)
        sys.exit(1)
    sys.exit(launch(cmd, num_workers, coordinator))


if __name__ == "__main__":
    cli_main()
