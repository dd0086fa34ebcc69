#!/usr/bin/env python3
"""bench_pool: max-pool backward micro-bench — reference tie rule vs
XLA's native single-winner rule, on AlexNet's three pooling shapes.

The tie-duplicating unpool backward (ops/pooling.py, the reference's
mshadow semantics) costs ky*kx shifted compares over input-sized
tensors; XLA's native select_and_scatter picks one winner. Whether
that traffic matters on a real chip decides the default guidance for
`pool_grad = winner` (docs/layer.md). Prints one JSON line per shape.
One process; timed work ends in block_until_ready.

Usage: python -m cxxnet_tpu.tools.bench_pool [--steps N]
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def main(argv) -> int:
    steps = 30
    batch = 256
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    if "--batch" in argv:
        # CPU smoke: bf16 pooling is emulated (pathologically slow) on
        # the host backend; shrink the batch there
        batch = int(argv[argv.index("--batch") + 1])

    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.ops.pooling import pool2d
    from cxxnet_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()

    # (name, input shape, k, stride) — AlexNet's pools, default b256
    shapes = [("pool1", (batch, 96, 55, 55), 3, 2),
              ("pool2", (batch, 256, 27, 27), 3, 2),
              ("pool3", (batch, 256, 13, 13), 3, 2)]
    rng = np.random.RandomState(0)
    for name, shp, k, st in shapes:
        x = jnp.asarray(rng.randn(*shp), jnp.bfloat16)
        row = {"shape": name}
        for gm in ("ties", "winner"):
            f = jax.jit(jax.grad(
                lambda x, gm=gm: pool2d(
                    x, "max", k, k, st, grad_mode=gm)
                .astype(jnp.float32).sum()))
            g = f(x)
            jax.block_until_ready(g)
            t0 = time.perf_counter()
            for _ in range(steps):
                g = f(x)
            jax.block_until_ready(g)
            row[gm + "_ms"] = round(
                (time.perf_counter() - t0) / steps * 1e3, 3)
        row["winner_speedup"] = round(
            row["ties_ms"] / max(row["winner_ms"], 1e-9), 3)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
