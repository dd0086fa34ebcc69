#!/usr/bin/env python3
"""bench_attn: flash-attention kernel block-size sweep on the chip.

The Pallas kernel's (BLOCK_Q, BLOCK_K) trades grid overhead against
VMEM per program: (128, 128) is exact MXU-shaped score tiles but a
(b, h, s/bq, s/bk) grid of tiny programs; larger tiles amortize the
grid at more VMEM per program. This sweeps the candidates and prints
one JSON line per config so the winner can be promoted to the module
defaults with data.

Usage:  python -m cxxnet_tpu.tools.bench_attn [--quick]
          [--shape b,h,s,d] [--steps N]

Each config is measured fwd+all-grads (the training cost), bf16, one
process, timed work ending in block_until_ready. A config that fails
to lower prints an error row instead of aborting the sweep.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def measure(core, q, k, v, flops, steps):
    import jax
    f = jax.jit(jax.grad(
        lambda q, k, v: core(q, k, v).astype("float32").sum(),
        argnums=(0, 1, 2)))
    t0 = time.perf_counter()
    g = jax.block_until_ready(f(q, k, v))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        g = f(q, k, v)
    jax.block_until_ready(g)
    return steps * flops / (time.perf_counter() - t0) / 1e12, compile_s


def main(argv) -> int:
    shape = (4, 8, 4096, 128)
    steps = 10
    if "--shape" in argv:
        shape = tuple(
            int(t) for t in argv[argv.index("--shape") + 1].split(","))
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    configs = [(128, 128), (256, 256), (512, 512), (256, 1024),
               (512, 1024), (1024, 1024)]
    if "--quick" in argv:
        configs = [(128, 128), (512, 512)]

    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.ops import pallas_attention as PA
    from cxxnet_tpu.ops.attention import blockwise_attention
    from cxxnet_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()

    b, h, s, d = shape
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
               for _ in range(3))
    flops = 14.0 * b * h * s * s * d
    # causal rows count REALIZED flops (~half: future tiles skipped)
    # and compare against a causal XLA baseline - full-count causal
    # numbers would overstate throughput ~2x and make vs_xla
    # apples-to-oranges
    flops_c = flops / 2.0

    baselines = {}
    for causal in (False, True):
        tf, _ = measure(
            lambda q, k, v, c=causal: blockwise_attention(
                q, k, v, kv_block=512, causal=c),
            q, k, v, flops_c if causal else flops, steps)
        baselines[causal] = tf
        print(json.dumps({
            "config": "xla_blockwise" + ("_causal" if causal else ""),
            "tflops": round(tf, 2)}), flush=True)

    saved = PA.BLOCK_Q, PA.BLOCK_K
    try:
        for bq, bk in configs:
            PA.BLOCK_Q, PA.BLOCK_K = bq, bk
            for causal in (False, True):
                try:
                    tf, comp = measure(
                        lambda q, k, v: PA.flash_attention(
                            q, k, v, causal, None, False),
                        q, k, v, flops_c if causal else flops, steps)
                    print(json.dumps({
                        "config": f"bq{bq}_bk{bk}" +
                                  ("_causal" if causal else ""),
                        "tflops": round(tf, 2),
                        "vs_xla": round(tf / baselines[causal], 3),
                        "compile_s": round(comp, 1)}), flush=True)
                except Exception as e:  # noqa: BLE001 - sweep survives
                    print(json.dumps({
                        "config": f"bq{bq}_bk{bk}" +
                                  ("_causal" if causal else ""),
                        "error": f"{type(e).__name__}: {e}"[:200]}),
                        flush=True)
    finally:
        PA.BLOCK_Q, PA.BLOCK_K = saved
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
