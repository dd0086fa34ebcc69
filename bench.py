"""Headline benchmark: AlexNet training throughput (images/sec).

Two numbers are measured on the same trainer:

- ``compute``:  the jitted train step driven on pre-staged device
  buffers - the kernel/compiler ceiling.
- ``e2e``:      the full product path the reference times
  (cxxnet_main.cpp:367-387): ``trainer.update()`` fed per-step from
  host batches - includes padding, H2D staging, the on-device metric
  accumulation, and the optimizer, i.e. what a user actually gets.

The headline ``value`` is the END-TO-END number. Extras (each optional,
each snapshotted, each individually guarded so a failure leaves an
``*_error`` field in the artifact and the later measurements still
run - the exit code is then 1) record:

- ``top_ops``/``profiled_device_ms``: top-5 device ops of the compiled
  e2e step (where the step time goes).
- ``host_prep_ms_p50``/``device_step_ms_p50``/``augment_ips``: the
  input-pipeline split - is training host-bound or device-bound, and
  can host-side crop/mirror/mean augmentation keep up with the chip
  (the device-side-augmentation go/no-go in docs/perf.md).
- ``attn_*``: Pallas flash-attention kernel vs the XLA blockwise path
  (fwd+bwd TFLOP/s).
- ``googlenet_ips`` / ``resnet18_ips`` (+ ``*_devicedata_ips``):
  additional model families - GoogLeNet (BASELINE config #5,
  concat-heavy inception graph) and ResNet-18 (residual adds +
  per-shard batch norm; last in the registry).
- ``e2e_eval_train_ips``: eval_train=1 (the reference's default mode)
  with device-side metric accumulators compiled into the step. Needs a
  second full AlexNet compile -> a deliberately late, expendable
  extra.

One process holds the chip: every measurement runs inline in this
process (a chip belongs to one process at a time - a parent that has
touched JAX holds it, and a child that needs it then fails or hangs),
and every timed region ends in ``jax.block_until_ready``.

This is a DEVICE benchmark: ``python bench.py`` exits non-zero, naming
the platform it found, when JAX has no TPU, and exits non-zero on any
error - a crash, a watchdog cut, or a single ``*_error`` field in the
printed artifact. A CPU run can state counts, never a rate, so there
is no CPU fallback. Every artifact names ``platform`` / ``device_kind`` /
``device_count``. (``run()`` itself stays callable on any backend: the
test suite drives it at a tiny batch on the CPU as a harness smoke,
and nothing it returns there is a device number.)

Partial-result discipline: ``_PARTIAL`` is snapshotted after EVERY
measurement (compute first). If the watchdog fires mid-run, or a late
measurement crashes, whatever is complete is printed (labeled
``truncated``) before the non-zero exit.

Compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` if set, else the
repo-local ``.jax_cache/`` (gitignored) - utils/platform.py.

Baseline constant: the reference publishes no numbers (BASELINE.md), and
this sandbox has no A100 (and no egress to cite one), so the A100
anchor is an arithmetic estimate, documented at the constant. The
``achieved_tflops``/``mfu_pct`` fields ground the perf claim in the
chip's own peak instead.

Usage: python bench.py [--profile DIR] [--steps N] [--batch N]
    --profile DIR  additionally capture a jax.profiler trace of the
                   steady-state e2e loop into DIR.

A watchdog thread (CXN_BENCH_TIMEOUT, default 480 s; 0 = off) bounds
the run: when it fires it prints the snapshot, if any measurement
completed, and exits 1.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import jax
import numpy as np

# AlexNet training flops/image ~= 0.72 GMAC fwd x 2 flop/MAC x 3
# (fwd + dgrad + wgrad) ~= 4.3 GFLOP. A100 bf16 peak = 312 TFLOP/s;
# AlexNet's LRN/pooling/fc mix sustains well under full MFU - assume
# ~15%, in line with public convnet training MFU on Ampere, giving
# 312e12 * 0.15 / 4.3e9 ~= 10.9k img/s; rounded to 10k. An estimate,
# not a measurement: no A100 exists here and the reference publishes
# no throughput numbers (BASELINE.md).
A100_IMAGES_PER_SEC = 10000.0
ALEXNET_TRAIN_GFLOP_PER_IMG = 4.3

# bf16 peak TFLOP/s by device_kind substring - grounds the perf claim
# in the chip's own numbers (public TPU spec sheets)
_TPU_PEAK_TFLOPS = (
    ("v6e", 918.0), ("v6 lite", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0), ("v5 lite", 197.0), ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)

_REPO = os.path.dirname(os.path.abspath(__file__))


def _peak_for(device_kind: str) -> float:
    """Spec bf16 peak of a TPU device_kind. A device that is not in
    the table is an error, not a default: an mfu_pct against a made-up
    peak would be a made-up number."""
    for sub, peak in _TPU_PEAK_TFLOPS:
        if sub in device_kind.lower():
            return peak
    raise ValueError(
        f"no bf16 peak known for device_kind {device_kind!r}; add it "
        "to _TPU_PEAK_TFLOPS with its source")

# headline results land here as soon as they are measured; the watchdog
# and the crash path print these instead of throwing away a completed
# measurement. _EMIT_LOCK serializes the "who prints the one JSON line"
# decision between the main thread and the watchdog timer.
_PARTIAL: dict = {}
_EMIT_LOCK = threading.Lock()


def _snapshot(out: dict) -> None:
    """Checkpoint the result dict so the watchdog can emit it as-is.
    REPLACES the previous snapshot rather than merging; the 'emitted'
    print-claim flag is the one key that survives."""
    with _EMIT_LOCK:
        emitted = _PARTIAL.get("emitted")
        _PARTIAL.clear()
        _PARTIAL.update(out)
        if emitted:
            _PARTIAL["emitted"] = True


def _alexnet_batch(rng, batch):
    """The bench's input shape in ONE place (matches _ALEXNET_CONF)."""
    return (rng.randn(batch, 3, 227, 227).astype(np.float32),
            rng.randint(0, 1000, size=(batch, 1)).astype(np.float32))


def _measure_compute(trainer, batch, steps):
    """Train-step-only throughput on pre-staged device buffers.

    Staging mirrors trainer.update(): data under _data_sharded with
    the host-side compute-dtype cast (_host_input), labels/mask under
    _batch_sharded, extras the () the conf declares - the exact
    in_shardings the compiled step was built with (trainer.py _compile).
    """
    rng = np.random.RandomState(0)
    hdata, hlabel = _alexnet_batch(rng, batch)
    data = jax.device_put(trainer._host_input(hdata),
                          trainer._data_sharded)
    label = jax.device_put(hlabel, trainer._batch_sharded)
    mask = jax.device_put(np.ones(batch, np.float32),
                          trainer._batch_sharded)
    labels = {"label": label}
    key = jax.random.PRNGKey(0)

    state = trainer.state
    # warmup (compile + first run)
    for i in range(3):
        state, loss = trainer._train_step(
            state, data, (), labels, mask, jax.random.fold_in(key, i))
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for i in range(steps):
        state, loss = trainer._train_step(
            state, data, (), labels, mask, jax.random.fold_in(key, i))
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    trainer.state = state
    return steps * batch / dt


def _warm_and_size(trainer, step_fn, steps, budget_s, floor=4):
    """Shared warmup + window-sizing for every host-paced (H2D) loop:
    compile + first step, ONE timed step to estimate the per-step
    cost, then return how many steps fit budget_s (capped at `steps`,
    floored at `floor`)."""
    step_fn(0)  # compile + first step
    jax.block_until_ready(trainer.state)
    t0 = time.perf_counter()
    step_fn(1)
    jax.block_until_ready(trainer.state)
    per_step = max(time.perf_counter() - t0, 1e-6)
    return int(min(steps, max(floor, budget_s / per_step)))


def _measure_e2e(trainer, batch, steps, profile_dir="", budget_s=60.0):
    """Full trainer.update() path fed from host batches.

    Returns (images_per_sec, steps_used); steps_used is window-sized
    by _warm_and_size."""
    from cxxnet_tpu.io.data import DataBatch
    rng = np.random.RandomState(1)
    # a few distinct host batches cycled through, like a RAM-resident
    # iterator (membuffer); fresh numpy arrays each step would measure
    # the RNG, identical ones would hide nothing - staging cost is the
    # same either way
    nbuf = min(8, steps)
    batches = [DataBatch(*_alexnet_batch(rng, batch))
               for _ in range(nbuf)]
    n = _warm_and_size(trainer,
                       lambda i: trainer.update(batches[i % nbuf]),
                       steps, budget_s)

    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    t0 = time.perf_counter()
    for i in range(n):
        trainer.update(batches[i % nbuf])
    jax.block_until_ready(trainer.state)
    dt = time.perf_counter() - t0
    if profile_dir:
        jax.profiler.stop_trace()
    return n * batch / dt, n


def _bench_attention(platform: str) -> dict:
    """Flash-attention kernel micro-bench (TPU only): fwd+bwd TFLOP/s
    for the Pallas kernel vs the XLA blockwise path on a transformer
    shape (b4 h8 s4096 d128, bf16). This is the kernel's on-hardware
    validation - the sandbox's CPU mesh can only run it in interpret
    mode - so a kernel failure leaves an attn_error field (and exit
    code 1) without stopping the measurements after it. Disable with
    CXN_BENCH_ATTN=0."""
    if platform != "tpu" or os.environ.get("CXN_BENCH_ATTN") == "0":
        return {}
    try:
        import jax.numpy as jnp
        from cxxnet_tpu.ops.attention import blockwise_attention
        from cxxnet_tpu.ops.pallas_attention import flash_attention

        b, h, s, d = 4, 8, 4096, 128
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
                   for _ in range(3))
        # fwd 2 matmuls (4bhs^2d flops) + bwd 5 matmuls (10bhs^2d)
        flops = 14.0 * b * h * s * s * d
        steps = 10

        def measure(core):
            # all three grads: argnums=0 alone would let XLA dead-code
            # the dK/dV matmuls out of the XLA path while the fused
            # Pallas bwd computes them regardless, skewing the ratio
            f = jax.jit(jax.grad(
                lambda q, k, v: core(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)))
            g = f(q, k, v)
            jax.block_until_ready(g)
            t0 = time.perf_counter()
            for _ in range(steps):
                g = f(q, k, v)
            jax.block_until_ready(g)
            return steps * flops / (time.perf_counter() - t0) / 1e12

        pallas_tf = measure(
            lambda q, k, v: flash_attention(q, k, v, False, None, False))
        xla_tf = measure(
            lambda q, k, v: blockwise_attention(q, k, v, kv_block=512))
        return {"attn_pallas_tflops": round(pallas_tf, 2),
                "attn_xla_tflops": round(xla_tf, 2),
                "attn_pallas_speedup": round(pallas_tf / xla_tf, 3)}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"attn_error": f"{type(e).__name__}: {e}"}


def _bench_top_ops(trainer, batch, platform: str) -> dict:
    """Compact device profile of the already-compiled e2e step (TPU
    only; no extra compile): 8 profiled updates -> top-5 ops by device
    time as [[name, pct], ...]. The driver records the JSON artifact,
    so this lands the step's time breakdown on every on-chip bench run.
    Disable with CXN_BENCH_PROFILE=0."""
    if platform != "tpu" or os.environ.get("CXN_BENCH_PROFILE") == "0":
        return {}
    try:
        import glob
        import tempfile

        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.tools.profile_step import op_table
        rng = np.random.RandomState(2)
        db = DataBatch(*_alexnet_batch(rng, batch))
        d = tempfile.mkdtemp(prefix="cxn_bench_prof_")
        try:
            jax.profiler.start_trace(d)
            for _ in range(8):
                trainer.update(db)
            # the trace must contain EXECUTED steps
            jax.block_until_ready(trainer.state)
            jax.profiler.stop_trace()
            xp = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True)
            rows, total = op_table(xp[0], top=5)
        finally:
            import shutil
            shutil.rmtree(d, ignore_errors=True)
        return {"top_ops": [[n[:60], round(100.0 * ns / max(total, 1), 1)]
                            for n, ns in rows],
                "profiled_device_ms": round(total / 1e6, 2)}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"profile_error": f"{type(e).__name__}: {e}"}


def _bench_input_split(trainer, batch, platform: str) -> dict:
    """Host-prep vs device-step split (no extra compile) + host-side
    augmentation throughput - the numbers behind the device-side-
    augmentation go/no-go (docs/perf.md).

    - host_prep_ms_p50 / device_step_ms_p50: a short profile=1 loop
      through trainer.update() (pad + cast + H2D stage vs blocked
      device step). profile=1 serializes the async overlap, so this
      runs AFTER the headline e2e loop, on its own steps.
    - augment_ips: single-thread images/sec of the imgbin hot path per
      image - random 256->227 crop + mirror + mean-image subtract
      (io/augment.py:278-302) - measured on the bench host, so the
      artifact records whether CPU-side augmentation can keep up with
      the chip's e2e rate (augment_ips x decode threads vs value).
    Disable with CXN_BENCH_SPLIT=0."""
    if os.environ.get("CXN_BENCH_SPLIT") == "0":
        return {}
    try:
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.utils.profiler import StepProfiler
        rng = np.random.RandomState(3)
        db = DataBatch(*_alexnet_batch(rng, batch))
        prof = StepProfiler()
        old_profile, old_profiler = trainer.profile, trainer.profiler
        trainer.profile, trainer.profiler = 1, prof
        try:
            n = 8 if platform == "tpu" else 2
            trainer.update(db)  # warm the profiled path
            prof.reset()
            for _ in range(n):
                trainer.update(db)
            jax.block_until_ready(trainer.state)
        finally:
            trainer.profile, trainer.profiler = old_profile, old_profiler
        out = {}
        if prof.step_s and prof.data_s:
            host = float(np.percentile(prof.data_s, 50) * 1e3)
            out["host_prep_ms_p50"] = round(host, 2)
            dev = float(np.percentile(prof.step_s, 50) * 1e3)
            out.update(device_step_ms_p50=round(dev, 2),
                       host_over_device=round(
                           host / max(dev, 1e-9), 3))

        # augment hot path, per image, single thread: drive the REAL
        # AugmentIterator._set_data (mean-image subtract, contrast/
        # illumination, rand crop, mirror, scale) on the AlexNet.conf
        # recipe - an inline transcription would silently drift from
        # the pipeline this number gates (docs/perf.md go/no-go rule)
        from cxxnet_tpu.io.augment import AugmentIterator
        from cxxnet_tpu.io.data import DataInst

        class _Base:  # _set_data never touches the base iterator
            def set_param(self, name, val):
                pass

        it = AugmentIterator(_Base())
        for kv in (("input_shape", "3,227,227"), ("rand_crop", "1"),
                   ("rand_mirror", "1")):
            it.set_param(*kv)
        it.meanimg = rng.randn(3, 256, 256).astype(np.float32)
        insts = [DataInst(index=i, data=im, label=np.zeros(1, np.float32))
                 for i, im in enumerate(
                     rng.randint(0, 256, (32, 3, 256, 256))
                     .astype(np.float32))]
        t0 = time.perf_counter()
        reps = 4
        for _ in range(reps):
            for inst in insts:
                it._set_data(inst)
                it.value()
        dt = time.perf_counter() - t0
        out["augment_ips"] = round(reps * len(insts) / dt, 1)
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"split_error": f"{type(e).__name__}: {e}"}


def _bench_stage_f32(trainer, batch, steps, platform: str) -> dict:
    """e2e with `stage_dtype = float32`: stage f32 (2x H2D bytes) and
    let the jitted step cast to bf16 ON DEVICE (fused into the first
    conv) instead of the host-side ml_dtypes cast (~70 ms single-thread
    for an AlexNet b256 batch - potentially several device-steps'
    worth). Whichever of `value` vs `e2e_f32stage_ips` wins tells
    which side of the host-CPU/link trade this machine sits on. Costs
    one retrace of the same step for the f32 aval. TPU only (the
    host-vs-link trade does not exist on the CPU backend). Disable
    with CXN_BENCH_STAGEF32=0."""
    if platform != "tpu" or os.environ.get("CXN_BENCH_STAGEF32") == "0":
        return {}
    try:
        if trainer.compute_dtype == np.float32:
            return {}  # f32 compute already stages f32; nothing to vary
        trainer.stage_dtype = "float32"
        try:
            ips, n = _measure_e2e(trainer, batch, steps)
        finally:
            trainer.stage_dtype = ""
        return {"e2e_f32stage_ips": round(ips, 2), "f32stage_steps": n}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"stage_f32_error": f"{type(e).__name__}: {e}"}


def _bench_device_augment(batch, steps, platform: str) -> dict:
    """e2e with `device_augment = 1`: raw 3x256x256 uint8 batches
    (50 MB H2D vs 79 MB bf16 / 158 MB f32 crops) with crop / mirror /
    mean / scale fused into the jitted step - the measured AFTER for
    the device-side-augmentation go/no-go (docs/perf.md): compare
    `device_augment_ips` against `value` (host-prepped crops) and the
    host augment ceiling (`augment_ips` x cores). TPU only (one more
    full compile). Disable with CXN_BENCH_DAUG=0."""
    if platform != "tpu" or os.environ.get("CXN_BENCH_DAUG") == "0":
        return {}
    try:
        from __graft_entry__ import _ALEXNET_CONF, _make_trainer
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.utils.config import parse_config_file
        tr = _make_trainer(
            parse_config_file(_ALEXNET_CONF),
            _flagship_overrides(batch, 0, (
                ("device_augment", "1"), ("rand_crop", "1"),
                ("rand_mirror", "1"), ("mean_value", "104,117,123"),
                ("image_mean", ""))))
        rng = np.random.RandomState(5)
        nbuf = min(8, steps)
        batches = [DataBatch(
            data=rng.randint(0, 256, (batch, 3, 256, 256),
                             dtype=np.uint8).astype(np.uint8),
            label=rng.randint(0, 1000, (batch, 1)).astype(np.float32))
            for _ in range(nbuf)]
        n = _warm_and_size(tr, lambda i: tr.update(batches[i % nbuf]),
                           steps, 60.0)
        t0 = time.perf_counter()
        for i in range(n):
            tr.update(batches[i % nbuf])
        jax.block_until_ready(tr.state)
        dt = time.perf_counter() - t0
        return {"device_augment_ips": round(n * batch / dt, 2),
                "device_augment_steps": n}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"device_augment_error": f"{type(e).__name__}: {e}"}


def _bench_model_family(conf_name, prefix, gate, batch, steps,
                        platform: str, seed: int) -> dict:
    """Shared e2e measurement for a non-flagship model family: streamed
    images/sec at reduced steps + the device-resident (staged-once)
    variant, fields named <prefix>_ips / <prefix>_devicedata_ips. TPU
    only (a b256 deep-net compile+run on the host CPU would blow the
    whole watchdog budget)."""
    if platform != "tpu" or os.environ.get(gate) == "0":
        return {}
    try:
        from __graft_entry__ import _make_trainer
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.utils.config import parse_config_file
        conf = os.path.join(_REPO, "examples", "ImageNet", conf_name)
        tr = _make_trainer(
            parse_config_file(conf),
            [("batch_size", str(batch)), ("dev", "tpu"), ("silent", "1"),
             ("eval_train", "0"), ("save_model", "0")])
        rng = np.random.RandomState(seed)
        db = DataBatch(
            data=rng.randn(batch, 3, 224, 224).astype(np.float32),
            label=rng.randint(0, 1000, (batch, 1)).astype(np.float32))
        gsteps = _warm_and_size(tr, lambda i: tr.update(db),
                                max(2, steps // 5), 45.0, floor=2)
        t0 = time.perf_counter()
        for _ in range(gsteps):
            tr.update(db)
        jax.block_until_ready(tr.state)
        dt = time.perf_counter() - t0
        out = {f"{prefix}_ips": round(gsteps * batch / dt, 2),
               f"{prefix}_steps": gsteps}
        # device-resident variant (same compiled step, batch staged
        # once): the family's input-pipeline-free number, like
        # e2e_devicedata_ips for AlexNet - budget-bounded
        try:
            ips, _n = _time_staged(tr, [tr.stage_batch(db)],
                                   max(4, gsteps), batch, 25.0)
            out[f"{prefix}_devicedata_ips"] = round(ips, 2)
        except Exception as e:  # noqa: BLE001 - keep the streamed number
            out[f"{prefix}_devicedata_error"] = \
                f"{type(e).__name__}: {e}"
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {f"{prefix}_error": f"{type(e).__name__}: {e}"}


def _bench_googlenet(batch, steps, platform: str) -> dict:
    """Second model family (BASELINE config #5): GoogLeNet, the
    concat-heavy inception graph - stresses fusion patterns AlexNet
    doesn't. Disable with CXN_BENCH_GOOGLENET=0."""
    return _bench_model_family("GoogLeNet.conf", "googlenet",
                               "CXN_BENCH_GOOGLENET", batch, steps,
                               platform, seed=4)


def _bench_resnet(batch, steps, platform: str) -> dict:
    """Third model family: ResNet-18 (examples/ImageNet/ResNet18.conf)
    - residual adds + per-shard batch norm, the add/BN composition the
    other families don't exercise. Last in the registry. Disable
    with CXN_BENCH_RESNET=0."""
    return _bench_model_family("ResNet18.conf", "resnet18",
                               "CXN_BENCH_RESNET", batch, steps,
                               platform, seed=6)


def _bench_chip_matmul(platform: str) -> dict:
    """Pure-matmul sustained TFLOP/s: 64 chained 4096^2 bf16 matmuls
    inside ONE jitted lax.scan, so per-call dispatch latency cannot
    bound the number. Grounds the MFU story: if the chip sustains
    near its spec peak here but AlexNet's step runs far below, the
    gap is model-shape-bound (conv1 11x11/s4, LRN, pools), not a chip
    or runtime artifact. TPU only. Disable with CXN_BENCH_MATMUL=0."""
    if platform != "tpu" or os.environ.get("CXN_BENCH_MATMUL") == "0":
        return {}
    try:
        import jax.numpy as jnp
        from jax import lax
        n, chain = 4096, 64

        def body(x, _):
            return (x @ x) * (1.0 / n), None

        @jax.jit
        def run(x):
            y, _ = lax.scan(body, x, None, length=chain)
            return y

        x = jnp.full((n, n), 1.0, jnp.bfloat16)
        jax.block_until_ready(run(x))
        reps = 5
        t0 = time.perf_counter()
        y = x
        for _ in range(reps):
            y = run(y)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        tflops = reps * chain * 2.0 * n ** 3 / dt / 1e12
        return {"chip_matmul_tflops": round(tflops, 1)}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"matmul_probe_error": f"{type(e).__name__}: {e}"}


def _time_staged(tr, staged, steps, batch, budget_s):
    """Timed update(staged) loop - the device-resident measurement
    shared by the AlexNet and model-family measurements. One sized
    step bounds the loop to budget_s."""
    n_st = len(staged)
    for i in range(2):
        tr.update(staged[i % n_st])
    jax.block_until_ready(tr.state)
    t0 = time.perf_counter()
    tr.update(staged[2 % n_st])
    jax.block_until_ready(tr.state)
    per = max(time.perf_counter() - t0, 1e-6)
    n = int(min(steps, max(4, budget_s / per)))
    t0 = time.perf_counter()
    for i in range(n):
        tr.update(staged[i % n_st])
    jax.block_until_ready(tr.state)
    return n * batch / (time.perf_counter() - t0), n


def _bench_device_data(ctx) -> dict:
    """e2e with a DEVICE-RESIDENT dataset: stage_batch() pre-stages
    the batches once, update(staged) streams zero bytes per step -
    the TPU-first analog of the reference's membuffer (RAM-resident
    host batches, iter_mem_buffer-inl.hpp). For any dataset that fits
    HBM this IS the product e2e path (compare compute_ips: the
    remaining gap is the trainer's per-step host work - RNG fold,
    dispatch - not input streaming). Disable with CXN_BENCH_DEVDATA=0."""
    if (ctx.platform != "tpu"
            or os.environ.get("CXN_BENCH_DEVDATA") == "0"):
        return {}
    try:
        from cxxnet_tpu.io.data import DataBatch
        tr = ctx.trainer
        rng = np.random.RandomState(7)
        staged = [tr.stage_batch(DataBatch(*_alexnet_batch(rng,
                                                           ctx.batch)))
                  for _ in range(4)]
        ips, _n = _time_staged(tr, staged, ctx.steps, ctx.batch, 45.0)
        return {"e2e_devicedata_ips": round(ips, 2)}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"device_data_error": f"{type(e).__name__}: {e}"}


def _bench_prefetch(ctx) -> dict:
    """Streamed e2e THROUGH the H2D staging prefetcher
    (trainer.prefetch, io/prefetch.py): batch k+1's pad + cast +
    device_put runs on a worker thread while step k executes - the
    reference ThreadBuffer idea at the host->device edge
    (thread_buffer.h:22-202). The delta vs `e2e_ips` prices the
    double buffering; e2e_prefetch_ips >= 0.9 x compute_ips is the
    product bar for streamed training. Runs on CPU too (the overlap logic is
    platform-free). Disable with CXN_BENCH_PREFETCH=0."""
    if os.environ.get("CXN_BENCH_PREFETCH") == "0":
        return {}
    try:
        from cxxnet_tpu.io.data import DataBatch
        tr = ctx.trainer
        batch = ctx.batch
        rng = np.random.RandomState(11)
        nbuf = min(8, ctx.steps)
        batches = [DataBatch(*_alexnet_batch(rng, batch))
                   for _ in range(nbuf)]

        class _Cycle:
            """Minimal DataIter serving n host batches."""

            def __init__(self, n):
                self.n, self.i = n, -1

            def before_first(self):
                self.i = -1

            def next(self):
                self.i += 1
                return self.i < self.n

            def value(self):
                return batches[self.i % nbuf]

        n = _warm_and_size(tr,
                           lambda i: tr.update(batches[i % nbuf]),
                           ctx.steps, 45.0)
        pf = tr.prefetch(_Cycle(n), depth=1)
        try:
            t0 = time.perf_counter()
            pf.before_first()
            while pf.next():
                tr.update(pf.value())
            jax.block_until_ready(tr.state)
            dt = time.perf_counter() - t0
        finally:
            pf.close()  # an update() error must not leak the worker
        return {"e2e_prefetch_ips": round(n * batch / dt, 2),
                "e2e_prefetch_steps": n}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"e2e_prefetch_error": f"{type(e).__name__}: {e}"}


def _bench_fused(ctx) -> dict:
    """e2e with fused multi-step dispatch (steps_per_dispatch=K,
    docs/PERFORMANCE.md): K host batches stage + stack into one
    StagedChunk and ONE jitted scan runs all K updates, so the host
    pays one dispatch + zero per-step readbacks per K steps. The
    derived `fused_over_e2e` ratio vs `e2e_ips` prices exactly the
    per-step dispatch overhead this removes (CPU harness ratios are
    meaningful - both sides pace the same host; the TPU field names
    are wired for the next verified-sync run). One extra compile (the
    chunk executable inlines K step bodies). K via CXN_BENCH_FUSED_K,
    default 4. Disable with CXN_BENCH_FUSED=0."""
    if os.environ.get("CXN_BENCH_FUSED") == "0":
        return {}
    try:
        from cxxnet_tpu.io.data import DataBatch
        tr = ctx.trainer
        batch = ctx.batch
        k = max(2, int(os.environ.get("CXN_BENCH_FUSED_K", "4")))
        rng = np.random.RandomState(13)
        nbuf = 8
        batches = [DataBatch(*_alexnet_batch(rng, batch))
                   for _ in range(nbuf)]

        def chunk_at(i):
            return [batches[(i * k + j) % nbuf] for j in range(k)]

        nchunks = _warm_and_size(
            tr, lambda i: tr.update_chunk(chunk_at(i)),
            max(2, ctx.steps // k), 45.0, floor=2)
        t0 = time.perf_counter()
        for i in range(nchunks):
            tr.update_chunk(chunk_at(i))
        jax.block_until_ready(tr.state)
        dt = time.perf_counter() - t0
        return {"e2e_fused_ips": round(nchunks * k * batch / dt, 2),
                "e2e_fused_k": k,
                "e2e_fused_steps": nchunks * k}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"e2e_fused_error": f"{type(e).__name__}: {e}"}


def _bench_zero(ctx) -> dict:
    """e2e with ZeRO-2 weight-update sharding (zero_stage=2,
    docs/parallel.md): gradients reduce-scattered over the data axis,
    the optimizer update run on each device's 1/N shard, fresh
    weights all-gathered. The derived `zero_over_e2e` ratio vs
    `e2e_ips` prices the trade (less update FLOPs + state HBM vs the
    extra gather latency); `opt_state_bytes_per_dev` is the measured
    per-device optimizer-state footprint - the HBM claim as a gauge
    through the telemetry registry, not an assertion (on a 1-device
    mesh it simply equals the full state and the stage degrades to
    replicated, which the ratio then shows as ~1.0). Second AlexNet
    compile. Disable with CXN_BENCH_ZERO=0."""
    if os.environ.get("CXN_BENCH_ZERO") == "0":
        return {}
    try:
        from cxxnet_tpu import telemetry
        tr = ctx.make(0, [("zero_stage", "2")])
        out = {}
        state_bytes = sum(
            a.addressable_shards[0].data.nbytes
            for a in jax.tree_util.tree_leaves(tr.state["ustate"]))
        out["opt_state_bytes_per_dev"] = int(state_bytes)
        telemetry.set_gauge("zero.opt_state_bytes_per_dev",
                            float(state_bytes))
        ips, n = _measure_e2e(tr, ctx.batch, ctx.steps)
        out["zero2_ips"] = round(ips, 2)
        out["zero2_steps"] = n
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"zero2_error": f"{type(e).__name__}: {e}"}


def _bench_serve(ctx) -> dict:
    """Continuous-batching serving (serve/server.py, docs/SERVING.md):
    warmed bucket executables + replica fan-out driven by a threaded
    load generator of mixed-size requests. `serve_qps` is requests/s
    and `serve_rows_per_s` images/s through the server (the physics-
    capped field); `serve_p50_ms`/`serve_p99_ms` are the end-to-end
    request latencies from the telemetry histogram; the derived
    `serve_over_predict` prices continuous batching against the ideal
    batch-at-a-time predict loop over the SAME images in the SAME
    window (<1 = the bucket padding + admission wait you pay for
    bounded per-request latency; docs/SERVING.md's cost model).
    Queue depth rides the `serve.queue_depth` registry gauge.
    Compiles one fwd executable per bucket. Disable with
    CXN_BENCH_SERVE=0; CXN_BENCH_SERVE_MAXB bounds the bucket ladder
    (default 32)."""
    if os.environ.get("CXN_BENCH_SERVE") == "0":
        return {}
    try:
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.serve import Server
        tr = ctx.trainer
        batch = ctx.batch
        rng = np.random.RandomState(17)
        data, label = _alexnet_batch(rng, batch)
        db = DataBatch(data, label)
        # batch-at-a-time baseline over the same infer executable:
        # compile + warm, one sizing rep, then a budgeted loop
        tr.predict_dist(db)
        t0 = time.perf_counter()
        tr.predict_dist(db)
        per_rep = max(time.perf_counter() - t0, 1e-6)
        nrep = max(2, min(8, int(20.0 / per_rep)))
        t0 = time.perf_counter()
        for _ in range(nrep):
            tr.predict_dist(db)
        predict_rps = nrep * batch / (time.perf_counter() - t0)
        mb = min(batch,
                 int(os.environ.get("CXN_BENCH_SERVE_MAXB", "32")))
        srv = Server(tr, max_batch=mb, max_wait_ms=2.0, replicas=2)
        t0 = time.perf_counter()
        srv.warmup()
        warm_s = time.perf_counter() - t0
        srv.start()
        # mixed request sizes covering the bucket ladder; total rows
        # sized to ~the baseline loop's traffic so both numbers come
        # from comparable windows
        sizes, total, i = [], 0, 0
        cycle = [1, mb // 2, mb, 3, mb // 4 or 1, mb, 7, mb // 2]
        target = max(2 * batch, nrep * batch // 2)
        while total < target:
            n = max(1, min(cycle[i % len(cycle)], mb))
            sizes.append(n)
            total += n
            i += 1
        reqs = [data[:n] for n in sizes]  # views: staging copies
        t0 = time.perf_counter()
        futs = [srv.submit(r) for r in reqs]
        for f in futs:
            f.result(timeout=600)
        dt = max(time.perf_counter() - t0, 1e-9)
        stats = srv.stop()
        if stats["errors"]:
            return {"serve_error":
                    f"{stats['errors']} dispatch errors"}
        out = {
            "serve_qps": round(len(reqs) / dt, 2),
            "serve_rows_per_s": round(total / dt, 2),
            "serve_p50_ms": stats["latency_p50_ms"],
            "serve_p99_ms": stats["latency_p99_ms"],
            "serve_warmup_s": round(warm_s, 2),
            "serve_buckets": len(srv.buckets),
            "serve_max_batch": mb,
            "serve_requests": len(reqs),
            "serve_padding_rows": stats["padding_rows"],
        }
        if predict_rps > 0:
            out["serve_over_predict"] = round(
                (total / dt) / predict_rps, 4)
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"serve_error": f"{type(e).__name__}: {e}"}


def _bench_serve_storm(ctx) -> dict:
    """Overload behavior of the serving front (docs/SERVING.md
    "Serving over HTTP"): an OPEN-LOOP Poisson load generator - seeded
    exponential inter-arrivals, ragged request sizes - driven at ~2x
    the server's measured sustainable row rate with `queue_limit`
    armed, so the excess MUST be shed rather than queued. The numbers
    that matter under overload: `serve_storm_p99_ms` is the end-to-end
    p99 of the ACCEPTED requests (bounded latency is the whole point
    of shedding - an unbounded queue would show every request slow),
    and `serve_shed_frac` is the shed fraction of offered requests
    (~0.5 at 2x is healthy; ~0 means the storm never exceeded
    capacity, ~1 means admission collapsed). Open-loop matters:
    a closed-loop generator self-throttles when the server slows,
    hiding exactly the overload this measures. Disable with
    CXN_BENCH_SERVE_STORM=0."""
    if os.environ.get("CXN_BENCH_SERVE_STORM") == "0":
        return {}
    try:
        from cxxnet_tpu.serve import QueueFullError, Server
        tr = ctx.trainer
        batch = ctx.batch
        rng = np.random.RandomState(23)
        data, _ = _alexnet_batch(rng, batch)
        mb = min(batch,
                 int(os.environ.get("CXN_BENCH_SERVE_MAXB", "32")))
        # leg 1: closed-loop calibration of the sustainable row rate
        # over the same buckets (no limit, no storm)
        srv = Server(tr, max_batch=mb, max_wait_ms=2.0, replicas=2)
        srv.warmup()
        srv.start()
        cycle = [1, mb // 2, mb, 3, mb // 4 or 1, 7]
        cal_sizes = [max(1, min(s, mb)) for s in cycle * 6]
        t0 = time.perf_counter()
        futs = [srv.submit(data[:n]) for n in cal_sizes]
        for f in futs:
            f.result(timeout=600)
        cal_dt = max(time.perf_counter() - t0, 1e-9)
        cal_stats = srv.stop()
        sustainable_rows = sum(cal_sizes) / cal_dt
        # leg 2: the storm - offered load 2x sustainable, hard
        # queue_limit of ~4 buckets of backlog
        limit = 4 * mb
        srv = Server(tr, max_batch=mb, max_wait_ms=2.0, replicas=2,
                     queue_limit=limit)
        srv.warmup()
        srv.start()
        offered_rows = 2.0 * sustainable_rows
        mean_size = sum(cal_sizes) / len(cal_sizes)
        n_req = max(60, int(os.environ.get(
            "CXN_BENCH_STORM_REQS", "120")))
        gaps = rng.exponential(mean_size / offered_rows, n_req)
        sizes = [max(1, min(int(rng.choice(cycle)), mb))
                 for _ in range(n_req)]
        arrivals = np.cumsum(gaps)
        live, shed = [], 0
        t_start = time.perf_counter()
        for i in range(n_req):
            # open loop: sleep the Poisson gap regardless of how the
            # server is doing, then offer the request
            target = t_start + float(arrivals[i])
            pause = target - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            t_sub = time.perf_counter()
            try:
                live.append((srv.submit(data[:sizes[i]]), t_sub))
            except QueueFullError:
                shed += 1
        lat_ms = []
        for f, t_sub in live:
            f.result(timeout=600)
            lat_ms.append((time.perf_counter() - t_sub) * 1e3)
        stats = srv.stop()
        if stats["errors"]:
            return {"serve_storm_error":
                    f"{stats['errors']} dispatch errors"}
        lat_ms.sort()
        p99 = lat_ms[min(len(lat_ms) - 1,
                         int(0.99 * len(lat_ms)))] if lat_ms else 0.0
        return {
            "serve_storm_p99_ms": round(p99, 2),
            "serve_shed_frac": round(shed / max(n_req, 1), 4),
            "serve_storm_accepted": len(live),
            "serve_storm_offered": n_req,
            "serve_storm_offered_rows_per_s": round(offered_rows, 2),
            "serve_storm_sustainable_rows_per_s": round(
                sustainable_rows, 2),
            "serve_storm_queue_limit": limit,
            "serve_uncontended_p99_ms": cal_stats["latency_p99_ms"],
        }
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"serve_storm_error": f"{type(e).__name__}: {e}"}


def _bench_canary_swap(ctx) -> dict:
    """Cost of a canaried rollout (docs/SERVING.md "Canary runbook"):
    requests served WHILE a canary is active go through the same
    warmed bucket executables as steady state (the candidate is just
    a second params argument binding), so `serve_canary_p99_ms`
    should sit on top of the uncontended serve p99 - a gap means the
    judge's shadow dispatches or the routing split are stealing
    device time. `serve_canary_promote_lag_ms` is the judge's
    overhead beyond the configured window: how long after the window
    closes the promote actually lands. The candidate is the
    incumbent's own checkpoint (agreement 1.0 - promote guaranteed);
    this prices the machinery, not the model. Disable with
    CXN_BENCH_SERVE_CANARY=0."""
    if os.environ.get("CXN_BENCH_SERVE_CANARY") == "0":
        return {}
    try:
        import tempfile

        from cxxnet_tpu.serve import Server
        tr = ctx.trainer
        batch = ctx.batch
        rng = np.random.RandomState(27)
        data, _ = _alexnet_batch(rng, batch)
        mb = min(batch,
                 int(os.environ.get("CXN_BENCH_SERVE_MAXB", "32")))
        window_s = 0.6
        srv = Server(tr, max_batch=mb, max_wait_ms=2.0, replicas=2,
                     canary_frac=0.5, canary_window=window_s)
        srv.warmup()
        n_warm = srv.executable_cache_size()
        srv.start()
        with tempfile.TemporaryDirectory(
                prefix="bench_canary_") as d:
            ck = os.path.join(d, "cand.model")
            with open(ck, "wb") as f:
                tr.save_model(f)
            t_pub = time.perf_counter()
            if not srv.swap_to(ck):
                srv.stop()
                return {"serve_canary_error": "swap_to refused"}
            cycle = [1, mb // 2, mb, 3, mb // 4 or 1, 7]
            lat_ms = []
            # closed-loop probes for the whole canary lifetime: every
            # request lands on one side of the split or the other
            while srv.stats()["canary_active"]:
                n = max(1, min(int(rng.choice(cycle)), mb))
                t_sub = time.perf_counter()
                srv.submit(data[:n]).result(timeout=600)
                lat_ms.append((time.perf_counter() - t_sub) * 1e3)
            promote_lag_ms = (time.perf_counter() - t_pub
                              - window_s) * 1e3
            stats = srv.stats()
            flat = srv.executable_cache_size() == n_warm
            srv.stop()
        if stats["canary_promoted"] != 1:
            return {"serve_canary_error":
                    f"verdict was not promote: {stats}"}
        lat_ms.sort()
        p99 = lat_ms[min(len(lat_ms) - 1,
                         int(0.99 * len(lat_ms)))] if lat_ms else 0.0
        return {
            "serve_canary_p99_ms": round(p99, 2),
            "serve_canary_promote_lag_ms": round(promote_lag_ms, 1),
            "serve_canary_requests": stats["canary_requests"],
            "serve_canary_probes": len(lat_ms),
            "serve_canary_cache_flat": flat,
        }
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"serve_canary_error": f"{type(e).__name__}: {e}"}


_BN_CONVNET_CONF = """
netconfig=start
layer[+1:c1] = conv:c1
  nchannel = 24
  kernel_size = 3
  pad = 1
layer[+1:b1] = batch_norm:b1
layer[+1:r1] = relu
layer[+1:p1] = max_pooling
  kernel_size = 2
  stride = 2
layer[+1:c2] = conv:c2
  nchannel = 32
  kernel_size = 3
  pad = 1
layer[+1:b2] = batch_norm:b2
layer[+1:r2] = relu
layer[+1:p2] = max_pooling
  kernel_size = 2
  stride = 2
layer[+1:fl] = flatten
layer[+1:fc] = fullc:fc
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 3,48,48
eta = 0.1
silent = 1
seed = 19
"""

def _bench_fold(ctx) -> dict:
    """Inference with the conv+bn folding graph pass
    (graph_passes=fold_conv_bn,dead_layer_elim - nnet/passes.py,
    docs/GRAPH_PASSES.md) vs the unfolded graph, on a bn-heavy
    convnet (AlexNet has LRN, not BN, so the flagship can't carry
    this field): the SAME predict_dist loop over the SAME images in
    the same window, so the derived `fold_over_infer` prices exactly
    what the fold removes - the per-batch moment/variance pipeline
    and the normalize pass over every BN activation. >1.0 = folding
    won; the fold leg calibrates once on the first batch (included
    in warmup, not the timed window - warmup cost is the one-time
    calibration executable). Two small compiles. Disable with
    CXN_BENCH_FOLD=0."""
    if os.environ.get("CXN_BENCH_FOLD") == "0":
        return {}
    try:
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.nnet.trainer import NetTrainer
        from cxxnet_tpu.utils.config import parse_config_string
        batch = ctx.batch

        def build(extra=""):
            tr = NetTrainer()
            for k, v in parse_config_string(
                    _BN_CONVNET_CONF + f"batch_size = {batch}\n"
                    + extra):
                tr.set_param(k, v)
            tr.init_model()
            return tr

        rng = np.random.RandomState(31)
        db = DataBatch(
            data=rng.rand(batch, 3, 48, 48).astype(np.float32),
            label=rng.randint(0, 10, (batch, 1)).astype(np.float32))

        def ips_of(tr, budget_s=20.0):
            tr.predict_dist(db)  # compile (+ fold calibration)
            t0 = time.perf_counter()
            tr.predict_dist(db)
            per = max(time.perf_counter() - t0, 1e-6)
            n = max(3, min(64, int(budget_s / per)))
            t0 = time.perf_counter()
            for _ in range(n):
                tr.predict_dist(db)
            return n * batch / (time.perf_counter() - t0), n

        unfolded, n1 = ips_of(build())
        folded, n2 = ips_of(build(
            "graph_passes = fold_conv_bn,dead_layer_elim\n"))
        out = {"fold_infer_ips": round(folded, 2),
               "fold_unfolded_ips": round(unfolded, 2),
               "fold_steps": n1 + n2}
        if unfolded > 0:
            out["fold_over_infer"] = round(folded / unfolded, 4)
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"fold_error": f"{type(e).__name__}: {e}"}


# int8 PTQ workload: a weight-bound wide-fullc MLP at a SERVING-shaped
# small batch - the regime the quantize_int8 pass exists for
# (docs/GRAPH_PASSES.md "when int8 loses": large batches go
# compute-bound and int8's extra quant/dequant work outweighs the
# weight-bandwidth saving; measured on this container's XLA:CPU the
# crossover sits between batch 16 and 64)
_INT8_MLP_CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 2048
  init_sigma = 0.05
layer[+1:bn1] = batch_norm:bn1
layer[+1:r1] = relu
layer[+1:fc2] = fullc:fc2
  nhidden = 2048
  init_sigma = 0.05
layer[+1:bn2] = batch_norm:bn2
layer[+1:r2] = relu
layer[+1:fc3] = fullc:fc3
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,512
dev = cpu
eta = 0.1
silent = 1
seed = 19
"""

# fixed serving-shaped batch for the int8 pair: ctx.batch is the
# TRAINING workload size; quantized inference's claim is the
# small-batch weight-bound serving regime
_INT8_BATCH = 16


def _bench_int8(ctx) -> dict:
    """Int8 post-training-quantized inference (quantize_int8 pass +
    ops/int8.py kernels - docs/GRAPH_PASSES.md "Quantization") vs the
    folded-float pipeline, on a weight-bound wide-fullc MLP at a
    serving-shaped batch: the SAME predict_dist loop over the SAME
    rows in the same window, so `int8_over_fold` prices exactly what
    quantization changes - int8 weight traffic + MXU/VNNI-rate
    contraction against the extra quantize/dequantize elementwise
    work. >1.0 = int8 won. The speed claim ships with its accuracy
    cost: `int8_argmax_agree` is the fraction of a fixed 256-row
    synthetic eval set where the quantized argmax matches the float
    one (1.0 = no prediction changed). Calibration (one batch)
    happens in warmup, outside the timed window, like the fold leg.
    Disable with CXN_BENCH_INT8=0."""
    if os.environ.get("CXN_BENCH_INT8") == "0":
        return {}
    try:
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.nnet.trainer import NetTrainer
        from cxxnet_tpu.utils.config import parse_config_string
        batch = _INT8_BATCH

        def build(extra=""):
            tr = NetTrainer()
            for k, v in parse_config_string(
                    _INT8_MLP_CONF + f"batch_size = {batch}\n"
                    "graph_passes = dead_layer_elim,fold_conv_bn,"
                    "fuse_activation" + extra):
                tr.set_param(k, v)
            tr.init_model()
            return tr

        rng = np.random.RandomState(41)
        db = DataBatch(
            data=rng.rand(batch, 1, 1, 512).astype(np.float32),
            label=rng.randint(0, 10, (batch, 1)).astype(np.float32))

        def ips_of(tr, budget_s=20.0):
            tr.predict_dist(db)  # compile (+ calibration)
            t0 = time.perf_counter()
            tr.predict_dist(db)
            per = max(time.perf_counter() - t0, 1e-6)
            n = max(3, min(256, int(budget_s / per)))
            t0 = time.perf_counter()
            for _ in range(n):
                tr.predict_dist(db)
            return n * batch / (time.perf_counter() - t0), n

        fold_tr, int8_tr = build(), build(",quantize_int8")
        folded, n1 = ips_of(fold_tr)
        int8, n2 = ips_of(int8_tr)
        # accuracy delta on a fixed held-out set (same weights, same
        # rows): argmax agreement between the two inference paths
        agree = total = 0
        for i in range(256 // batch):
            r = np.random.RandomState(900 + i)
            eb = DataBatch(
                data=r.rand(batch, 1, 1, 512).astype(np.float32),
                label=r.randint(0, 10, (batch, 1)).astype(np.float32))
            pf = fold_tr.predict_dist(eb).argmax(axis=1)
            pq = int8_tr.predict_dist(eb).argmax(axis=1)
            agree += int((pf == pq).sum())
            total += batch
        out = {"int8_infer_ips": round(int8, 2),
               "int8_fold_ips": round(folded, 2),
               "int8_batch": batch,
               "int8_steps": n1 + n2,
               "int8_argmax_agree": round(agree / max(total, 1), 4)}
        if folded > 0:
            out["int8_over_fold"] = round(int8 / folded, 4)
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"int8_error": f"{type(e).__name__}: {e}"}


def _bench_plan(ctx) -> dict:
    """The PER-LAYER autotuner's value proposition, measured
    (schema-v2 tuning_cache, docs/GRAPH_PASSES.md "per-layer
    autotuner"): run tools/autotune.py's bounded greedy per-layer
    search on the bf16 BN-convnet (autocast pass armed, so
    `layer_dtype` flips feed the dtype plan - on hosts without fast
    bf16 conv the per-layer f32 pins are a real win), persist the
    plan as a v2 cache, and drive the SAME predict loop with the
    plan replayed via `tuning_cache =` vs defaults in the same
    window. `plan_over_default` is the ratio the per-layer plan buys
    over global defaults; the plan itself lands in `plan_layers` so
    the artifact doubles as tuning evidence. Disable with
    CXN_BENCH_PLAN=0; CXN_BENCH_PLAN_SECS bounds the search
    (default 20)."""
    if os.environ.get("CXN_BENCH_PLAN") == "0":
        return {}
    try:
        import shutil
        import tempfile

        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.nnet import tuning
        from cxxnet_tpu.nnet.trainer import NetTrainer
        from cxxnet_tpu.tools import autotune
        from cxxnet_tpu.utils.config import parse_config_string
        batch = ctx.batch
        pairs = parse_config_string(
            _BN_CONVNET_CONF + f"batch_size = {batch}\n"
            "dtype = bfloat16\ngraph_passes = autocast\n")
        budget = float(os.environ.get("CXN_BENCH_PLAN_SECS", "20"))
        pl = autotune.per_layer_search(pairs, budget)
        d = tempfile.mkdtemp(prefix="cxn_bench_plan_")
        try:
            cache = os.path.join(d, "plan.json")
            tuning.save_entry(cache, jax.default_backend(), {},
                              layers=pl["layers"])

            def build(extra=()):
                tr = NetTrainer()
                for k, v in list(pairs) + list(extra):
                    tr.set_param(k, v)
                tr.init_model()
                return tr

            rng = np.random.RandomState(37)
            db = DataBatch(
                data=rng.rand(batch, 3, 48, 48).astype(np.float32),
                label=rng.randint(0, 10, (batch, 1))
                .astype(np.float32))

            def ips_of(tr, budget_s=10.0):
                tr.predict_dist(db)  # compile + warm
                t0 = time.perf_counter()
                tr.predict_dist(db)
                per = max(time.perf_counter() - t0, 1e-6)
                n = max(3, min(64, int(budget_s / per)))
                t0 = time.perf_counter()
                for _ in range(n):
                    tr.predict_dist(db)
                return n * batch / (time.perf_counter() - t0)

            default_ips = ips_of(build())
            tuned_ips = ips_of(build([("tuning_cache", cache)]))
        finally:
            shutil.rmtree(d, ignore_errors=True)
        out = {"plan_tuned_ips": round(tuned_ips, 2),
               "plan_default_ips": round(default_ips, 2),
               "plan_layers": pl["layers"]}
        if default_ips > 0:
            out["plan_over_default"] = round(
                tuned_ips / default_ips, 4)
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"plan_error": f"{type(e).__name__}: {e}"}


def _bench_autotune(ctx) -> dict:
    """The TVM-style autotuner's own value proposition, measured:
    run the bounded (steps_per_dispatch x prefetch_stage) search of
    tools/autotune.py on its dispatch-bound default workload and
    report the best cell (`autotune_best_ips`) against the shipped
    defaults' cell in the SAME window (`tuned_over_default` - the
    ratio a `tuning_cache =` pickup buys on this host). The serving
    ladder is skipped here (the serve family already prices bucket
    choice); the knob dict itself lands in `autotune_best` so a
    bench artifact doubles as tuning evidence. Disable with
    CXN_BENCH_AUTOTUNE=0; CXN_BENCH_AUTOTUNE_SECS bounds the search
    (default 30)."""
    if os.environ.get("CXN_BENCH_AUTOTUNE") == "0":
        return {}
    try:
        from cxxnet_tpu.tools import autotune
        from cxxnet_tpu.utils.config import parse_config_string
        budget = float(os.environ.get("CXN_BENCH_AUTOTUNE_SECS",
                                      "30"))
        pairs = parse_config_string(autotune._DEFAULT_CONF)
        # per_layer=False: the MLP workload has no per-layer
        # candidates, and the plan family has its own field
        # (_bench_plan's plan_over_default on the BN-convnet)
        res = autotune.search(pairs, budget, serve=False,
                              per_layer=False)
        m = res["measured"]
        out = {"autotune_best_ips": m["best_ips"],
               "autotune_best": {k: v for k, v
                                 in res["knobs"].items()},
               "autotune_grid": m["grid"]}
        if m.get("default_ips"):
            out["autotune_default_ips"] = m["default_ips"]
            out["tuned_over_default"] = round(
                m["best_ips"] / m["default_ips"], 4)
        return out
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"autotune_error": f"{type(e).__name__}: {e}"}


def _bench_pool_ties(make, batch, steps, platform: str) -> dict:
    """Compute-path throughput with `pool_grad = ties` (the reference's
    tie-duplicating max-pool backward) vs the bench flagship's
    `winner` default - the measured cost of exact mshadow tie parity.
    The tie backward is the separable two-stage unpool
    (ops/pooling.py: ~2*ceil(k/s) half-size passes, 4 vs 9 for the
    AlexNet pools), not measured on this installation: THIS field is
    the defaults decision (ROADMAP D4) - if ties meets winner, parity
    becomes the flagship config too. One extra compile; TPU only.
    Disable with CXN_BENCH_POOLTIES=0."""
    if platform != "tpu" or os.environ.get("CXN_BENCH_POOLTIES") == "0":
        return {}
    try:
        tr = make(0, [("pool_grad", "ties")])
        return {"compute_poolties_ips":
                round(_measure_compute(tr, batch, steps), 2)}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"pool_ties_error": f"{type(e).__name__}: {e}"}


def _bench_eval_train(make, batch, steps) -> dict:
    """eval_train=1 (the reference's default mode): the conf's metric
    lines (error, rec@1, rec@5) compile into the step as device-side
    accumulators. Needs a SECOND full AlexNet compile, which is why it
    runs after the other throughput extras - if the watchdog budget
    dies here, every headline and extra before it is already
    snapshotted (only the profiler fetch, which needs no compile,
    comes later). Disable with CXN_BENCH_EVALTRAIN=0."""
    if os.environ.get("CXN_BENCH_EVALTRAIN") == "0":
        return {}
    try:
        trainer_m = make(1)
        ips, n = _measure_e2e(trainer_m, batch, steps)
        return {"e2e_eval_train_ips": round(ips, 2),
                "eval_train_steps": n}
    except Exception as e:  # noqa: BLE001 - never kill the headline
        return {"eval_train_error": f"{type(e).__name__}: {e}"}


def _flagship_overrides(batch, eval_train, extra=()):
    """The ONE source of the flagship bench config - every trainer the
    bench builds (headline, eval_train, pool_ties, device_augment)
    derives from this list so the numbers stay comparable.
    pool_grad=winner is the flagship default; what the reference's
    tie-duplicating max-pool backward costs against it is
    compute_poolties_ips (ROADMAP D4);
    FIRST in the list so an explicit extra still overrides it (later
    set_param wins)."""
    return [("pool_grad", "winner"),
            ("batch_size", str(batch)), ("dev", "tpu"), ("silent", "1"),
            ("eval_train", str(eval_train)), ("save_model", "0"),
            *extra]


class _Ctx:
    """Everything a measurement needs, built lazily and shared by all
    of them, so each AlexNet variant compiles once per process."""

    def __init__(self, batch, steps, platform, profile_dir=""):
        self.batch, self.steps = batch, steps
        self.platform, self.profile_dir = platform, profile_dir
        self._trainers = {}

    def make(self, eval_train, extra=()):
        key = (eval_train, tuple(extra))
        if key not in self._trainers:
            from __graft_entry__ import _ALEXNET_CONF, _make_trainer
            from cxxnet_tpu.utils.config import parse_config_file
            self._trainers[key] = _make_trainer(
                parse_config_file(_ALEXNET_CONF),
                _flagship_overrides(self.batch, eval_train, extra))
        return self._trainers[key]

    @property
    def trainer(self):
        return self.make(0)


def _m_e2e(ctx) -> dict:
    """Headline: full trainer.update() loop + a host-link probe
    (h2d_mbps: one timed ~20 MB f32 device_put before the warmup, so
    the artifact records what the host->device link sustained)."""
    out = {}
    if ctx.platform == "tpu":
        try:
            probe = np.ones((min(ctx.batch, 32), 3, 227, 227),
                            np.float32)
            t0 = time.perf_counter()
            d = jax.block_until_ready(jax.device_put(probe))
            dt = max(time.perf_counter() - t0, 1e-9)
            out["h2d_mbps"] = round(probe.nbytes / 1e6 / dt, 1)
            del d, probe
        except Exception as e:  # noqa: BLE001 - probe is best-effort
            out["h2d_probe_error"] = f"{type(e).__name__}: {e}"
    ips, n = _measure_e2e(ctx.trainer, ctx.batch, ctx.steps,
                          ctx.profile_dir)
    out["e2e_ips"] = round(ips, 2)
    out["e2e_steps"] = n
    return out


def _m_compute(ctx) -> dict:
    out = {"compute_ips": round(
        _measure_compute(ctx.trainer, ctx.batch, ctx.steps), 2)}
    try:
        # HBM high-water mark after a full train step - the parity
        # datum for the reference's ">3 GB GPU memory at batch 256"
        # claim (example/ImageNet/README.md:7-10). memory_stats is
        # client metadata, not a buffer transfer; absent on backends
        # that don't expose it.
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak:
            out["hbm_peak_gb"] = round(peak / 2 ** 30, 2)
    except Exception:  # noqa: BLE001 - metadata only, never the number
        pass
    return out


# (name, fn(ctx) -> fragment, gate env var or ""). ORDER: the headline
# pair and the chip-critical numbers before the nice-to-have extras,
# so a watchdog cut truncates from the tail.
_MEASUREMENTS = (
    ("e2e", _m_e2e, ""),
    ("compute", _m_compute, ""),
    ("pool_ties",
     lambda c: _bench_pool_ties(c.make, c.batch, c.steps, c.platform),
     "CXN_BENCH_POOLTIES"),
    ("googlenet",
     lambda c: _bench_googlenet(c.batch, c.steps, c.platform),
     "CXN_BENCH_GOOGLENET"),
    ("device_data", _bench_device_data, "CXN_BENCH_DEVDATA"),
    ("e2e_prefetch", _bench_prefetch, "CXN_BENCH_PREFETCH"),
    ("fused", _bench_fused, "CXN_BENCH_FUSED"),
    ("zero", _bench_zero, "CXN_BENCH_ZERO"),
    ("serve", _bench_serve, "CXN_BENCH_SERVE"),
    ("serve_storm", _bench_serve_storm, "CXN_BENCH_SERVE_STORM"),
    ("canary_swap", _bench_canary_swap, "CXN_BENCH_SERVE_CANARY"),
    ("fold", _bench_fold, "CXN_BENCH_FOLD"),
    ("int8", _bench_int8, "CXN_BENCH_INT8"),
    ("autotune", _bench_autotune, "CXN_BENCH_AUTOTUNE"),
    ("plan", _bench_plan, "CXN_BENCH_PLAN"),
    ("attention",
     lambda c: _bench_attention(c.platform), "CXN_BENCH_ATTN"),
    ("top_ops",
     lambda c: _bench_top_ops(c.trainer, c.batch, c.platform),
     "CXN_BENCH_PROFILE"),
    ("device_augment",
     lambda c: _bench_device_augment(c.batch, c.steps, c.platform),
     "CXN_BENCH_DAUG"),
    ("stage_f32",
     lambda c: _bench_stage_f32(c.trainer, c.batch, c.steps, c.platform),
     "CXN_BENCH_STAGEF32"),
    ("chip_matmul",
     lambda c: _bench_chip_matmul(c.platform), "CXN_BENCH_MATMUL"),
    ("input_split",
     lambda c: _bench_input_split(c.trainer, c.batch, c.platform),
     "CXN_BENCH_SPLIT"),
    ("eval_train",
     lambda c: _bench_eval_train(c.make, c.batch, c.steps),
     "CXN_BENCH_EVALTRAIN"),
    # truly last: a nice-to-have third family must never cost an
    # established field (chip_matmul anchors mfu_pct) its window budget
    ("resnet18",
     lambda c: _bench_resnet(c.batch, c.steps, c.platform),
     "CXN_BENCH_RESNET"),
)

# execution order, DERIVED from the registry so a new measurement can
# never be silently skipped: compute first (cheapest number to land -
# the snapshot discipline), the profiler trace last (tracing slows the
# host), registry order otherwise.
_INLINE_ORDER = tuple(
    ["compute"]
    + [m[0] for m in _MEASUREMENTS if m[0] not in ("compute", "top_ops")]
    + ["top_ops"])


def _derive(out: dict, batch: int, platform: str, ndev: int,
            peak_tflops: float) -> None:
    """(Re)compute the headline + derived fields from whatever raw
    numbers are present - called after every fragment merge so the
    snapshot always carries a correctly-labeled best-so-far."""
    comp, e2e = out.get("compute_ips"), out.get("e2e_ips")
    fused, zero = out.get("e2e_fused_ips"), out.get("zero2_ips")
    if fused and e2e:
        # the K>1 vs K=1 ratio: what fusing K steps into one dispatch
        # buys over the per-step e2e path (>1 = dispatch overhead was
        # a real cost in this window)
        out["fused_over_e2e"] = round(fused / e2e, 4)
    if zero and e2e:
        # ZeRO-2 vs replicated update: >1 = the sharded update's FLOP/
        # HBM saving beat its extra gather latency in this window
        out["zero_over_e2e"] = round(zero / e2e, 4)
    if e2e:
        out["metric"] = "alexnet_b%d_%s_train_e2e" % (batch, platform)
        out["value"], out["value_is"] = e2e, "e2e"
        out["vs_baseline"] = round(e2e / A100_IMAGES_PER_SEC, 4)
        out["achieved_tflops"] = round(
            e2e * ALEXNET_TRAIN_GFLOP_PER_IMG / 1e3, 2)
        if comp:
            out["e2e_over_compute"] = round(e2e / comp, 4)
        if peak_tflops:
            out["peak_tflops"] = peak_tflops
            out["mfu_pct"] = round(
                100.0 * out["achieved_tflops"] / (peak_tflops * ndev), 2)
    elif comp:
        out["metric"] = "alexnet_b%d_%s_train_compute" % (batch, platform)
        out["value"], out["value_is"] = comp, "compute_only"
        out["vs_baseline"] = round(comp / A100_IMAGES_PER_SEC, 4)


def run(profile_dir="", steps_override=0, batch_override=0) -> dict:
    """Every measurement, inline, in this one process. Callable on any
    backend (the test suite drives it at a tiny batch on the CPU as a
    harness smoke); `python bench.py` itself refuses to start without
    a TPU (main) - what this returns elsewhere is not a device
    number."""
    from cxxnet_tpu.utils.platform import setup_compile_cache
    devices = jax.devices()
    platform = devices[0].platform
    setup_compile_cache()
    ndev = len(devices)
    kind = devices[0].device_kind
    # an unknown TPU is an error (_peak_for); a non-TPU backend has no
    # peak to hold mfu_pct against
    peak_tflops = _peak_for(kind) if platform == "tpu" else 0.0
    batch, steps = batch_override or 256, steps_override or 50

    out = {
        "metric": "alexnet_b%d_%s_train_e2e" % (batch, platform),
        "unit": "images/sec",
        "platform": platform,
        "device_count": ndev,
        "device_kind": kind,
        "per_device_batch": batch // ndev,
        "steps": steps,
        # flagship config choice, stated in the artifact: industry-
        # standard single-winner max-pool backward (the reference tie
        # rule is the opt-in; compute_poolties_ips prices it)
        "pool_grad": "winner",
    }
    _snapshot(out)

    gates_off = {m[0] for m in _MEASUREMENTS
                 if m[2] and os.environ.get(m[2]) == "0"}
    ctx = _Ctx(batch, steps, platform, profile_dir)
    specs = {m[0]: m for m in _MEASUREMENTS}
    for name in _INLINE_ORDER:
        if name in gates_off:
            continue
        # compute/e2e are the headline: exceptions propagate (the
        # main() snapshot/error paths own that contract); extras
        # leave *_error fields from inside their own bodies, so the
        # later measurements still run (main() then exits 1)
        out.update(specs[name][1](ctx))
        _derive(out, batch, platform, ndev, peak_tflops)
        _snapshot(out)
    _measure_graftlint(out)
    _measure_obs(out)
    _measure_lock_audit(out)
    _finalize(out)
    _snapshot(out)
    return out


def _measure_graftlint(out: dict) -> None:
    """Wall-time of the tier-1 static-analysis pass over the full
    package tree (docs/STATIC_ANALYSIS.md) - the analysis itself gets
    a perf trajectory, with a < 10 s CI budget the blocking job
    enforces (--max-seconds). Guarded like every extra: a failure
    degrades to graftlint_error, never kills the headline."""
    try:
        from cxxnet_tpu.analysis.astlint import lint_paths
        pkg = os.path.join(_REPO, "cxxnet_tpu")
        findings, n_files, elapsed = lint_paths([pkg])
        out["graftlint_s"] = round(elapsed, 3)
        out["graftlint_files"] = n_files
        out["graftlint_unwaived"] = sum(
            1 for f in findings if not f.waived)
        out["graftlint_budget_s"] = 10.0
    except Exception as e:  # noqa: BLE001 - extras must not kill bench
        out["graftlint_error"] = f"{type(e).__name__}: {e}"


def _measure_obs(out: dict) -> None:
    """Cost of the live observability plane's exposition path
    (docs/OBSERVABILITY.md): Prometheus render time over a
    realistically populated registry, and one localhost /metrics
    scrape round trip through the stdlib HTTP server - the per-scrape
    tax a metrics_port= run pays, which must stay far below any sane
    scrape interval. Guarded like every extra."""
    try:
        from cxxnet_tpu import telemetry
        from cxxnet_tpu.telemetry.http import (
            ObservabilityServer, render_prometheus, validate_exposition)
        tel = telemetry.Telemetry()
        # ~the instrument population of a long training run: a few
        # dozen series incl. full histogram windows
        for i in range(24):
            h = tel.histogram(f"bench.h{i:02d}_s")
            for k in range(512):
                h.observe((k % 97) * 1e-4)
        for i in range(24):
            tel.inc(f"bench.c{i:02d}", i * 7)
            tel.set_gauge(f"bench.g{i:02d}", i * 0.5)
        t0 = time.monotonic()
        n_render = 20
        for _ in range(n_render):
            text = render_prometheus(tel)
        out["obs_render_ms"] = round(
            (time.monotonic() - t0) / n_render * 1e3, 3)
        if validate_exposition(text):
            out["obs_error"] = "render produced malformed exposition"
            return
        import urllib.request
        srv = ObservabilityServer(tel, 0, host="127.0.0.1").start()
        try:
            url = f"http://127.0.0.1:{srv.port}/metrics"
            scrapes = []
            for _ in range(10):
                t0 = time.monotonic()
                with urllib.request.urlopen(url, timeout=5.0) as r:
                    r.read()
                scrapes.append(time.monotonic() - t0)
            scrapes.sort()
            out["obs_scrape_ms"] = round(
                scrapes[len(scrapes) // 2] * 1e3, 3)
        finally:
            srv.close()
    except Exception as e:  # noqa: BLE001 - extras must not kill bench
        out["obs_error"] = f"{type(e).__name__}: {e}"


def _measure_lock_audit(out: dict) -> None:
    """Wall-time + worst held-duration of the runtime lock audit's
    jax-free scenarios (docs/STATIC_ANALYSIS.md "Concurrency
    analysis") - the concurrency gate gets a perf trajectory like
    graftlint_s, and the contention gauges (`lock.audit.*`) land in
    the telemetry registry as a side effect. The serve-storm scenario
    stays in CI only: it rebuilds a trainer, which would perturb the
    bench window. Guarded like every extra."""
    try:
        from cxxnet_tpu.analysis.lock_audit import run_lock_audit
        rep = run_lock_audit(
            scenarios=("prefetch-round", "watchdog-stall"))
        out["lock_audit_s"] = rep["elapsed_s"]
        out["lock_max_held_ms"] = rep["max_held_ms"]
        if rep["failed"]:
            out["lock_audit_failed"] = rep["failed"]
    except Exception as e:  # noqa: BLE001 - extras must not kill bench
        out["lock_audit_error"] = f"{type(e).__name__}: {e}"


def _finalize(out: dict) -> None:
    """run()'s tail: label an all-failed artifact."""
    if "value" not in out:
        # every measurement failed: the metric name still says "e2e",
        # so the zero must be self-describing (value_is=none), not
        # readable as an e2e result of 0
        out.update(value=0.0, vs_baseline=0.0, value_is="none")


def _error_json(msg: str) -> str:
    return json.dumps({"metric": "alexnet_train_e2e", "value": 0.0,
                       "unit": "images/sec", "vs_baseline": 0.0,
                       "error": msg})


def main(argv) -> int:
    """0 only for a complete run on a TPU with no failed measurement.
    No TPU, bad arguments, a crash, a watchdog cut, or any `*_error`
    field in the artifact all exit 1 - after printing what was
    measured, if anything was."""
    try:
        profile_dir = ""
        steps = batch = 0
        if "--profile" in argv:
            profile_dir = argv[argv.index("--profile") + 1]
        if "--steps" in argv:
            steps = int(argv[argv.index("--steps") + 1])
        if "--batch" in argv:
            batch = int(argv[argv.index("--batch") + 1])
        budget = int(os.environ.get("CXN_BENCH_TIMEOUT", "480"))
    except (IndexError, ValueError) as e:
        print(_error_json(f"bad arguments {argv}: {e}"))
        return 1

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            "bench.py measures on a TPU and JAX found none: the "
            f"platform is '{dev.platform}' ({len(jax.devices())} x "
            f"{dev.device_kind}). A CPU run can state counts, never a "
            "rate - there is no CPU fallback.\n")
        return 1

    def _emit_partial(reason: str) -> bool:
        # caller holds _EMIT_LOCK and has claimed "emitted"
        if not _PARTIAL.get("value"):
            return False
        _PARTIAL["truncated"] = reason
        print(json.dumps({k: v for k, v in _PARTIAL.items()
                          if k != "emitted"}), flush=True)
        return True

    def watchdog():
        # a dispatch wedged inside the runtime blocks in C where no
        # Python signal is delivered - escaping from a daemon thread
        # is the only reliable move
        with _EMIT_LOCK:
            if _PARTIAL.get("emitted"):
                return  # main thread already printed the full result
            _PARTIAL["emitted"] = True
            if not _emit_partial(f"cut at the {budget}s watchdog"):
                print(_error_json(f"benchmark exceeded {budget}s"),
                      flush=True)
        os._exit(1)

    if budget > 0:
        t = threading.Timer(budget, watchdog)
        t.daemon = True
        t.start()
    try:
        out = run(profile_dir, steps, batch)
        # claim the single JSON line under the lock: a timer firing in
        # this window must neither double-print nor mislabel a full
        # run as truncated
        with _EMIT_LOCK:
            if _PARTIAL.get("emitted"):
                return 1  # watchdog already printed the partial line
            _PARTIAL["emitted"] = True
    except BaseException as e:  # noqa: BLE001 - print what was measured, then fail
        # a CRASH after a completed measurement emits the snapshot
        # before the non-zero exit; claim the line under the lock so a
        # concurrently-firing watchdog cannot double-print
        with _EMIT_LOCK:
            if _PARTIAL.get("emitted"):
                return 1
            _PARTIAL["emitted"] = True
            if not _emit_partial(
                    f"crashed mid-run: {type(e).__name__}: {e}"):
                print(_error_json(f"{type(e).__name__}: {e}"))
        if not isinstance(e, Exception):
            raise  # KeyboardInterrupt / SystemExit keep their meaning
        return 1
    finally:
        if budget > 0:
            t.cancel()
    print(json.dumps(out))
    # an extra that failed kept the run alive (its *_error field is in
    # the artifact just printed) but the run is not clean: a Mosaic
    # refusal in one kernel must not exit 0
    failed = sorted(k for k in out if k.endswith("_error"))
    if failed or out.get("value_is") == "none":
        sys.stderr.write("bench.py: %d measurement(s) failed: %s\n"
                         % (len(failed), ", ".join(failed)))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
