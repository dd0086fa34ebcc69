#!/usr/bin/env python3
"""chip_smoke: does the system still start on the chip?

One process drives the main path once, through the entry points a user
calls, on `examples/ImageNet/AlexNet.conf` as committed (3x227x227,
batch 256, bfloat16, imgbin + threadbuffer, rand_crop / rand_mirror /
mean image) with weights from a seed and a synthetic imgbin written
from a seed into the output directory:

  train    `cxxnet_tpu.main` task=train, num_round=2 (4 steps a round):
           finite loss, a train-error line per round, weights changed,
           a valid 0002.model, no compile in round 2, and the Pallas
           LRN kernels in the compiled step - on a one-chip mesh
           whatever the host's device count.
  serve    task=pred then task=serve on that checkpoint over the eval
           imgbin: one prediction per row, the two files identical,
           at least two bucket sizes dispatched, no compile after
           warmup().
  kernel   each Pallas kernel (LRN, flash attention, int8 matmul)
           compiled with interpret=False at shapes its layer uses and
           compared with its float32 jax.numpy reference.
  lm       one `kda` layer at the Kimi conf's widths through both
           routes of its chunk-local part (the Pallas kernel pair the
           chip takes, the XLA code everything else takes): outputs
           and gradients agree; one `gconv` layer at the LFM2 conf's
           widths in bf16 against float32; then task=train over
           examples/LongSeq/kimi_linear_5l.conf, three steps, a
           falling loss.
  four     with >= 4 devices: the train leg again on `dev = tpu:0-3` -
           mesh of 4, shards on 4 distinct devices, memory in use on
           all 4, gradient all-reduce + shard_map LRN in the step,
           losses agreeing with the one-chip leg.

It refuses to start unless `jax.devices()[0].platform == "tpu"`, and
any failed check raises: no leg's failure becomes a field. The last
stdout line is `{"ok": ..., "device": {"platform", "kind", "count"}}`
with the device as JAX reports it - those keys and no others (the
driver's contract); the line before it, `[chip_smoke] summary {...}`,
carries each leg's pass/skip, compile seconds and the cache directory.
Wall and compile seconds it prints are information, not a record.

    python chip_smoke.py             # on the chip (the driver's check)
    JAX_PLATFORMS=cpu python chip_smoke.py --dry-run
        every leg at a tiny size on the host, kernels in interpret
        mode; proves the script, never the chip: "ok" stays false.
    --out DIR    output directory (default <checkout>/chip_smoke_out)
"""

from __future__ import annotations

import argparse
import gc
import io
import json
import os
import shutil
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
_CONF = os.path.join(_REPO, "examples", "ImageNet", "AlexNet.conf")

# the conf has no pred block (the reference's ImageNet.conf has none
# either): task=pred/serve read the eval imgbin through this one,
# appended to the committed text - net and hyper-parameters untouched
_PRED_BLOCK = """
pred = pred.txt
iter = imgbin
  image_list = "./data/test.lst"
  image_bin = "./data/test.bin"
  image_root = "./data/resize256/"
  image_mean = "models/image_net_mean.bin"
iter = end
"""

# sizes: what the chip run uses, and the tiny stand-ins of --dry-run
# (the net keeps AlexNet.conf's layers; 67x67 is the smallest input
# its conv/pool stack reduces to 1x1, f32 because XLA:CPU emulates
# bf16). task=serve replays the eval rows as ragged requests
# (serve_rows=0: 1,2,3,5,7,4,6,8,...) into buckets [1,2,4,8]: requests
# coalesce whole and in order, so every "4" ships alone (7+4 and 4+6
# overflow 8) into bucket 4 and every "8" fills bucket 8 - two bucket
# sizes whatever the timing.
_SERVE = ["serve_rows=0", "serve_max_batch=8"]
_FULL = dict(n_train=1024, n_eval=256, image=256, overrides=[])
_TINY = dict(n_train=64, n_eval=32, image=72,
             overrides=["batch_size=16", "input_shape=3,67,67",
                        "dtype=float32"])


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    """A failed check ends the run (never `assert`: -O strips it)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")
    say(f"  ok: {msg}")


# ---------------------------------------------------------------------------
# compile accounting: every executable jax builds or loads, timestamped
# ---------------------------------------------------------------------------
class CompileLog:
    """(wall time, name, seconds) of every backend compile - a load
    from the persistent cache included, which is what makes a second
    run's total small - plus the cache's own hit/miss counts."""

    def __init__(self) -> None:
        import jax
        self.events = []
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            # wall clock on purpose: compared with the telemetry
            # streams' `ts`
            self.events.append((time.time(), kw.get("fun_name", "?"),
                                float(secs)))

    def _ev(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float):
        return [e for e in self.events if t0 < e[0] <= t1]

    @property
    def total_s(self) -> float:
        return sum(e[2] for e in self.events)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def write_imgbin(data_dir: str, name: str, n: int, size: int,
                 seed: int) -> None:
    """n distinct size x size JPEGs + labels spread over 0-999 in the
    reference's imgbin format (BinaryPage .bin + .lst). Smooth images
    (an upsampled 8x8 field), so a blob is a few KB."""
    from PIL import Image
    from cxxnet_tpu.utils.binary_page import BinaryPageWriter
    rng = np.random.RandomState(seed)
    labels = rng.permutation(n) * 1000 // n
    with open(os.path.join(data_dir, name + ".bin"), "wb") as fo:
        w = BinaryPageWriter(fo)
        for _ in range(n):
            low = rng.randint(0, 256, (8, 8, 3)).astype(np.uint8)
            img = Image.fromarray(low).resize((size, size),
                                              Image.BILINEAR)
            buf = io.BytesIO()
            img.save(buf, format="JPEG", quality=90)
            w.push(buf.getvalue())
        w.close()
    with open(os.path.join(data_dir, name + ".lst"), "w") as fo:
        for i in range(n):
            fo.write(f"{i}\t{int(labels[i])}\t{name}{i}.jpg\n")


def enter_leg_dir(out: str, leg: str) -> str:
    """AlexNet.conf names its data, mean image and model_dir relative
    to the working directory; each training leg gets its own, with
    ./data pointing at the shared imgbin - that is the whole of the
    'path override'. The process STAYS there: the iterators' reader
    threads reopen the relative paths every epoch."""
    d = os.path.join(out, leg)
    os.makedirs(d, exist_ok=True)
    link = os.path.join(d, "data")
    if not os.path.islink(link):
        os.symlink(os.path.join("..", "data"), link)
    os.chdir(d)
    return d


# ---------------------------------------------------------------------------
# the CLI, in-process
# ---------------------------------------------------------------------------
def run_cli(argv):
    """What `python -m cxxnet_tpu.main <argv>` runs (main() is
    `LearnTask().run(argv)`), keeping the task so a leg can look at the
    trainer it built."""
    from cxxnet_tpu.main import LearnTask
    say("cxxnet_tpu.main " + " ".join(argv))
    task = LearnTask()
    rc = task.run(argv)
    if rc != 0:
        raise RuntimeError(f"cxxnet_tpu.main returned {rc}")
    return task


def events_of(path: str):
    from cxxnet_tpu.telemetry.sink import read_jsonl
    return list(read_jsonl(path))


def describe_mesh(leg: str, trainer) -> None:
    import jax
    d = jax.devices()[0]
    say(f"leg {leg}: platform={d.platform} device_kind={d.device_kind} "
        f"device_count={jax.device_count()} "
        f"mesh={dict(trainer.mesh.shape)} over devices "
        f"{[x.id for x in trainer.mesh.devices.flat]}")


def staged_zero_batch(trainer):
    from cxxnet_tpu.io.data import DataBatch
    c, y, x = trainer.net_cfg.input_shape
    b = trainer.batch_size
    return trainer.stage_batch(DataBatch(
        data=np.zeros((b, c, y, x), np.float32),
        label=np.zeros((b, 1), np.float32)))


def step_programs(trainer):
    """(traced jaxpr text, compiled HLO text) of the train step at the
    shapes it ran with. The compile is the one the run already paid
    for: same module, so the caches answer."""
    import jax
    sb = staged_zero_batch(trainer)
    args = (trainer.state, sb.data, sb.extras, sb.labels, sb.mask,
            jax.random.PRNGKey(0))
    traced = trainer._train_step.trace(*args)
    return str(traced.jaxpr), traced.lower().compile().as_text(), sb


def train_leg(leg: str, cfg: dict, extra, clog: CompileLog,
              dry: bool) -> dict:
    """task=train for two rounds + every check both training legs
    share; returns what the callers compare (losses, trainer, texts)."""
    from cxxnet_tpu import telemetry
    from cxxnet_tpu.nnet import checkpoint
    log = os.path.abspath("train_events.jsonl")
    before = {e["fingerprint"]: e["dispatches"]
              for e in telemetry.get().executables.snapshot()}
    task = run_cli([_CONF, "num_round=2", f"log_file={log}"]
                   + cfg["overrides"] + list(extra))
    tr = task.net_trainer
    describe_mesh(leg, tr)
    ev = events_of(log)

    losses = [e["loss"] for e in ev
              if e["kind"] == "span" and e.get("name") == "train.step"]
    steps = 2 * (cfg["n_train"] // tr.batch_size)
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"{steps} train steps, every loss finite: "
          f"{[round(v, 4) for v in losses]}")
    evals = {e["round"]: e["values"] for e in ev if e["kind"] == "eval"}
    check(all("train-error" in evals.get(r, {})
              and "test-error" in evals.get(r, {}) for r in (1, 2)),
          f"a train-error and a test-error line per round: {evals}")

    # weights moved, the checkpoint is whole
    err = checkpoint.validate_file("models/0002.model")
    check(err is None, f"checkpoint.validate_file(0002.model): {err}")
    with open("models/0000.model", "rb") as f0, \
            open("models/0002.model", "rb") as f2:
        p0 = checkpoint.load_model(f0)["params"]
        p2 = checkpoint.load_model(f2)["params"]
    moved = [f"{lk}.{pn}" for lk, d in p2.items() for pn, a in d.items()
             if not np.array_equal(a, p0[lk][pn])]
    finite = all(np.isfinite(a).all() for d in p2.values()
                 for a in d.values())
    check(moved and finite,
          f"{len(moved)} weight tensors changed, all finite")

    # round 2 compiled nothing: the registry holds ONE train program
    # (all 8 dispatches on it), the step's jit cache one entry, and no
    # backend compile was logged after round 2 began
    new = [e for e in telemetry.get().executables.snapshot()
           if e["kind"] == "train"
           and e["dispatches"] > before.get(e["fingerprint"], 0)]
    check(len(new) == 1 and new[0]["dispatches"]
          - before.get(new[0]["fingerprint"], 0) == steps,
          f"executable registry: one train program took all {steps} "
          f"dispatches ({[(e['name'], e['dispatches']) for e in new]})")
    check(tr._train_step._cache_size() == 1,
          "train step jit cache holds one executable")
    t_r2 = [e["ts"] for e in ev
            if e["kind"] == "round_start" and e["round"] == 2][0]
    t_end = [e["ts"] for e in ev if e["kind"] == "run_end"][0]
    late = clog.between(t_r2, t_end)
    check(not late, f"no compile in round 2 (found {late})")

    # the kernels are IN the step: traced, and (on the chip) compiled
    jaxpr, hlo, sb = step_programs(tr)
    n_fwd, n_bwd = (jaxpr.count("name=lrn_fwd"),
                    jaxpr.count("name=lrn_bwd"))
    check((n_fwd, n_bwd) == (2, 2),
          f"train step traces the Pallas LRN kernel {n_fwd}x forward, "
          f"{n_bwd}x backward")
    routes = [r for r in ("route.pallas", "route.sharded", "route.xla")
              if f"/{r}/" in hlo]
    check(routes == ["route.sharded" if tr.mesh.devices.size > 1
                     else "route.pallas"],
          f"the step's text names the one LRN route taken: {routes}")
    if not dry:
        n_cc = hlo.count("tpu_custom_call")
        check(n_cc >= 4,
              f"compiled train step holds {n_cc} Mosaic custom calls")
    return dict(trainer=tr, losses=losses, jaxpr=jaxpr,
                hlo=hlo, staged=sb)


def serve_leg(cfg: dict, clog: CompileLog, dry: bool) -> None:
    """task=pred, then task=serve, on the train leg's checkpoint."""
    from cxxnet_tpu import telemetry
    with open(_CONF) as f:
        conf_text = f.read()
    with open("AlexNet_pred.conf", "w") as f:
        f.write(conf_text + _PRED_BLOCK)
    common = ["AlexNet_pred.conf", "model_in=models/0002.model"] \
        + cfg["overrides"]
    task = run_cli(common + ["task=pred", "pred=pred.txt"])
    describe_mesh("serve (task=pred)", task.net_trainer)
    del task

    log = os.path.abspath("serve_events.jsonl")
    before = {e["fingerprint"]: e["dispatches"]
              for e in telemetry.get().executables.snapshot()}
    task = run_cli(common + ["task=serve", "pred=serve.txt",
                             f"log_file={log}"] + _SERVE)
    tr = task.net_trainer
    describe_mesh("serve (task=serve)", tr)

    with open("pred.txt") as f:
        pred = f.read().split()
    with open("serve.txt") as f:
        served = f.read().split()
    check(len(pred) == cfg["n_eval"] and len(served) == cfg["n_eval"],
          f"one prediction per eval row in both files ({len(pred)}, "
          f"{len(served)} of {cfg['n_eval']})")
    # Same rows in the same order. Identical on the host in float32
    # (--dry-run). On the chip a row's activations depend on the batch
    # it is computed in: XLA's convs round differently at 256 rows and
    # at 8 (conv1's output already differs by an ulp of bf16, before
    # and after PR 28 alike, PERF.md section 6), and this checkpoint's
    # classes are nearly tied, so a few rows may fall the other way.
    # The LRN kernels add nothing to that (kernel leg, "8 rows alone").
    diff = [i for i, (a, b) in enumerate(zip(pred, served)) if a != b]
    if dry:
        check(not diff, f"task=serve output identical to task=pred "
                        f"(rows that differ: {diff[:8]})")
    else:
        check(len(diff) <= len(pred) // 20,
              f"task=serve agrees with task=pred on all rows but "
              f"near-ties: {len(diff)} of {len(pred)} differ "
              f"({diff[:8]})")
    say(f"  predicted classes: {sorted(set(pred))[:12]}")

    hit = {e["name"]: e["dispatches"] - before.get(e["fingerprint"], 0)
           for e in telemetry.get().executables.snapshot()
           if e["kind"] == "serve"}
    hit = {k: v for k, v in hit.items() if v > 0}
    check(len(hit) >= 2, f"bucket executables dispatched: {hit}")
    ev = events_of(log)
    warm = [e for e in ev if e["kind"] == "serve"
            and e.get("op") == "warmup"][0]
    t_end = [e["ts"] for e in ev if e["kind"] == "run_end"][0]
    late = clog.between(warm["ts"], t_end)
    check(not late, f"no compile after warmup() (found {late})")
    node = tr.net_cfg.num_nodes - 1
    n_exec = tr._infer_jits[node]._cache_size()
    check(n_exec == len(warm["buckets"]),
          f"infer jit cache holds {n_exec} executables for buckets "
          f"{warm['buckets']}")


# ---------------------------------------------------------------------------
# kernels against their references
# ---------------------------------------------------------------------------
def close(name: str, got, ref, rtol: float, atol: float) -> None:
    """|got - ref| <= atol * max|ref| + rtol * |ref| everywhere; atol
    is a FRACTION of the reference's scale, so a tensor of tiny values
    cannot pass vacuously."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    scale = float(np.abs(ref).max())
    err = np.abs(got - ref)
    bound = atol * scale + rtol * np.abs(ref)
    check(got.shape == ref.shape and np.isfinite(got).all()
          and bool((err <= bound).all()),
          f"{name}: max|err| {float(err.max()):.3g} at scale "
          f"{scale:.3g} (rtol {rtol:g}, atol {atol:g} x scale)")


def kernel_leg(dry: bool) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from cxxnet_tpu.ops import int8 as I8
    from cxxnet_tpu.ops import pallas_attention as PA
    from cxxnet_tpu.ops import pallas_lrn as PL
    from cxxnet_tpu.ops.attention import naive_attention
    from cxxnet_tpu.ops.nn import lrn_xla

    interp = bool(dry)
    d = jax.devices()[0]
    say(f"leg kernel: platform={d.platform} device_kind={d.device_kind} "
        f"device_count={jax.device_count()} mesh=none (direct calls "
        "on the default device)")
    if not dry:
        check(not (PL._FORCE_INTERPRET or PA._FORCE_INTERPRET
                   or I8._FORCE_INTERPRET),
              "all three _FORCE_INTERPRET hooks are off")
    rng = np.random.RandomState(7)

    # -- LRN: AlexNet's two layers (n=5, alpha=1e-3, beta=.75, k=1).
    # bf16 is what the layer feeds the kernel under dtype=bfloat16:
    # the kernel computes in f32 and rounds its OUTPUT to bf16, so it
    # matches the f32 reference to bf16 rounding (2^-8 relative). f32
    # in/out isolates the kernel's own arithmetic at the CPU test's
    # tolerance (tests/test_pallas_lrn.py).
    hyper = (5, 0.001, 0.75, 1.0)
    # AlexNet.conf's batch and the benchmark's (alexnet.train_resident)
    # go batch on lanes, Server buckets 1 and 8 positions on lanes, the
    # 5x5 and 3x3 stand-ins ragged lanes (ops/pallas_lrn.py _plan)
    shapes = ([(4, 16, 5, 5), (2, 32, 3, 3), (128, 16, 3, 3)] if dry else
              [(256, 96, 27, 27), (256, 256, 13, 13),
               (2048, 96, 27, 27), (2048, 256, 13, 13),
               (1, 96, 27, 27), (8, 96, 27, 27),
               (1, 256, 13, 13), (8, 256, 13, 13)])
    for shp in shapes:
        for dt, rt, at, grt, gat in ((jnp.bfloat16, 8e-3, 8e-3, 2e-2,
                                      2e-2),
                                     (jnp.float32, 1e-5, 1e-6, 1e-4,
                                      1e-5)):
            x = jnp.asarray(rng.randn(*shp), dt)
            g = jnp.asarray(rng.randn(*shp), jnp.float32)
            x32 = x.astype(jnp.float32)
            # (g is an argument: closed over, its half gigabyte at
            # the benchmark's shapes would be compiled into the program)
            fk = jax.jit(lambda x: PL.lrn_pallas(x, *hyper, interp))
            gk = jax.jit(jax.grad(lambda x, g: jnp.sum(
                PL.lrn_pallas(x, *hyper, interp).astype(jnp.float32)
                * g)))
            fr = jax.jit(lambda x: lrn_xla(x, *hyper))
            gr = jax.jit(jax.grad(
                lambda x, g: jnp.sum(lrn_xla(x, *hyper) * g)))
            tag = f"lrn {shp} {jnp.dtype(dt).name}"
            y = fk(x)
            close(tag + " fwd", y, fr(x32), rt, at)
            close(tag + " grad", gk(x, g), gr(x32, g), grt, gat)
            if shp[0] % 128 == 0:
                # what task=serve == task=pred rests on: a bucket of 8
                # rows reads positions on lanes, the batch they came
                # from batch on lanes, and a row comes out the same
                check(np.array_equal(
                    np.asarray(y[:8], np.float32),
                    np.asarray(fk(x[:8]), np.float32)),
                    tag + ": 8 rows alone == the same rows in the batch")

    # -- flash attention, bf16, forward + all three grads, causal and
    # not, against naive_attention in f32 at "highest" matmul
    # precision, one batch row at a time (the reference materializes
    # S x S scores). Two shapes: the kernel's design shape, and the
    # LongSeq example's (seq_mnist.conf: 4 heads, 28 steps, head dim
    # 7). Tolerance: the CPU suite's bf16 bound
    # (tests/test_pallas_attention.py test_bf16_forward, 0.05), here
    # relative to each tensor's scale.
    def flash_case(b, h, s, dh, causal, hkv=None, window=0):
        q, k, v = (jnp.asarray(rng.randn(b, n, s, dh), jnp.bfloat16)
                   for n in (h, hkv or h, hkv or h))
        w = jnp.asarray(rng.randn(b, h, s, dh), jnp.float32)

        def loss_k(q, k, v, w):
            o = PA.flash_attention(q, k, v, causal, None, interp, window)
            return jnp.sum(o.astype(jnp.float32) * w), o

        def loss_r(q, k, v, w):
            o = naive_attention(q, k, v, causal=causal, window=window)
            return jnp.sum(o * w), o

        (_, ok), gk = jax.jit(jax.value_and_grad(
            loss_k, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
        ref_fn = jax.jit(jax.value_and_grad(
            loss_r, argnums=(0, 1, 2), has_aux=True))
        outs, grads = [], [[], [], []]
        with jax.default_matmul_precision("highest"):
            for i in range(b):
                sl = slice(i, i + 1)
                (_, o), g3 = ref_fn(*(t[sl].astype(jnp.float32)
                                      for t in (q, k, v)), w[sl])
                outs.append(np.asarray(o))
                for acc, gi in zip(grads, g3):
                    acc.append(np.asarray(gi))
        tag = (f"flash b{b} h{h}/{hkv or h} s{s} d{dh} bf16 "
               f"causal={int(causal)} window={window}")
        close(tag + " fwd", ok, np.concatenate(outs), 0.05, 0.05)
        for nm, got, acc in zip("qkv", gk, grads):
            close(f"{tag} d{nm}", got, np.concatenate(acc), 0.05, 0.05)

    for causal in (False, True):
        flash_case(*((2, 2, 32, 16) if dry else (4, 8, 4096, 128)),
                   causal)
        flash_case(4 if dry else 100, 4, 28, 7, causal)
    # the `gqa` layer's kernels: 7 query heads on each key/value head
    # (dk and dv sum over their group), without a window and with one a
    # quarter of the sequence long, so that whole tiles lie left of it
    # and are never walked (flash_win_fwd / flash_win_dq / flash_win_dkv)
    for window in (0, 8 if dry else 1024):
        flash_case(*((2, 14, 32, 16) if dry else (1, 14, 4096, 128)),
                   True, hkv=2, window=window)
    # a head of 64, half a lane tile, four query heads a key/value head
    # (examples/LongSeq/lfm2_5l.conf: the score product contracts over
    # 64 of the MXU's 128 rows, the 64-wide minor dimension is padded to
    # 128 lanes in HBM)
    flash_case(*((2, 8, 32, 64) if dry else (1, 16, 4096, 64)), True,
               hkv=2 if dry else 4)
    # the compiler takes the seq_mnist shape, but that example's layer
    # never sends it: _tile_ok declines d < 8 and a 28-row bf16 tile,
    # and AttentionLayer._core routes it to blockwise XLA
    check(not PA._tile_ok(jnp.zeros((100, 4, 28, 7), jnp.bfloat16), 28),
          "seq_mnist's shape is outside the flash kernel's own tile "
          "rule (its layer takes the blockwise XLA route)")

    # -- int8 matmul: AlexNet fc7 at a serving batch and a 2048-wide
    # fullc; int32 accumulation is exact, so the kernel must EQUAL
    # lax.dot_general (tests/test_quantize.py)
    for m, kk, n in ([(32, 128, 128)] if dry else
                     [(32, 4096, 4096), (32, 2048, 2048)]):
        check(I8._pallas_blocks(m, kk, n) is not None,
              f"int8 {m}x{kk}x{n} tiles for the kernel "
              f"(blocks {I8._pallas_blocks(m, kk, n)})")
        xq = jnp.asarray(rng.randint(-127, 128, (m, kk)), jnp.int8)
        wq = jnp.asarray(rng.randint(-127, 128, (n, kk)), jnp.int8)
        saved = I8._FORCE_INTERPRET
        I8._FORCE_INTERPRET = interp
        try:
            got = jax.jit(I8._matmul_pallas)(xq, wq)
        finally:
            I8._FORCE_INTERPRET = saved
        ref = lax.dot_general(xq, wq, I8._DN,
                              preferred_element_type=jnp.int32)
        check(got.dtype == jnp.int32
              and bool((np.asarray(got) == np.asarray(ref)).all()),
              f"int8 {m}x{kk}x{n}: kernel == lax.dot_general (int32)")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
_LM_CONF = os.path.join(_REPO, "examples", "LongSeq", "kimi_linear_5l.conf")


def _lm_tiny() -> dict:
    """The dry run's widths: the cut the benchmark's rehearsal of this
    conf makes, and a rate at which three steps show."""
    with open(os.path.join(_REPO, "benchmark", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        return dict(json.load(f)["dry_run_overrides"], eta="0.01")


def kda_routes(dry: bool) -> None:
    """One `kda` layer at the Kimi conf's widths (2304 wide, 32 heads of
    128, chunks of 64) over 1,024 positions in bf16, forward and the
    gradients of every parameter and of the input, through the route
    the chip takes (ops/pallas_kda.py's kernel pair, seen in the
    lowered text) and through the XLA route every other case takes
    (`ops.kda._backend_ok` held false). Both compute in float32 and
    round the same operands to bf16, at slightly different points: the
    flash kernel's tolerance of the kernel leg."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.ops import kda as KD
    e, nh, t = (32, 1, 128) if dry else (2304, 32, 1024)
    shape = (1, 1, t, e)
    lay = create_layer("kda", "k")
    for k, v in (("nhead", nh), ("head_dim", 128), ("gate_rank", 128),
                 ("init_sigma", 0.02)):
        lay.set_param(k, str(v))
    lay.infer_shapes([shape])
    p = lay.init_params(jax.random.PRNGKey(0), [shape])
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)

    def loss(p, x):
        pc = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        (y,) = lay.apply(pc, [x], train=True)
        return jnp.sum(y.astype(jnp.float32) * w), y

    def run(kernels: bool):
        ok = KD._backend_ok
        if not kernels:
            KD._backend_ok = lambda: False
        try:
            fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                            has_aux=True))
            text = fn.lower(p, x).as_text(debug_info=True)
            (_, y), (gp, gx) = fn(p, x)
        finally:
            KD._backend_ok = ok
        return text, y, dict(gp, x=gx)

    text_k, y_k, g_k = run(True)
    text_x, y_x, g_x = run(False)
    check("route.pallas" in text_k and "route.xla" not in text_k,
          "the kda layer takes the kernel route here")
    check("route.xla" in text_x and "route.pallas" not in text_x,
          "with the backend test held false it takes the XLA route")
    tag = f"kda {t} positions x {nh} heads x 128, kernel route v XLA route"
    close(f"{tag}: out", y_k, y_x, 2e-2, 2e-2)
    for name in sorted(g_x):
        close(f"{tag}: d{name}", g_k[name], g_x[name], 2e-2, 2e-2)


def gconv_check(dry: bool) -> None:
    """One `gconv` layer at the LFM2 conf's widths (2048 wide, 3 taps)
    over 4,096 positions: bf16 as a step runs it, forward and the
    gradients of every parameter and of the input, against the same
    layer in float32 at "highest" matmul precision; the kernel leg's
    tolerance."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.layers import create_layer
    e, t = (32, 64) if dry else (2048, 4096)
    shape = (1, 1, t, e)
    lay = create_layer("gconv", "g")
    lay.set_param("init_sigma", "0.02")
    lay.infer_shapes([shape])
    p = lay.init_params(jax.random.PRNGKey(0), [shape])
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)

    def run(dtype):
        def loss(p, x):
            pc = jax.tree.map(lambda a: a.astype(dtype), p)
            (y,) = lay.apply(pc, [x.astype(dtype)], train=True)
            return jnp.sum(y.astype(jnp.float32) * w), y
        (_, y), (gp, gx) = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(p, x)
        return y, dict(gp, x=gx)

    y_b, g_b = run(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        y_f, g_f = run(jnp.float32)
    tag = f"gconv {t} positions x {e}, bf16 v float32"
    close(f"{tag}: out", y_b, y_f, 2e-2, 2e-2)
    for name in sorted(g_f):
        close(f"{tag}: d{name}", g_b[name], g_f[name], 2e-2, 2e-2)


def lm_leg(out: str, dry: bool) -> None:
    """task=train over examples/LongSeq/kimi_linear_5l.conf as
    committed (the five Kimi-Linear layers at their published widths,
    602M parameters, 8,192 positions; a dry run cuts the widths in a
    copy): three steps over the conf's one seeded batch through the
    CLI, a finite and falling loss, nothing dropped by the experts. The
    benchmark's cell guards this model's speed; this guards its entry
    point."""
    import jax
    from cxxnet_tpu.utils.config import parse_config_string
    kda_routes(dry)
    gconv_check(dry)
    d = os.path.join(out, "lm")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    os.chdir(d)
    conf = _LM_CONF
    if dry:
        tiny = _lm_tiny()
        with open(_LM_CONF) as f:
            pairs = [(k, v) for k, v in parse_config_string(f.read())
                     if k not in tiny]
        conf = os.path.join(d, "tiny.conf")
        with open(conf, "w") as f:
            f.write("\n".join(f"{k} = {v}" for k, v in
                              pairs + list(tiny.items())) + "\n")
    log = os.path.join(d, "events.jsonl")
    task = run_cli([conf, "telemetry_steps=1", f"log_file={log}",
                    "log_format=json", "silent=1"])
    describe_mesh("lm (task=train, kimi_linear_5l.conf)", task.net_trainer)
    losses = [e["loss"] for e in events_of(log)
              if e.get("name") == "train.step"]
    check(len(losses) == 3 and all(np.isfinite(losses)),
          f"three steps, each with a finite loss: {losses}")
    check(losses[2] < losses[0],
          f"the loss falls over three steps of one batch: {losses}")
    counted = task.net_trainer.fetch_counters()
    check(counted and all(v == 0 for k, v in counted.items()
                          if k.endswith(".dropped")),
          "the expert layers computed every held assignment: "
          + json.dumps({k: v for k, v in counted.items()
                        if not k.endswith(".dropped")}))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        task.net_trainer.state["params"]))
    say(f"  {n:,} parameters; ids staged as "
        f"{task.net_trainer.stage_batch(task.itr_train.value()).data.dtype}")
    task.net_trainer.state = None
    del task
    gc.collect()


def four_leg(cfg: dict, clog: CompileLog, dry: bool, one) -> None:
    res = train_leg("four", cfg, ["dev=tpu:0-3"], clog, dry)
    tr = res["trainer"]
    check(tr.mesh.devices.size == 4,
          f"mesh has 4 devices, as asked ({dict(tr.mesh.shape)})")
    shards = res["staged"].data.addressable_shards
    devs = {s.device.id for s in shards}
    check(len(devs) == 4 and all(
        s.data.shape[0] == tr.batch_size // 4 for s in shards),
        f"staged batch: {len(shards)} shards of "
        f"{shards[0].data.shape} on devices {sorted(devs)}")
    if dry:
        say("  memory_stats: not reported by the cpu backend")
    else:
        used = {d.id: d.memory_stats()["bytes_in_use"]
                for d in tr.mesh.devices.flat}
        check(all(v > 64 << 20 for v in used.values()),
              "bytes_in_use on all four chips: "
              + str({k: f"{v >> 20} MiB" for k, v in used.items()}))
    check("all-reduce" in res["hlo"],
          "compiled step holds the gradient all-reduce")
    check("shard_map" in res["jaxpr"],
          "LRN takes the shard_map route over 'data'")
    # same data order, same seeds, same dropout bits (threefry is
    # sharding-invariant): what differs is the order gradients are
    # summed in, amplified step by step in bf16 while the loss is
    # still falling steeply. Measured on four v5e chips (PR 21):
    # step 1 identical, gap growing to 0.085 at step 8 = 0.6% of
    # the largest loss; the bound is 2% of it. On the host in f32
    # (--dry-run) the two legs agree to 2e-6.
    a, b4 = np.asarray(one), np.asarray(res["losses"])
    gap = float(np.abs(a - b4).max())
    check(gap <= 2e-2 * float(np.abs(a).max()),
          f"per-step losses agree with the one-chip leg: max gap "
          f"{gap:.3g} ({[round(v, 4) for v in res['losses']]})")


# ---------------------------------------------------------------------------
def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--out", default=os.path.join(_REPO,
                                                  "chip_smoke_out"))
    args = ap.parse_args(argv)
    dry = args.dry_run
    t_start = time.monotonic()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dry:
        if dev.platform != "cpu":
            sys.stderr.write(
                "chip_smoke.py --dry-run is the host rehearsal; run it "
                f"under JAX_PLATFORMS=cpu (found '{dev.platform}')\n")
            return 2
    elif dev.platform != "tpu":
        sys.stderr.write(
            "chip_smoke.py needs a TPU and JAX found none: the "
            f"platform is '{dev.platform}' ({device['count']} x "
            f"{dev.device_kind}). No result.\n")
        return 2

    from cxxnet_tpu.io.native import native_available
    from cxxnet_tpu.utils.platform import setup_compile_cache
    cache_dir = setup_compile_cache()
    clog = CompileLog()
    cfg = _TINY if dry else _FULL
    say(f"device {device}; compile cache {cache_dir}; "
        f"{'DRY RUN (never a chip pass)' if dry else 'chip run'}")
    if dry:
        from cxxnet_tpu.ops import int8 as I8
        from cxxnet_tpu.ops import kda as KD
        from cxxnet_tpu.ops import pallas_attention as PA
        from cxxnet_tpu.ops import pallas_lrn as PL
        PL._FORCE_INTERPRET = PA._FORCE_INTERPRET = True
        I8._FORCE_INTERPRET = KD._FORCE_INTERPRET = True

    out = os.path.abspath(args.out)
    data_dir = os.path.join(out, "data")
    for sub in ("data", "one", "four", "lm"):
        # what an earlier run left (a mean image, checkpoints) would be
        # picked up by this one: start from nothing
        shutil.rmtree(os.path.join(out, sub), ignore_errors=True)
    os.makedirs(data_dir)
    t0 = time.monotonic()
    write_imgbin(data_dir, "train", cfg["n_train"], cfg["image"], 1)
    write_imgbin(data_dir, "test", cfg["n_eval"], cfg["image"], 2)
    say(f"wrote {cfg['n_train']} train + {cfg['n_eval']} eval "
        f"{cfg['image']}x{cfg['image']} JPEGs into {data_dir} "
        f"({time.monotonic() - t0:.1f} s)")

    # every leg, in order; a failed check raised, so a leg that
    # returned passed
    enter_leg_dir(out, "one")
    res = train_leg("train", cfg, [], clog, dry)
    check(res["trainer"].mesh.devices.size == 1,
          "dev = tpu built a one-device mesh on a host with "
          f"{device['count']} device(s)")
    say(f"  decoder that fed the train leg: "
        f"{'native (libcxxnet_io.so)' if native_available() else 'PIL'}")
    one_losses = res["losses"]
    del res
    serve_leg(cfg, clog, dry)
    gc.collect()
    kernel_leg(dry)
    gc.collect()
    lm_leg(out, dry)
    status = {"train": "pass", "serve": "pass", "kernel": "pass",
              "lm": "pass"}
    if device["count"] >= 4:
        enter_leg_dir(out, "four")
        four_leg(cfg, clog, dry, one_losses)
        status["four"] = "pass"
    else:
        status["four"] = f"skipped: {device['count']} device(s), needs 4"
        say(f"leg four: {status['four']}")

    say("summary " + json.dumps({
        "legs": status,
        "dry_run": dry,
        "compile_s": round(clog.total_s, 1),
        "executables_built": len(clog.events),
        "cache_hits": clog.hits,
        "cache_misses": clog.misses,
        "cache_dir": cache_dir,
        "wall_s": round(time.monotonic() - t_start, 1),
    }))
    # the last line is the driver's contract: exactly these keys. A pass
    # is a whole run on a TPU - never a dry run
    print(json.dumps({"ok": bool(not dry and dev.platform == "tpu"),
                      "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
