"""Driver `train_resident`: batches resident on the device, cycled
through `NetTrainer.update(StagedBatch)`.

Set-up builds the trainer as `main.py` does (`set_param` for every
pair of the conf, then the overrides, then `init_model()`), makes the
batches from the seed, stages them once, and drives the first
`compared_steps` steps through the same `update` call the window uses.
Those steps are the warm-up AND what `correct` compares: their losses,
the first gradient as the updater got it (worked out from the momentum
after one step) and the change of the parameters after all of them,
against the plain reference, which runs after the window, when the
program's state has been freed.
"""

from __future__ import annotations

import gc
import importlib
import math
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
DISPATCH_SPAN = "bench.dispatch"
WAIT_SPAN = "bench.backpressure"

Batch = Tuple[np.ndarray, np.ndarray]      # uint8 images, int labels


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------
def make_batches(seed: int, count: int, rows: int, shape, classes: int
                 ) -> List[Batch]:
    """uint8 noise and labels 0..classes-1: every row differs."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        images = rng.integers(0, 256, size=(rows,) + tuple(shape),
                              dtype=np.uint8)
        labels = rng.integers(0, classes, size=(rows,), dtype=np.int64)
        out.append((images, labels))
    return out


def program_seed(seed: int) -> int:
    """The trainer adds 100 to its seed for dropout and makes int32 keys
    of both: fold any `--seed` into that range."""
    return seed % 2_000_000_011


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------
def build_trainer(conf_text: str, overrides: Dict[str, str], seed: int):
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    trainer = NetTrainer()
    for k, v in parse_config_string(conf_text):
        if k not in overrides:
            trainer.set_param(k, v)
    for k, v in overrides.items():
        trainer.set_param(k, v)
    trainer.set_param("seed", str(seed))
    trainer.init_model()
    return trainer


def stage(trainer, batch: Batch, mean: float):
    from cxxnet_tpu.io.data import DataBatch
    images, labels = batch
    data = images.astype(np.float32)
    data -= np.float32(mean)
    return trainer.stage_batch(DataBatch(
        data=data, label=labels.astype(np.float32).reshape(-1, 1)))


@dataclass
class Prepared:
    trainer: Any
    staged: List[Any]
    batches: List[Batch]
    losses: List[Any]
    steps_done: int = 0
    readings: Dict[str, Any] = field(default_factory=dict)


def _leaf_norms_fns(hyper):
    """Two jitted reductions over the trainer's state, one number a leaf:
    the norm of the gradient the updater got in its first step,
    `-m/lr - wd*w0` (sgd: `m = -lr*(g + wd*w0)` from zero momentum),
    and the norm of `w - w0`."""
    import jax
    import jax.numpy as jnp

    def grad_norms(ustate, w0):
        out = {}
        for lk, d in w0.items():
            for pn, w in d.items():
                h = hyper[lk][pn]
                g = -ustate[lk][pn]["m"] / h["lr0"] - h["wd"] * w
                out[f"{lk}.{pn}"] = jnp.sqrt(jnp.sum(g * g))
        return out

    def change_norms(params, w0):
        return {f"{lk}.{pn}": jnp.sqrt(jnp.sum((params[lk][pn] - w) ** 2))
                for lk, d in w0.items() for pn, w in d.items()}

    def copy(tree):
        return jax.tree.map(jnp.copy, tree)

    return jax.jit(grad_norms), jax.jit(change_norms), jax.jit(copy)


def prepare(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
            overrides: Dict[str, str], reference) -> Prepared:
    """Everything before the window. `reference` is the configuration's
    reference object (for shapes and the updater's settings only)."""
    import jax
    pseed = program_seed(seed)
    rows = int(overrides["batch_size"])
    classes = reference.net.layers[-1].out_shape[0]
    batches = make_batches(seed, int(traffic["distinct_batches"]), rows,
                           reference.net.input_shape, classes)
    trainer = build_trainer(cfg["conf_text"], overrides, pseed)
    mean = float(traffic["pixel_mean"])
    staged = [stage(trainer, b, mean) for b in batches]

    # the step hands back its loss and `update` drops it: keep each one
    # (a device scalar, nothing waits for it) for the comparison, the
    # count of failed steps and the window's back-pressure
    losses: List[Any] = []
    inner = trainer._train_step

    def recording_step(*args):
        out = inner(*args)
        losses.append(out[1])
        return out

    trainer._train_step = recording_step

    hyper = {lk: {pn: {"lr0": reference.lr_at(h, 0), "wd": h["wd"]}
                  for pn, h in d.items()}
             for lk, d in reference.hyper().items()}
    grad_norms, change_norms, copy = _leaf_norms_fns(hyper)
    prep = Prepared(trainer, staged, batches, losses)
    w0 = copy(trainer.state["params"])
    steps = int(traffic["compared_steps"])
    g1 = None
    for k in range(steps):
        drive_step(prep)
        if k == 0:
            g1 = grad_norms(trainer.state["ustate"], w0)
    dw = change_norms(trainer.state["params"], w0)
    jax.block_until_ready(trainer.state)
    prep.readings = {
        "loss": [float(v) for v in jax.device_get(losses[:steps])],
        "grad1": {k: float(v) for k, v in jax.device_get(g1).items()},
        "dparam": {k: float(v) for k, v in jax.device_get(dw).items()},
    }
    del w0
    return prep


def drive_step(prep: Prepared) -> None:
    """The one call that set-up and the window both make."""
    prep.trainer.update(prep.staged[prep.steps_done % len(prep.staged)])
    prep.steps_done += 1


@dataclass
class Window:
    wall_s: float
    steps: int
    images: int
    failed: int
    dispatch_s: List[float]
    wait_s: List[float]
    t0: float
    t1: float

    @property
    def attempted(self) -> int:
        """Operations are steps."""
        return self.steps

    def end_to_end(self) -> Dict[str, float]:
        """All images of all steps over the whole window's wall time."""
        return {"train_img_s": self.images / self.wall_s}


def window(prep: Prepared, seconds: float, traffic: Dict[str, Any]
           ) -> Window:
    """Dispatch steps until `seconds` have gone by, never more than
    the traffic's `in_flight_steps` ahead of the device, then wait for
    the last."""
    import jax
    from jax.profiler import TraceAnnotation
    in_flight = int(traffic["in_flight_steps"])
    losses = prep.losses
    n0 = len(losses)
    rows = prep.trainer.batch_size
    dispatch: List[float] = []
    waits: List[float] = []
    with TraceAnnotation(WINDOW_SPAN):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        ta = t0
        while ta < deadline:
            with TraceAnnotation(DISPATCH_SPAN):
                drive_step(prep)
            tb = time.perf_counter()
            dispatch.append(tb - ta)
            done = len(losses) - n0 - in_flight
            if done >= 0:
                with TraceAnnotation(WAIT_SPAN):
                    jax.block_until_ready(losses[n0 + done])
            ta = time.perf_counter()
            waits.append(ta - tb)
        jax.block_until_ready(prep.trainer.state)
        t1 = time.perf_counter()
    steps = len(losses) - n0
    values = jax.device_get(losses[n0:])
    failed = sum(1 for v in values if not math.isfinite(float(v)))
    failed += int(getattr(prep.trainer, "bad_rounds", 0))
    return Window(t1 - t0, steps, steps * rows, failed, dispatch, waits,
                  t0, t1)


def free(prep: Prepared) -> None:
    """Drop the program's state and batches from the device."""
    prep.trainer.state = None
    prep.trainer = None
    prep.staged = []
    prep.losses = []
    gc.collect()


# ---------------------------------------------------------------------------
# the reference, and the comparison
# ---------------------------------------------------------------------------
def make_reference(cfg: Dict[str, Any], overrides: Dict[str, str],
                   quant: Optional[str] = None):
    mod = importlib.import_module(
        "benchmark.reference." + cfg["reference"]["module"])
    return mod.Reference(cfg["conf_text"], overrides, quant)


def reference_readings(ref, cfg: Dict[str, Any], traffic: Dict[str, Any],
                       seed: int, batches: List[Batch]) -> Dict[str, Any]:
    """The same three readings from the plain reference."""
    import jax
    import jax.numpy as jnp
    pseed = program_seed(seed)
    steps = int(traffic["compared_steps"])
    block = int(cfg["reference"].get("block_images", 0)) or len(batches[0][0])
    mean = float(traffic["pixel_mean"])

    @jax.jit
    def norms(tree):
        return {f"{lk}.{pn}": jnp.sqrt(jnp.sum(a * a))
                for lk, d in tree.items() for pn, a in d.items()}

    @jax.jit
    def diff(a, b):
        return jax.tree.map(jnp.subtract, a, b)

    params0 = jax.jit(ref.init)(pseed)
    params = params0
    mom = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))(params0)
    update = jax.jit(ref.update, static_argnums=3)
    out: Dict[str, Any] = {"loss": []}
    for k in range(steps):
        images, labels = batches[k % len(batches)]
        loss, grad = ref.grads(params, images, mean, labels, pseed, k, block)
        if k == 0:
            out["grad1"] = {n: float(v) for n, v in
                            jax.device_get(norms(grad)).items()}
        out["loss"].append(float(loss))
        params, mom = update(params, mom, grad, k)
    out["dparam"] = {n: float(v) for n, v in
                     jax.device_get(norms(diff(params, params0))).items()}
    return out


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               leaves: List[str]) -> Dict[str, float]:
    """For each leaf the gap between the two norms, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    med = statistics.median(ref[n] for n in leaves)
    gaps = {}
    for n in leaves:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        gaps[n] = gap if math.isfinite(gap) else float("inf")
    return gaps


def compare(prog: Dict[str, Any], ref: Dict[str, Any]
            ) -> Tuple[Dict[str, float], Dict[str, str]]:
    """The numbers `correct` can be decided by (the cell's limits name
    those that are), and for the two taken by the worst leaf, which leaf
    it was. `grad1`, `dparam`: the worst leaf's gap; `grad1_med`,
    `dparam_med`: the median leaf's, steady where small leaves are
    noisy."""
    nums: Dict[str, float] = {}
    where: Dict[str, str] = {}
    for k, (lp, lr) in enumerate(zip(prog["loss"], ref["loss"])):
        gap = abs(lp - lr) / abs(lr)
        nums[f"loss{k + 1}"] = gap if math.isfinite(gap) else float("inf")
    leaves = sorted(ref["grad1"])
    # a leaf whose gradient is nought to rounding in the reference moves
    # by round-off alone: it is left out of the change, by this rule
    med = statistics.median(ref["grad1"].values())
    moving = [n for n in leaves if ref["grad1"][n] >= 1e-3 * med]
    for key, names in (("grad1", leaves), ("dparam", moving)):
        gaps = _leaf_gaps(prog[key], ref[key], names)
        where[key] = max(gaps, key=gaps.get)
        nums[key] = gaps[where[key]]
        nums[key + "_med"] = statistics.median(gaps.values())
    return nums, where
