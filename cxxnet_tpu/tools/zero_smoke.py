"""ZeRO smoke: zero_stage=2/3 vs zero_stage=0 must be the SAME run.

    python -m cxxnet_tpu.tools.zero_smoke [--out DIR] [--keep]

Trains the tiny synthetic-MNIST MLP through the real CLI
(`python -m cxxnet_tpu.main`) on an 8-FAKE-DEVICE CPU mesh
(`--xla_force_host_platform_device_count=8`, `mesh=data:8`) four
times - replicated baseline (zero_stage=0), ZeRO-2, ZeRO-2 fused with
steps_per_dispatch=4 (chunked staging + the round-boundary short
chunk), and ZeRO-3 - then asserts:

- every run's final checkpoint has the SAME sha256 as the stage-0
  baseline: reduce-scatter + sharded update + all-gather is bitwise
  the replicated update (docs/parallel.md), and stage 3's
  gather-on-save keeps the checkpoint byte-compatible;
- identical per-round eval lines on stderr for every run.

All children run on the CPU with 8 virtual devices. XLA:CPU compiles
a contraction per program shape, which can put ~1 ULP between two
executables of the same math: backend noise, not a sharding-path
property. On this MLP the replicated and zero-region executables
compile the same contractions, and the byte-equality above is what
the smoke asserts. Exit 0 iff all checks pass.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import tempfile

from cxxnet_tpu.tools.telemetry_smoke import write_synth_mnist

CONF = """
data = train
iter = mnist
    path_img = "{d}/train-img.gz"
    path_label = "{d}/train-lbl.gz"
    shuffle = 1
iter = end
eval = test
iter = mnist
    path_img = "{d}/test-img.gz"
    path_label = "{d}/test-lbl.gz"
iter = end

netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:sg1] = tanh
layer[sg1->fc2] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end

input_shape = 1,1,36
batch_size = 32
mesh = data:8
save_model = 1
save_optimizer = 1
num_round = 3
max_round = 3
eta = 0.3
metric = error
eval_train = 1
silent = 1
"""


def _run_cli(out_dir: str, tag: str, overrides) -> dict:
    """One `python -m cxxnet_tpu.main` child; returns its artifacts."""
    mdir = os.path.join(out_dir, f"models_{tag}")
    flags = [t for t in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in t]
    flags += ["--xla_force_host_platform_device_count=8"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=" ".join(flags))
    r = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.main",
         os.path.join(out_dir, "zero_smoke.conf"),
         f"model_dir={mdir}"] + list(overrides),
        env=env, capture_output=True, text=True, timeout=540)
    path = os.path.join(mdir, "0003.model")
    sha = ""
    if os.path.exists(path):
        with open(path, "rb") as f:
            sha = hashlib.sha256(f.read()).hexdigest()
    return {
        "rc": r.returncode, "stderr": r.stderr, "sha": sha,
        "evals": [ln for ln in r.stderr.splitlines()
                  if ln.startswith("[")],
    }


def run_smoke(out_dir: str) -> int:
    # 288 instances = 9 batches/round at b32, so the K=4 variant chunks
    # as 4+4+1 and every round crosses the short-chunk path too
    write_synth_mnist(out_dir, 288, 0, "train")
    write_synth_mnist(out_dir, 64, 1, "test")
    with open(os.path.join(out_dir, "zero_smoke.conf"), "w") as f:
        f.write(CONF.format(d=out_dir))

    runs = {
        "z0": _run_cli(out_dir, "z0", ["zero_stage=0"]),
        "z2": _run_cli(out_dir, "z2", ["zero_stage=2"]),
        "z2k4": _run_cli(out_dir, "z2k4",
                         ["zero_stage=2", "steps_per_dispatch=4"]),
        "z3": _run_cli(out_dir, "z3", ["zero_stage=3"]),
    }
    base = runs["z0"]
    checks = [(f"{tag} run completed", r["rc"] == 0 and bool(r["sha"]))
              for tag, r in runs.items()]
    checks += [
        (f"{tag} final checkpoint sha256 == zero_stage=0",
         bool(base["sha"]) and r["sha"] == base["sha"])
        for tag, r in runs.items() if tag != "z0"]
    checks += [
        (f"{tag} per-round eval lines == zero_stage=0",
         len(base["evals"]) == 3 and r["evals"] == base["evals"])
        for tag, r in runs.items() if tag != "z0"]
    ok = True
    for label, passed in checks:
        print(f"  [{'ok' if passed else 'FAIL'}] {label}")
        ok = ok and bool(passed)
    if not ok:
        for tag, r in runs.items():
            if r["rc"] != 0:
                print(f"--- {tag} stderr tail ---")
                print(r["stderr"][-2000:])
    shas = {tag: r["sha"][:12] for tag, r in runs.items()}
    print(f"zero_smoke: {'PASS' if ok else 'FAIL'} {shas}")
    return 0 if ok else 1


def main() -> int:
    args = sys.argv[1:]
    if "--out" in args:
        i = args.index("--out")
        if i + 1 >= len(args):
            print("usage: zero_smoke [--out DIR] [--keep]")
            return 2
        out = args[i + 1]
        os.makedirs(out, exist_ok=True)
        return run_smoke(out)
    if "--keep" in args:
        d = tempfile.mkdtemp(prefix="zero_smoke_")
        rc = run_smoke(d)
        print(f"zero_smoke: artifacts kept in {d}")
        return rc
    with tempfile.TemporaryDirectory() as d:
        return run_smoke(d)


if __name__ == "__main__":
    sys.exit(main())
