"""Fused-dispatch smoke: K=4 vs K=1 must be the SAME training run.

    python -m cxxnet_tpu.tools.fused_smoke [--out DIR] [--keep]

Trains the tiny synthetic-MNIST MLP twice through the real CLI
(`python -m cxxnet_tpu.main`) - once streamed (steps_per_dispatch=1)
and once fused (steps_per_dispatch=4, exercising the chunked staging
prefetcher, the jitted scan, and the round-boundary short chunk) -
with telemetry armed, then asserts:

- identical final checkpoint SHA-256 (the bitwise trajectory-equality
  acceptance proof of docs/PERFORMANCE.md at the product surface);
- identical per-round eval lines on stderr;
- the fused run's event stream carries `train.chunk` spans with
  per-microstep loss vectors.

XLA:CPU compiles a contraction per program shape, which can put
~1 ULP between the per-step and fused executables: backend noise, not
a dispatch-path property (docs/PERFORMANCE.md). On this MLP both
compile the same contractions, and the byte-equality above is what
the smoke asserts. Exit 0 iff all checks pass; CI uploads the
produced JSONL streams next to the telemetry-smoke artifacts.
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
dev = cpu
save_model = 1
num_round = 3
max_round = 3
eta = 0.3
metric = error
eval_train = 1
silent = 1
"""


def _run_cli(out_dir: str, tag: str, k: int) -> dict:
    """One `python -m cxxnet_tpu.main` child; returns its artifacts."""
    mdir = os.path.join(out_dir, f"models_{tag}")
    log = os.path.join(out_dir, f"events_{tag}.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.main",
         os.path.join(out_dir, "fused_smoke.conf"),
         f"model_dir={mdir}", f"steps_per_dispatch={k}",
         f"log_file={log}",
         f"metrics_file={os.path.join(out_dir, f'metrics_{tag}.jsonl')}"],
        env=env, capture_output=True, text=True, timeout=540)
    path = os.path.join(mdir, "0003.model")
    sha = ""
    if os.path.exists(path):
        with open(path, "rb") as f:
            sha = hashlib.sha256(f.read()).hexdigest()
    return {
        "rc": r.returncode, "stderr": r.stderr, "sha": sha,
        "log": log,
        "evals": [l for l in r.stderr.splitlines()
                  if l.startswith("[")],
    }


def run_smoke(out_dir: str) -> int:
    from cxxnet_tpu.telemetry.sink import read_jsonl
    # 288 instances = 9 batches/round at b32: K=4 chunks as 4+4+1, so
    # every round exercises the round-boundary SHORT chunk too
    write_synth_mnist(out_dir, 288, 0, "train")
    write_synth_mnist(out_dir, 64, 1, "test")
    with open(os.path.join(out_dir, "fused_smoke.conf"), "w") as f:
        f.write(CONF.format(d=out_dir))

    streamed = _run_cli(out_dir, "k1", 1)
    fused = _run_cli(out_dir, "k4", 4)
    chunks = []
    if os.path.exists(fused["log"]):
        chunks = [e for e in read_jsonl(fused["log"])
                  if e.get("kind") == "span"
                  and e.get("name") == "train.chunk"]
    checks = [
        ("K=1 run completed", streamed["rc"] == 0 and streamed["sha"]),
        ("K=4 run completed", fused["rc"] == 0 and fused["sha"]),
        ("identical final checkpoint sha256",
         bool(streamed["sha"]) and streamed["sha"] == fused["sha"]),
        ("identical per-round eval lines",
         len(streamed["evals"]) == 3
         and streamed["evals"] == fused["evals"]),
        ("fused run emitted train.chunk spans (3 rounds x 4+4+1)",
         len(chunks) == 9),
        ("chunk spans carry per-microstep losses",
         bool(chunks)
         and all(len(c.get("loss", [])) == c.get("steps")
                 for c in chunks)),
        ("round-boundary short chunk present",
         sum(1 for c in chunks if c.get("steps") == 1) == 3),
    ]
    ok = True
    for label, passed in checks:
        print(f"  [{'ok' if passed else 'FAIL'}] {label}")
        ok = ok and bool(passed)
    if not ok:
        for tag, run in (("k1", streamed), ("k4", fused)):
            if run["rc"] != 0:
                print(f"--- {tag} stderr tail ---")
                print(run["stderr"][-2000:])
    print(f"fused_smoke: {'PASS' if ok else 'FAIL'} "
          f"(sha {streamed['sha'][:12]} vs {fused['sha'][:12]}, "
          f"{len(chunks)} chunk spans)")
    return 0 if ok else 1


def main() -> int:
    args = sys.argv[1:]
    if "--out" in args:
        i = args.index("--out")
        if i + 1 >= len(args):
            print("usage: fused_smoke [--out DIR] [--keep]")
            return 2
        out = args[i + 1]
        os.makedirs(out, exist_ok=True)
        return run_smoke(out)
    if "--keep" in args:
        d = tempfile.mkdtemp(prefix="fused_smoke_")
        rc = run_smoke(d)
        print(f"fused_smoke: artifacts kept in {d}")
        return rc
    with tempfile.TemporaryDirectory() as d:
        return run_smoke(d)


if __name__ == "__main__":
    sys.exit(main())
