"""Where a cell's set-up goes, phase by phase, from the program's own
set-up spans (`NetTrainer.fetch_setup()`, `cxxnet_tpu/telemetry/spans.py`).

    python3 benchmark/tools/setup_phases.py --workload <cell> --seed <n> \
        [--seconds 10] [--trace 0|1] [--skip-reference] [--dry-run] \
        [--out FILE]

Runs the cell as `benchmark/run.py` does, in this process (its first
import is run.py's, so set-up is counted from the same clock), then
prints one JSON line:

- `setup_s` as the harness reckons it, and `phases`: rows of (phase,
  seconds) that add up to it - imports and the reference, the three
  children of `setup.init` and what else it holds, staging the batches,
  `setup.step` split into trace, lowering, compile or cache load and the
  rest, the two further steps and the harness's norms, and what lies
  between the end of set-up and the window;
- `spans`: what the program's set-up spans read, summed over the
  top-level ones by the per-layer readers' own code
  (`benchmark/setup_spans.py`: `setup_init_s`, `setup_step_s`,
  `setup_trace_s`, `setup_compile_s`, `setup_traces`);
- `jax`: the program's `jax.*` counters over the whole process;
- `dispatch_ms`: the median host time of one window step.

A tree without the set-up spans gives `phases` and `spans` null. With
`--trace 1` the result line's idle gaps may name the program's host
spans (`train.key`, `train.call`), which the harness leaves out.
`--skip-reference` puts the program's own readings in the reference's
place: the run then says nothing of `correct`. Needs the chip unless
`--dry-run`.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench     # noqa: E402  (starts the clock)

import argparse                        # noqa: E402
import json                            # noqa: E402
import statistics                      # noqa: E402
from typing import Any, Dict, List, Optional, Tuple   # noqa: E402

from benchmark.setup_spans import span_metrics   # noqa: E402


def phases(t_start: float, runtime_s: float, prep_end: float,
           win_t0: float, records: List[Dict[str, Any]]
           ) -> List[Tuple[str, float]]:
    """Rows of (phase, seconds) from the set-up span records; they add
    up to `win_t0 - t_start - runtime_s`, the harness's `setup_s`."""
    init = next(r for r in records if r["name"] == "setup.init")
    step = next(r for r in records if r["name"] == "setup.step")
    kids = [r for r in records if r["parent"] == "setup.init"]
    rows = [("imports_and_reference",
             init["start"] - t_start - runtime_s)]
    rows += [(r["name"], r["end"] - r["start"]) for r in kids]
    rows.append(("setup.init.other", init["end"] - init["start"]
                 - sum(r["end"] - r["start"] for r in kids)))
    rows.append(("staging", step["start"] - init["end"]))
    parts = [("setup.step.trace", step["trace_s"]),
             ("setup.step.lower", step["lower_s"]),
             ("setup.step.compile_or_load",
              step["compile_s"] + step["cache_load_s"])]
    rows += parts
    rows.append(("setup.step.other", step["end"] - step["start"]
                 - sum(v for _, v in parts)))
    rows.append(("further_steps_and_norms", prep_end - step["end"]))
    rows.append(("to_window", win_t0 - prep_end))
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--skip-reference", action="store_true")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seen: Dict[str, Any] = {}

    find_devices = bench.find_devices

    def finding(*a):
        devs, seen["runtime_s"] = find_devices(*a)
        return devs, seen["runtime_s"]

    load_module = bench.load_module

    def loading(path):
        mod = load_module(path)
        if os.path.basename(os.path.dirname(path)) == "drivers":
            hook_driver(mod, seen, args.skip_reference)
        return mod

    bench.find_devices, bench.load_module = finding, loading
    if args.trace:
        # the harness keeps host spans named `bench.*`; keep the
        # program's too, so that an idle gap names its innermost one
        from benchmark import trace_reduce
        from cxxnet_tpu.telemetry import spans
        names = [v for k, v in vars(spans).items() if k.isupper()]
        trace_reduce.HOST_PREFIX = (trace_reduce.HOST_PREFIX, *names)
    run_args = ["--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", str(args.trace)]
    rc = bench.main(run_args + (["--dry-run"] if args.dry_run else []))

    win = seen["window"]
    records = seen["records"]
    setup_s = win.t0 - bench.T_START - seen["runtime_s"]
    out = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "setup_s": setup_s, "runtime_s": seen["runtime_s"],
        "phases": (phases(bench.T_START, seen["runtime_s"],
                          seen["prepare_end"], win.t0, records)
                   if records else None),
        "spans": span_metrics(records) if records else None,
        "jax": seen.get("jax"),
        "dispatch_ms": 1e3 * statistics.median(win.dispatch_s),
        "train_img_s": win.end_to_end()["train_img_s"],
    }
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return rc


def hook_driver(mod, seen: Dict[str, Any], skip_reference: bool) -> None:
    """Wrap the driver's prepare (its end, the trainer's set-up records
    and counters), window (the window) and, if asked, reference."""
    import time
    prepare, window = mod.prepare, mod.window

    def preparing(*a):
        prep = prepare(*a)
        seen["prepare_end"] = time.perf_counter()
        fetch = getattr(prep.trainer, "fetch_setup", None)
        seen["records"] = fetch() if fetch else None
        if fetch:
            from cxxnet_tpu import telemetry
            snap = telemetry.get().registry.snapshot()
            seen["jax"] = {k: v for k, v in snap.items()
                           if k.startswith("jax.")}
        seen["readings"] = prep.readings
        return prep

    def windowing(*a):
        seen["window"] = window(*a)
        return seen["window"]

    mod.prepare, mod.window = preparing, windowing
    if skip_reference:
        mod.reference_readings = lambda *a: seen["readings"]


if __name__ == "__main__":
    sys.exit(main())
