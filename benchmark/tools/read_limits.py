"""Readings that a cell's limits are set from, over several seeds in
one process (set-up is long; the training readings need no window).

    python3 benchmark/tools/read_limits.py --workload <cell> --seeds 1,2,3 \
        [--control float8_e4m3fn] [--faults half_batch] [--out FILE]

For each seed: the program's three compared steps against the plain
reference (the lower reading); with `--control`, the reference computed
in that type, put in the program's place (the upper reading); with
`--faults half_batch`, the program fed batches whose second half
repeats the first, so that the mean is over half of the rows. Prints one
JSON line a seed. Needs the chip unless `--dry-run`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def half_batch(batches):
    """The second half of every batch repeats the first."""
    import numpy as np
    out = []
    for images, labels in batches:
        h = len(images) // 2
        out.append((np.concatenate([images[:h], images[:h]]),
                    np.concatenate([labels[:h], labels[:h]])))
    return out


def main() -> int:
    from benchmark import run as bench
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()
    cell = bench.load_cell(args.workload)
    from cxxnet_tpu.utils.platform import setup_compile_cache
    import jax
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    bench.find_devices(cell.chips, args.dry_run)
    driver = bench.load_module(os.path.join(
        ROOT, "benchmark", "drivers", cell.traffic["driver"] + ".py"))
    overrides = dict(cell.cfg["overrides"])
    if args.dry_run:
        overrides.update(cell.cfg["dry_run_overrides"])
    ref = driver.make_reference(cell.cfg, overrides)
    control = (driver.make_reference(cell.cfg, overrides, args.control)
               if args.control else None)
    make = driver.make_batches
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        prep = driver.prepare(cell.cfg, cell.traffic, seed, overrides, ref)
        program, batches = prep.readings, prep.batches
        driver.free(prep)
        reference = driver.reference_readings(
            ref, cell.cfg, cell.traffic, seed, batches)
        row = {"workload": cell.name, "seed": seed,
               "loss": reference["loss"]}
        row["program"], row["program_leaf"] = driver.compare(
            program, reference)
        if control is not None:
            got = driver.reference_readings(
                control, cell.cfg, cell.traffic, seed, batches)
            row["control"], row["control_leaf"] = driver.compare(
                got, reference)
        if "half_batch" in args.faults:
            driver.make_batches = lambda *a, **k: half_batch(make(*a, **k))
            try:
                prep = driver.prepare(cell.cfg, cell.traffic, seed,
                                      overrides, ref)
            finally:
                driver.make_batches = make
            got = prep.readings
            driver.free(prep)
            row["half_batch"], row["half_batch_leaf"] = driver.compare(
                got, reference)
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
