"""Readings that a token cell's limits are set from, over several seeds
in one process (`read_limits.py` for the `train_tokens` driver, whose
faults are of another kind).

    python3 benchmark/tools/read_limits_tokens.py --workload <cell> \
        --seeds 1,2,3 [--variants float8_e4m3fn,no_decay,no_routed,rotary_on,half] \
        [--variant-seeds 2] [--out FILE] [--dry-run]

For each seed: the program's three compared steps against the plain
reference (the lower reading). For the first `--variant-seeds` seeds
also, each put in the program's place and compared with the same
reference (the upper readings): the reference computed with operands
rounded to a narrower type (the control), the reference with one
mechanism broken (`no_decay`: alpha = 1; `no_routed`: the held experts'
part zeroed; `rotary_on`: a rotary embedding this model does not have),
and `half`: the program fed rows whose second half of positions repeats
the first. (`unchanged`, a step that returns its state, reads `grad1` =
`dparam` = 1 by construction and needs no run.) One JSON line a seed;
`norms` holds each side's first-gradient norm by leaf, so that a number
over a group of leaves can be read again without a chip. Needs the chip
unless `--dry-run`.
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


def half_rows(batches):
    """The second half of every row's positions repeats the first."""
    import numpy as np
    out = []
    for ids, _ in batches:
        h = ids.shape[1] // 2
        ids = np.concatenate([ids[:, :h], ids[:, :h]], axis=1)
        out.append((ids, np.roll(ids, -1, axis=1)))
    return out


def main() -> int:
    from benchmark import run as bench
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="")
    ap.add_argument("--variant-seeds", type=int, default=2)
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
    groups = bench.leaf_groups(cell, ref.net)
    variants = [v for v in args.variants.split(",") if v]
    make = driver.make_batches
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        prep = driver.prepare(cell.cfg, cell.traffic, seed, overrides, ref)
        program, batches = prep.readings, prep.batches
        driver.free(prep)
        t1 = time.perf_counter()
        reference = driver.reference_readings(
            ref, cell.cfg, cell.traffic, seed, batches)
        row = {"workload": cell.name, "seed": seed,
               "loss": reference["loss"], "program_loss": program["loss"],
               "program_s": t1 - t0, "reference_s": time.perf_counter() - t1}
        row["program"], row["program_leaf"] = driver.compare(
            program, reference, groups)
        row["norms"] = {"reference": reference["grad1"],
                        "program": program["grad1"]}
        for v in (variants if n < args.variant_seeds else []):
            if v == "half":
                driver.make_batches = lambda *a, **k: half_rows(make(*a, **k))
                try:
                    prep = driver.prepare(cell.cfg, cell.traffic, seed,
                                          overrides, ref)
                finally:
                    driver.make_batches = make
                got = prep.readings
                driver.free(prep)
            else:
                got = driver.reference_readings(
                    driver.make_reference(cell.cfg, overrides, v),
                    cell.cfg, cell.traffic, seed, batches)
            row[v], row[v + "_leaf"] = driver.compare(got, reference, groups)
            row["norms"][v] = got["grad1"]
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
