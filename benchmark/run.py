"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from
`BENCHMARK.json`: the configuration's file, the traffic mix's file
(`benchmark/traffic/<traffic>.json`, which names its driver under
`benchmark/drivers/`), the cell's limits (`benchmark/limits/<cell>.json`)
and one reader for each per-layer metric
(`benchmark/layer_metrics/<metric>.py`). See `benchmark/README.md`.

The last line of standard output is the result; what was compared, each
number beside its limit, is also the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                         # noqa: E402
import importlib.util                   # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import shutil                           # noqa: E402
import statistics                       # noqa: E402
import sys                              # noqa: E402
import tempfile                         # noqa: E402
from types import SimpleNamespace       # noqa: E402
from typing import Any, Dict, List, Optional   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_DEVICE = 3
EXIT_NO_PROGRAM = 4


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A module from a file whose name may hold dots; one object a file,
    so that a test can reach in and break it."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> SimpleNamespace:
    """The cell's entry, its configuration, traffic, limits and the
    per-layer metrics that list it."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[name]
    centry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, centry["file"]))
    from benchmark import confnet
    with open(os.path.join(ROOT, cfg["conf"])) as f:
        cfg["conf_text"] = confnet.with_layer_pairs(
            f.read(), cfg.get("layer_overrides", {}))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(HERE, "limits", name + ".json"))

    def listed(metric):
        return name in metric.get("workloads", [name])

    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), cfg=cfg, traffic=traffic,
        limits=limits["limits"],
        end_to_end=[m for m in spec["end_to_end"] if listed(m)],
        per_layer=[m for m in spec["per_layer"] if listed(m)])


def find_devices(chips: int, dry_run: bool):
    """The devices, and the seconds the runtime took to come up (the
    first `jax.devices()` call). A real run needs `chips` TPU chips."""
    import jax
    t = time.perf_counter()
    devs = jax.devices()
    runtime_s = time.perf_counter() - t
    if not dry_run and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"benchmark: needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)
    return devs, runtime_s


def device_report(devs, chips: int) -> Dict[str, Any]:
    """The device as JAX reports it, and the peak on the fullest chip.
    This runtime keeps two counts: `peak_bytes_in_use`, the buffers the
    process held, and `peak_bytes_reserved`, what the loaded programs
    set aside for their temporaries (a step's saved activations live
    there, not among the buffers). Both are held while a step runs, so
    the peak is their sum."""
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_per_layer(cell, obs) -> Dict[str, Dict[str, Any]]:
    """One reader a metric; a reader that finds nothing returns None and
    its metric is left out of the line."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(HERE, "layer_metrics",
                                          m["name"] + ".py"))
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="rehearsal on the CPU at the configuration's "
                         "tiny overrides: prints no metric")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    try:
        from cxxnet_tpu.utils.platform import setup_compile_cache
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e})",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    import jax
    cache_dir = setup_compile_cache()
    # every program of a run, the small ones too, comes out of the
    # cache from the second run on: set-up stays the same work
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs, runtime_s = find_devices(cell.chips, args.dry_run)
    log(f"{cell.name} seed {args.seed} on {len(devs)} x "
        f"{devs[0].device_kind} (runtime up in {runtime_s:.1f} s); "
        f"compile cache {cache_dir}")

    from benchmark import trace_reduce
    from benchmark.compile_log import CompileLog
    clog = CompileLog()
    driver = load_module(os.path.join(
        HERE, "drivers", cell.traffic["driver"] + ".py"))
    overrides = dict(cell.cfg["overrides"])
    if args.dry_run:
        overrides.update(cell.cfg["dry_run_overrides"])
    ref = driver.make_reference(cell.cfg, overrides)

    prep = driver.prepare(cell.cfg, cell.traffic, args.seed, overrides, ref)
    log(f"set-up done: {len(clog.events)} programs built or loaded "
        f"({clog.total_s:.1f} s; cache hits {clog.hits}, misses "
        f"{clog.misses})")
    batches = prep.batches
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        win = driver.window(prep, args.seconds, cell.traffic)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    # the runtime's own start is nobody's work to move or to mend, and
    # swings by seconds between runs of one code: it is left out
    setup_s = win.t0 - T_START - runtime_s
    compiles = clog.between(win.t0, win.t1)
    device = device_report(devs, cell.chips)
    log(f"window: {win.attempted} operations in {win.wall_s:.3f} s; "
        f"peak {device['memory_peak_bytes']} bytes")
    program = prep.readings
    driver.free(prep)

    breakdown = None
    obs = SimpleNamespace(
        cell=cell, net=ref.net, rows=int(overrides["batch_size"]),
        window=win, device_kind=devs[0].device_kind, trace=None,
        span=None, device_events=[])
    if args.trace:
        trace = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = trace_reduce.window_of(trace)
        obs.trace, obs.span = trace, span
        chips = [trace_reduce.clip(ops, *span)
                 for ops in list(trace.devices.values())[:cell.chips]]
        device["busy_s"] = statistics.fmean(
            trace_reduce.busy_ns(ops) / 1e9 for ops in chips) if chips else 0.0
        if chips:
            obs.device_events = first = chips[0]
            breakdown = {
                "device_ops": [[trace.labels.get(n, n), secs] for n, secs
                               in trace_reduce.top_ops(first)],
                "idle_gaps": [list(r) for r in trace_reduce.idle_by_host(
                    first, trace.host, *span)]}
        device["window_s"] = (span[1] - span[0]) / 1e9 if span else 0.0

    t_ref = time.perf_counter()
    reference = driver.reference_readings(ref, cell.cfg, cell.traffic,
                                          args.seed, batches)
    log(f"reference done in {time.perf_counter() - t_ref:.1f} s")
    numbers, where = driver.compare(program, reference)
    numbers["compiles_in_window"] = float(len(compiles))
    # a number the cell's limits do not name has no upper reading (see
    # PERF.md): it is printed as a reading and decides nothing
    compared = {name: {"value": numbers[name], "limit": float(limit)}
                for name, limit in cell.limits.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    if args.trace:
        metrics = read_per_layer(cell, obs)
    else:
        values = dict(win.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    if args.dry_run:
        log("dry run on " + devs[0].platform + ": these are not device "
            "numbers: " + json.dumps(metrics))
        metrics = {}
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": win.attempted,
        "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared

    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    for name, value in numbers.items():
        if name not in compared:
            print(f"reading      {name} = {value:.6g} (not compared)",
                  file=sys.stderr)
    for name, c in compared.items():
        mark = "ok  " if c["value"] <= c["limit"] else "OVER"
        at = f" (worst leaf {where[name]})" if name in where else ""
        print(f"compared {mark} {name} = {c['value']:.6g} limit "
              f"{c['limit']:.6g}{at}", file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
