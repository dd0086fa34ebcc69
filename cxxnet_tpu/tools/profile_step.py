"""Capture + summarize a device profile of the AlexNet train step.

The reference exposes wall-clock timing only (cxxnet_main.cpp's elapsed
prints); the TPU-native replacement is a real device trace:
`jax.profiler` captures an XSpace, and this tool aggregates per-op
device time so "where does the step go" is a committed number, not a
guess. Output: top-N ops by self time + total
step accounting, printed and optionally written as markdown.

Usage:
  python -m cxxnet_tpu.tools.profile_step [--steps N] [--out FILE.md]
                                          [--trace-dir DIR]

Runs the same end-to-end loop bench.py times (trainer.update on host
batches), wrapped in jax.profiler.start_trace/stop_trace, then parses
the .xplane.pb with jax.profiler.ProfileData.
"""

from __future__ import annotations

import glob
import os
import sys
import tempfile
from collections import defaultdict


def capture(trace_dir: str, steps: int = 20) -> str:
    """Run bench.py's e2e loop under the profiler; returns the xplane
    path. Reuses the exact harness the headline number comes from so the
    trace explains the benchmark, not a lookalike loop."""
    import jax
    try:
        import bench
        from __graft_entry__ import _ALEXNET_CONF, _make_trainer
    except ImportError as e:
        raise RuntimeError(
            "profile_step reuses the repo-root bench.py harness; run it "
            "from a source checkout root (bench/__graft_entry__ are not "
            "packaged)") from e
    from cxxnet_tpu.utils.config import parse_config_file
    from cxxnet_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    platform = jax.devices()[0].platform
    batch = 256 if platform != "cpu" else 8
    trainer = _make_trainer(
        parse_config_file(_ALEXNET_CONF),
        [("batch_size", str(batch)), ("dev", "tpu"), ("silent", "1"),
         ("eval_train", "0"), ("save_model", "0")])
    ips, n = bench._measure_e2e(trainer, batch, steps, trace_dir)
    print(f"traced {n} steps at {ips:.1f} images/sec")

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_table(xplane_path: str, top: int = 25):
    """Aggregate device-plane op self-times from an XSpace dump.
    Returns ([(op_name, total_ns)] sorted desc, total_ns) - the data
    behind summarize(), reused by bench.py's compact top_ops field."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    dev_planes = [p for p in data.planes if "/device:" in p.name]
    if not dev_planes:  # CPU runs put XLA ops on the host plane
        dev_planes = [p for p in data.planes if p.name == "/host:CPU"]
    op_time = defaultdict(float)
    total = 0.0
    for plane in dev_planes:
        # a device plane carries parallel lines (Steps / XLA Modules /
        # XLA Ops) covering the same wall time - summing all of them
        # would triple-count; the "XLA Ops" line holds the leaf op
        # self-times. Host planes (CPU smoke runs) have thread lines
        # only, which don't nest the same way.
        lines = [l for l in plane.lines if l.name == "XLA Ops"] \
            or list(plane.lines)
        for line in lines:
            for ev in line.events:
                dur = ev.duration_ns
                name = ev.name
                op_time[name] += dur
                total += dur
    return sorted(op_time.items(), key=lambda kv: -kv[1])[:top], total


def summarize(xplane_path: str, top: int = 25) -> str:
    """Markdown table of op_table()."""
    rows, total = op_table(xplane_path, top)
    out = ["| op | total ms | % of device time |",
           "|---|---|---|"]
    for name, ns in rows:
        out.append(f"| `{name[:70]}` | {ns / 1e6:.2f} | "
                   f"{100.0 * ns / max(total, 1):.1f}% |")
    out.append(f"\nTotal accounted {total / 1e6:.1f} ms")
    return "\n".join(out)


def main(argv) -> int:
    steps = 20
    out_file = ""
    trace_dir = ""
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    if "--out" in argv:
        out_file = argv[argv.index("--out") + 1]
    if "--trace-dir" in argv:
        trace_dir = argv[argv.index("--trace-dir") + 1]
    tmp = trace_dir or tempfile.mkdtemp(prefix="cxn_profile_")
    xplane = capture(tmp, steps)
    md = summarize(xplane)
    print(md)
    if out_file:
        with open(out_file, "w") as fo:
            fo.write("# AlexNet train-step device profile\n\n"
                     f"Captured from `{xplane}`, {steps} steps.\n\n"
                     + md + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
