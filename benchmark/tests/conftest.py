import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# the rehearsals below run on the CPU, whatever the machine holds
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmark import run as bench  # noqa: E402


class Spec:
    """BENCHMARK.json as the harness reads it: from `run.ROOT`, which
    test_spec_by_name.py points at a copy with entries appended. Every
    test that reads the file reads it through this, and finds an entry
    by its name, never by its place or the count of its list."""

    @property
    def root(self) -> str:
        return bench.ROOT

    def __call__(self) -> dict:
        with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
            return json.load(f)

    def entry(self, kind: str, name: str) -> dict:
        """The entry of `kind` ("configs", "workloads", "end_to_end",
        "per_layer") named `name`; fails where there is none."""
        found = [e for e in self()[kind] if e["name"] == name]
        assert len(found) == 1, f"{kind} has {len(found)} entries {name!r}"
        return found[0]


@pytest.fixture()
def spec() -> Spec:
    return Spec()


DRY_RUN_LINE = "dry run on cpu: these are not device numbers: "


SETUP_METRICS = ("setup_init_s", "setup_step_s", "setup_trace_s",
                 "setup_compile_s", "setup_traces")


@pytest.fixture()
def rehearsed():
    """The metrics a `--trace 1 --dry-run` of `cell` read and printed on
    standard error (its result line carries none): name -> value. Each
    is one the cell's entries list, and the five set-up metrics, which
    every cell lists, are there and above 0."""
    def read(err: str, cell: str) -> dict:
        line = next(l for l in err.splitlines() if DRY_RUN_LINE in l)
        got = {k: v["value"] for k, v in json.loads(
            line.split(DRY_RUN_LINE, 1)[1]).items()}
        listed = {m["name"] for m in bench.load_cell(cell).per_layer}
        assert set(got) <= listed
        assert all(got[n] > 0 for n in SETUP_METRICS)
        return got
    return read
