"""`kda_kernel_ms` on a made-up trace: the two kernels' events count,
under whatever wrapping XLA gave their names; an operation that only
reads a kernel's result, or any other kernel, does not; a trace without
them reads nothing."""

import os
from types import SimpleNamespace

import pytest

from benchmark import run

STEP = [("jvp_kda_local_fwd_.3", 0.0, 3000.0), ("fusion.7", 3000.0, 500.0),
        ("kda_local_fwd.9", 3500.0, 3100.0), ("flash_fwd.2", 6600.0, 900.0),
        ("kda_local_bwd", 7500.0, 9000.0), ("while.4", 16500.0, 70.0),
        ("copy.11", 16570.0, 30.0)]


def reader():
    return run.load_module(os.path.join(run.HERE, "layer_metrics",
                                        "kda_kernel_ms.py"))


def obs(events, steps):
    return SimpleNamespace(device_events=list(events),
                           window=SimpleNamespace(steps=steps))


@pytest.mark.parametrize("steps", [1, 2, 5])
def test_the_kernels_events_give_ms_a_step(steps):
    events = [(n, s + i * 20000.0, d) for i in range(steps)
              for n, s, d in STEP]
    got = reader().read(obs(events, steps))
    assert got == pytest.approx((3000 + 3100 + 9000) / 1e6)


def test_one_direction_alone_still_reads():
    """`task = pred` runs the forward kernel only."""
    got = reader().read(obs([e for e in STEP if "bwd" not in e[0]], 1))
    assert got == pytest.approx((3000 + 3100) / 1e6)


def test_silent_without_the_kernels():
    others = [e for e in STEP if "kda_local" not in e[0]]
    assert reader().read(obs(others, 1)) is None
    assert reader().read(obs([], 3)) is None
