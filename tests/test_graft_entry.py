"""Driver-contract checks: dryrun_multichip on the virtual 8-device CPU
mesh (conftest forces the platform), and entry() buildability."""

import os
import subprocess
import sys

import pytest

import __graft_entry__ as ge


def test_dryrun_multichip_8():
    ge.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_16_composed():
    """The 16-device run includes phase 5 (dp x tp x sp x pp in ONE
    mesh). Needs a fresh process: the suite's backend is pinned to 8
    virtual devices at first jax import."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=16")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as ge; ge.dryrun_multichip(16)"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]


def test_entry_builds_flagship():
    fn, (params, data) = ge.entry()
    assert data.shape == (32, 3, 227, 227)
    # flagship net: AlexNet fc8 produces 1000-way logits
    assert params["fc8"]["wmat"].shape[0] == 1000


def test_entry_fails_without_a_chip(monkeypatch):
    """entry() keeps `dev = tpu`: where JAX found no TPU (and nothing
    outside said JAX_PLATFORMS=cpu) it fails like every other caller
    instead of building the flagship on the host."""
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="dev = tpu"):
        ge.entry()
