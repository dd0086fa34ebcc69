"""The chip bring-up contract, checked on the CPU (ISSUE 21).

What the program does on the machine with the chip cannot run here;
what CAN is every decision that used to hide the device: `dev = tpu`
without a TPU raises instead of training on the host, the compile
cache goes where the environment says, a pruned mesh / a failed native
build / a failed staging says so, and `chip_smoke.py` refuses to start
without a TPU while its dry run rehearses every leg and can never
print a chip pass. (Kernel routing by mesh size lives beside the
kernels: test_pallas_lrn / test_pallas_attention / test_quantize.)
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NET_CFG = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[+1] = tanh
layer[+1] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,6
batch_size = 16
metric = error
silent = 1
"""


# ---------------------------------------------------------------------------
# dev = tpu is binding
# ---------------------------------------------------------------------------
def test_dev_tpu_without_a_tpu_raises(monkeypatch):
    """With JAX_PLATFORMS unset (a libtpu that failed to start leaves
    JAX on the CPU with a warning), `dev = tpu[:...]` must raise and
    name what it found - through the trainer and the wrapper alike."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.parallel.mesh import resolve_devices
    from cxxnet_tpu.wrapper import Net
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="platform is 'cpu'"):
        resolve_devices("tpu")
    for dev in ("tpu", "tpu:0-3"):
        tr = NetTrainer(dev=dev, cfg=NET_CFG)
        with pytest.raises(RuntimeError, match="dev = tpu"):
            tr.init_model()
    net = Net(dev="tpu", cfg=NET_CFG)
    with pytest.raises(RuntimeError, match="no tpu device"):
        net.init_model()
    # cpu / unnamed kinds take what JAX has
    assert resolve_devices("cpu") == jax.devices()
    assert resolve_devices("") == jax.devices()


def test_dev_tpu_runs_on_the_host_only_when_told_to(monkeypatch):
    """The one exemption: an explicit JAX_PLATFORMS that names cpu -
    an instruction from outside the program (tests, CI, the CPU verify
    recipe), not a fallback."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    for env in ("cpu", "tpu,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", env)
        tr = NetTrainer(dev="tpu:0-1", cfg=NET_CFG)
        tr.init_model()
        assert tr.mesh.devices.size == 2
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="dev = tpu"):
        NetTrainer(dev="tpu", cfg=NET_CFG).init_model()


def test_cli_dev_tpu_conf_fails_without_a_tpu(monkeypatch, tmp_path):
    """A `dev = tpu` conf through the CLI: every example conf says
    that, and used to train on the host and exit 0."""
    from cxxnet_tpu.main import main
    conf = tmp_path / "t.conf"
    conf.write_text(NET_CFG + "dev = tpu\nnum_round = 1\n"
                    f"model_dir = {tmp_path}/models\n")
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="dev = tpu"):
        main([str(conf)])
    assert not (tmp_path / "models").exists()


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------
@pytest.fixture
def cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_env_wins_and_code_sets_nothing(monkeypatch,
                                                      cache_config):
    """JAX_COMPILATION_CACHE_DIR set: jax read it at import; the
    program sets nothing - no subdirectory, no override."""
    from cxxnet_tpu.utils.platform import setup_compile_cache
    jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
    assert setup_compile_cache() == "/from/outside"
    assert jax.config.jax_compilation_cache_dir == "/sentinel/untouched"


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_config):
    """Unset: <checkout>/.jax_cache - a FIXED path (it is part of the
    cache key's lookup), never a temporary name, pid or time - and the
    CLI places it before its first compile."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.utils.platform import setup_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # ... and the CLI has placed it by the time init() can compile
    jax.config.update("jax_compilation_cache_dir", None)

    class Reached(Exception):
        pass

    def init(self):
        assert jax.config.jax_compilation_cache_dir == want
        raise Reached

    monkeypatch.setattr(LearnTask, "init", init)
    conf = os.path.join(REPO, "examples", "MNIST", "MNIST.conf")
    with pytest.raises(Reached):
        LearnTask().run([conf, "dev=cpu"])


# ---------------------------------------------------------------------------
# things that used to happen quietly
# ---------------------------------------------------------------------------
def test_pruned_data_axis_is_reported(capsys):
    """`dev = cpu:0-3` with a batch 4 does not divide trains on fewer
    devices than asked (reference parity) - and now says so."""
    from cxxnet_tpu.parallel.mesh import MeshSpec, build_mesh
    mesh = build_mesh(MeshSpec(device_indices=[0, 1, 2, 3]), 6)
    assert mesh.devices.size == 3
    err = capsys.readouterr().err
    assert "pruned from 4 to 3" in err and "batch_size 6" in err
    build_mesh(MeshSpec(device_indices=[0, 1, 2, 3]), 8)
    assert capsys.readouterr().err == ""


def test_wrapper_staging_absorbs_only_out_of_memory(monkeypatch,
                                                    capsys):
    """train() may fall back to streaming when the staged dataset does
    not fit the device - and must say so. Any other staging failure is
    a real error: it used to be swallowed with the dataset streamed
    behind the user's back."""
    import cxxnet_tpu.wrapper as W
    rng = np.random.RandomState(0)
    x = rng.randn(32, 1, 1, 6).astype(np.float32)
    y = (x.reshape(32, 6).sum(axis=1) > 0).astype(np.float32)
    orig = W.NetTrainer.stage_batch
    fail = []   # exceptions the next stage_batch calls raise, in order

    def stage(self, b):
        if fail:
            raise fail.pop(0)
        return orig(self, b)

    monkeypatch.setattr(W.NetTrainer, "stage_batch", stage)
    for err in (ValueError("bad batch"),
                jax.errors.JaxRuntimeError("INTERNAL: backend died")):
        fail[:] = [err]
        with pytest.raises(type(err)):
            W.train(NET_CFG, x, y, num_round=1, param={}, batch_size=16)
    capsys.readouterr()
    fail[:] = [jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: out of memory allocating 1 bytes")]
    net = W.train(NET_CFG, x, y, num_round=1, param={}, batch_size=16)
    assert net._net.epoch == 2   # streamed: both batches trained
    assert "ran out of memory; streaming" in capsys.readouterr().err


@pytest.fixture
def fresh_native(monkeypatch):
    from cxxnet_tpu.io import native
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    return native


def test_native_build_failure_is_reported_once(fresh_native,
                                               monkeypatch, capsys):
    """A failed build or load: one line on stderr, then the PIL
    decoder - not a silent drop."""
    native = fresh_native
    monkeypatch.delenv("CXXNET_TPU_NATIVE", raising=False)
    monkeypatch.setattr(native, "_build",
                        lambda path: "make failed: g++: not found")
    assert not native.native_available()
    assert not native.native_available()
    err = capsys.readouterr().err
    assert err.count("native io:") == 1
    assert "make failed: g++: not found" in err and "PIL" in err
    # a named library that does not load is reported the same way
    monkeypatch.setattr(native, "_load_attempted", False)
    monkeypatch.setenv("CXXNET_TPU_NATIVE", "/no/such/lib.so")
    assert not native.native_available()
    assert "load failed" in capsys.readouterr().err


@pytest.mark.skipif(shutil.which("make") is None
                    or shutil.which("g++") is None,
                    reason="needs make + g++")
def test_stale_native_library_is_rebuilt(fresh_native, monkeypatch):
    """make decides staleness: a library older than native/*.cc is
    rebuilt, not loaded as it is."""
    native = fresh_native
    monkeypatch.delenv("CXXNET_TPU_NATIVE", raising=False)
    lib = os.path.join(REPO, "cxxnet_tpu", "lib", native._LIB_NAME)
    src = os.path.join(REPO, "native", "cxxnet_io.cc")
    assert native.native_available()          # builds it if missing
    old = os.path.getmtime(src) - 100.0
    os.utime(lib, (old, old))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    assert native.native_available()
    assert os.path.getmtime(lib) > os.path.getmtime(src)


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------
def _run_smoke(args, cwd=REPO, script=None, **env):
    base = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    base.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py")]
        + args, cwd=cwd, env=base, capture_output=True, text=True,
        timeout=600)


def test_chip_smoke_refuses_without_a_tpu():
    """On a CPU-only machine: non-zero exit, the platform it found on
    stderr, and NO result on stdout."""
    r = _run_smoke([])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program: non-zero, no result - also
    past the platform gate (--dry-run), where only the missing
    package stops it."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    script = str(tmp_path / "chip_smoke.py")
    for args in ([], ["--dry-run"]):
        r = _run_smoke(args, cwd=str(tmp_path), script=script)
        assert r.returncode != 0, args
        assert "{" not in r.stdout, r.stdout
    assert "cxxnet_tpu" in r.stderr  # ModuleNotFoundError names it


def test_chip_smoke_dry_run_rehearses_every_leg(tmp_path):
    """--dry-run: every leg (the four-device one on the virtual mesh)
    at a tiny size with the kernels in interpret mode - and it cannot
    print a chip pass: "ok" stays false."""
    r = _run_smoke(
        ["--dry-run", "--out", str(tmp_path / "out")],
        XLA_FLAGS="--xla_force_host_platform_device_count=8")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the last line is the driver's contract: these keys and no others
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    tag = "[chip_smoke] summary "
    assert lines[-2].startswith(tag)
    summary = json.loads(lines[-2][len(tag):])
    assert summary["dry_run"] is True
    assert summary["legs"] == {"train": "pass", "serve": "pass",
                               "kernel": "pass", "lm": "pass",
                               "four": "pass"}
    for proof in ("no compile in round 2",
                  "the loss falls over three steps of one batch",
                  "the expert layers computed every held assignment",
                  "traces the Pallas LRN kernel 2x forward, 2x backward",
                  "names the one LRN route taken: ['route.pallas']",
                  "names the one LRN route taken: ['route.sharded']",
                  "8 rows alone == the same rows in the batch",
                  "task=serve output identical to task=pred",
                  "no compile after warmup()",
                  "LRN takes the shard_map route",
                  "per-step losses agree with the one-chip leg"):
        assert proof in r.stdout, proof
