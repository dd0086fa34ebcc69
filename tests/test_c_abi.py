"""Builds and runs the native C ABI driver (native/test_driver.c) against
libcxxnetwrapper.so - the analog of the reference's wrapper/ test-by-use
(its C ABI had no tests; this is the improvement SURVEY.md par.4 calls
for). The C process embeds its own CPython, so it runs as a subprocess
with the venv's site-packages + repo on PYTHONPATH."""

import gzip
import os
import struct
import subprocess
import sysconfig

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")
LIBDIR = os.path.join(REPO, "cxxnet_tpu", "lib")
LIB = os.path.join(LIBDIR, "libcxxnetwrapper.so")


def _build(tmp_path, cc: str) -> str:
    subprocess.run(["make", "-C", NATIVE], check=True,
                   capture_output=True)
    exe = str(tmp_path / "test_driver")
    subprocess.run(
        [cc, "-O1", "-o", exe, os.path.join(NATIVE, "test_driver.c"),
         "-I", NATIVE, "-L", LIBDIR, "-lcxxnetwrapper", "-lm",
         f"-Wl,-rpath,{LIBDIR}"],
        check=True, capture_output=True)
    return exe


def _write_mnist(tmp_path, n=96):
    rng = np.random.RandomState(0)
    images = rng.randint(0, 255, size=(n, 28, 28)).astype(np.uint8)
    labels = rng.randint(0, 10, size=n).astype(np.uint8)
    img = tmp_path / "img.gz"
    lab = tmp_path / "lab.gz"
    with gzip.open(img, "wb") as f:
        f.write(struct.pack(">iiii", 2051, n, 28, 28))
        f.write(images.tobytes())
    with gzip.open(lab, "wb") as f:
        f.write(struct.pack(">ii", 2049, n))
        f.write(labels.tobytes())
    return img, lab


def test_c_abi_driver(tmp_path):
    import shutil
    cc = shutil.which("gcc") or shutil.which("cc")
    if cc is None:
        pytest.skip("no C compiler")
    exe = _build(tmp_path, cc)
    img, lab = _write_mnist(tmp_path)
    iter_cfg = (
        "iter = mnist\n"
        f'path_img = "{img}"\n'
        f'path_label = "{lab}"\n'
        "input_flat = 0\n"
        "batch_size = 32\n"
        "iter = end\n")
    env = dict(os.environ)
    site = sysconfig.get_paths()["purelib"]
    inherited = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                 if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO, site] + inherited)
    env["JAX_PLATFORMS"] = "cpu"  # embedded python must not try the TPU
    out = subprocess.run(
        [exe, str(tmp_path / "model.bin"), iter_cfg],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "all checks passed" in out.stdout
    assert "train accuracy" in out.stdout
