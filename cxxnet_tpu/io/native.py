"""ctypes binding for the native io pipeline (native/cxxnet_io.cc).

The native library implements the reference's two-stage decode pipeline
(iter_thread_imbin_x-inl.hpp:18-397) in C++: a page-reader thread streams
64MiB BinaryPages, a worker pool decodes JPEG/PNG blobs off the GIL, and
records are handed back strictly in stream order. Python keeps the .lst
parsing, label join, shuffle, augmentation, and batching.

The library lives at cxxnet_tpu/lib/libcxxnet_io.so and is built from
native/cxxnet_io.cc of THIS checkout: the first use runs `make -C
native` for it, so make decides staleness - a copy on disk that make
would rebuild (older than its source) is rebuilt, never loaded as is.
$CXXNET_TPU_NATIVE names a prebuilt library instead and is loaded
untouched. `native_available()` gates all use; when the build or the
load fails every consumer takes the pure-Python (PIL) decoder, and
the failure is reported once on stderr first.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

from cxxnet_tpu import telemetry

_LIB_NAME = "libcxxnet_io.so"
_lib = None
_lib_lock = threading.Lock()
# one build + load attempt per process (both written under _lib_lock)
_load_attempted = False

_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(os.path.dirname(_PKG), "native")


class CxioRecord(ctypes.Structure):
    _fields_ = [("data", ctypes.POINTER(ctypes.c_ubyte)),
                ("h", ctypes.c_int),
                ("w", ctypes.c_int),
                ("c", ctypes.c_int)]


def _build(path: str) -> str:
    """Bring `path` up to date with native/cxxnet_io.cc through make
    ("" on success, else what went wrong). Only the io target: the
    C-ABI wrapper library needs python3-dev and is not ours to
    require here. A checkout without native/ (an installed package)
    has nothing to build from and keeps the copy it shipped with."""
    if not os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
        return "" if os.path.exists(path) else "library not built"
    target = os.path.relpath(path, _NATIVE_DIR)
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, target], check=True,
                       capture_output=True, text=True, timeout=120)
    except subprocess.CalledProcessError as e:
        return f"make failed: {(e.stderr or e.stdout).strip()[-300:]}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"make could not run: {e}"
    return ""


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    with _lib_lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        path = os.environ.get("CXXNET_TPU_NATIVE", "")
        err = ""
        if not path:
            path = os.path.join(_PKG, "lib", _LIB_NAME)
            err = _build(path)
        lib = None
        if not err:
            try:
                lib = ctypes.CDLL(path)
            except OSError as e:
                err = f"load failed: {e}"
        if lib is None:
            telemetry.stderr(
                f"native io: {path} unavailable ({err}); image "
                "iterators decode with PIL instead\n",
                event_kind="config", type="native_io_unavailable",
                path=path, error=err)
            return None
        lib.cxio_open.restype = ctypes.c_void_p
        lib.cxio_open.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                  ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int]
        lib.cxio_before_first.argtypes = [ctypes.c_void_p]
        lib.cxio_next.restype = ctypes.c_int
        lib.cxio_next.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(CxioRecord)]
        lib.cxio_last_error.restype = ctypes.c_char_p
        lib.cxio_last_error.argtypes = [ctypes.c_void_p]
        lib.cxio_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class NativeBinReader:
    """Ordered record stream over one or more .bin files.

    out_mode 1 (default): (c,h,w) float32 CHW, converted on the native
    worker threads - the host-augmentation layout. out_mode 2: (c,h,w)
    uint8 CHW - device-side augmentation staging (device_augment=1),
    1/4 the f32 bytes end-to-end."""

    def __init__(self, bin_paths: List[str], n_threads: int = 4,
                 max_inflight: int = 64, out_mode: int = 1):
        lib = _load()
        if lib is None:
            raise RuntimeError("native io library unavailable")
        self._lib = lib
        self._mode = out_mode
        arr = (ctypes.c_char_p * len(bin_paths))(
            *[p.encode() for p in bin_paths])
        self._h = lib.cxio_open(arr, len(bin_paths), n_threads,
                                max_inflight, out_mode)
        self._rec = CxioRecord()

    def before_first(self) -> None:
        self._lib.cxio_before_first(self._h)

    def next(self) -> Optional[np.ndarray]:
        """Next decoded image as (c,h,w) CHW (f32 or u8 per out_mode),
        or the raw blob decoded via PIL when the native decoders could
        not handle it. None at end of stream (raises on stream error)."""
        if not self._lib.cxio_next(self._h, ctypes.byref(self._rec)):
            err = self._lib.cxio_last_error(self._h)
            if err:
                raise IOError(err.decode())
            return None
        r = self._rec
        if r.c == 0:  # undecodable natively; PIL fallback on the raw blob
            from cxxnet_tpu.io.iter_img import decode_image
            blob = ctypes.string_at(r.data, r.w)
            img = decode_image(blob)  # uint8 CHW
            return img if self._mode == 2 else img.astype(np.float32)
        n = r.h * r.w * r.c
        if self._mode == 2:
            u8 = ctypes.cast(r.data, ctypes.POINTER(ctypes.c_uint8))
            return np.ctypeslib.as_array(u8, shape=(n,)).reshape(
                r.c, r.h, r.w).copy()
        # the record already is CHW float32 (converted on the native
        # worker threads); one memcpy to own the buffer
        fptr = ctypes.cast(r.data, ctypes.POINTER(ctypes.c_float))
        return np.ctypeslib.as_array(fptr, shape=(n,)).reshape(
            r.c, r.h, r.w).copy()

    def close(self) -> None:
        if self._h:
            self._lib.cxio_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
