"""Handle-based shim behind the native C ABI (native/cxxnet_wrapper.cc).

The reference exposes the trainer over a C ABI in
wrapper/cxxnet_wrapper.cpp:10-352; here the native library embeds CPython
and calls these functions. Raw device-independent data crosses the
boundary as integer pointer addresses + shapes (the C side owns the
buffers); objects live in a handle registry so the C side only ever holds
opaque uint64 ids.

Error contract: exceptions propagate to the embed layer, which fetches
them via the CPython error indicator and surfaces the message through
CXNGetLastError (cxxnet_wrapper.cc RecordPyError).
"""

from __future__ import annotations

import ctypes
import threading
from typing import Dict

import numpy as np

from cxxnet_tpu.wrapper import DataIter, Net

_lock = threading.Lock()
_objects: Dict[int, object] = {}
_next_id = 1


def _register(obj: object) -> int:
    global _next_id
    with _lock:
        hid = _next_id
        _next_id += 1
        _objects[hid] = obj
    return hid


def _get(hid: int):
    return _objects[hid]


def _as_f32(addr: int, *shape: int) -> np.ndarray:
    n = 1
    for s in shape:
        n *= int(s)
    buf = (ctypes.c_float * n).from_address(addr)
    return np.frombuffer(buf, dtype=np.float32).reshape(*shape)


def _copy_out(arr: np.ndarray, addr: int) -> int:
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    ctypes.memmove(addr, arr.ctypes.data, arr.nbytes)
    return arr.size


# ---------------------------------------------------------------------------
# object lifecycle
# ---------------------------------------------------------------------------

def net_create(dev: str, cfg: str) -> int:
    return _register(Net(dev=dev, cfg=cfg))


def io_create(cfg: str) -> int:
    return _register(DataIter(cfg))


def free(hid: int) -> None:
    with _lock:
        _objects.pop(hid, None)


# ---------------------------------------------------------------------------
# trainer surface (one function per CXN* entry point)
# ---------------------------------------------------------------------------

def net_set_param(hid: int, name: str, val: str) -> None:
    _get(hid).set_param(name, val)


def net_init_model(hid: int) -> None:
    _get(hid).init_model()


def net_load_model(hid: int, fname: str) -> None:
    _get(hid).load_model(fname)


def net_save_model(hid: int, fname: str) -> None:
    _get(hid).save_model(fname)


def net_start_round(hid: int, r: int) -> None:
    _get(hid).start_round(r)


def net_update_iter(hid: int, iter_hid: int) -> None:
    _get(hid).update(_get(iter_hid))


def net_update_batch(hid: int, daddr: int, b: int, c: int, h: int, w: int,
                     laddr: int, lwidth: int) -> None:
    net = _get(hid)
    data = _as_f32(daddr, b, c, h, w)
    # The caller owns its buffers again the moment this returns, but
    # the step is dispatched asynchronously and device_put may alias
    # host memory (the CPU backend does) - a caller that refills one
    # buffer per step (native/test_driver.c) would otherwise train on
    # half-overwritten batches. The labels and a host-cast (bf16) input
    # are fresh buffers by the time they are staged; an input staged as
    # f32 is the caller's memory itself, and only that case pays a copy.
    if net._net._host_input(data[:0]).dtype == data.dtype:
        data = data.copy()
    net.update(data, _as_f32(laddr, b, lwidth))


def net_evaluate(hid: int, iter_hid: int, name: str) -> str:
    return _get(hid).evaluate(_get(iter_hid), name)


def net_predict_batch(hid: int, daddr: int, b: int, c: int, h: int, w: int,
                      oaddr: int) -> int:
    """Writes b floats to oaddr; returns count."""
    pred = _get(hid).predict(_as_f32(daddr, b, c, h, w))
    return _copy_out(pred, oaddr)


def net_predict_iter(hid: int, iter_hid: int, oaddr: int, cap: int) -> int:
    preds = []
    it = _get(iter_hid)
    net = _get(hid)
    it.before_first()
    while it.next():
        # NetTrainer.predict already drops num_batch_padd rows (the
        # valid-mask truncation in _forward_nodes)
        preds.append(net.predict(it))
    out = np.concatenate(preds) if preds else np.zeros(0, np.float32)
    if out.size > cap:
        raise ValueError(f"output buffer too small: {out.size} > {cap}")
    return _copy_out(out, oaddr)


def net_extract_batch(hid: int, daddr: int, b: int, c: int, h: int, w: int,
                      node_name: str, oaddr: int, cap: int) -> int:
    feat = _get(hid).extract(_as_f32(daddr, b, c, h, w), node_name)
    if feat.size > cap:
        raise ValueError(f"output buffer too small: {feat.size} > {cap}")
    return _copy_out(feat, oaddr)


def net_get_weight(hid: int, layer_name: str, tag: str, oaddr: int,
                   cap: int, shape_addr: int) -> int:
    """Writes the 2-D flattened weight; shape_addr receives 2 uint64s.

    Returns element count, or 0 when the layer exists but has no weight
    under `tag` (CXNNetGetWeight returns NULL there); unknown layer
    names are errors."""
    net = _get(hid)
    if not net.has_layer(layer_name):
        raise KeyError(f"unknown layer name {layer_name}")
    try:
        w = net.get_weight(layer_name, tag)
    except KeyError:
        return 0
    if w.size > cap:
        raise ValueError(f"output buffer too small: {w.size} > {cap}")
    shp = (ctypes.c_uint64 * 2).from_address(shape_addr)
    shp[0], shp[1] = w.shape
    return _copy_out(w, oaddr)


def net_set_weight(hid: int, daddr: int, rows: int, cols: int,
                   layer_name: str, tag: str) -> None:
    _get(hid).set_weight(_as_f32(daddr, rows, cols), layer_name, tag)


# ---------------------------------------------------------------------------
# iterator surface
# ---------------------------------------------------------------------------

def io_next(hid: int) -> int:
    return 1 if _get(hid).next() else 0


def io_before_first(hid: int) -> None:
    _get(hid).before_first()


def io_get_data_shape(hid: int, shape_addr: int) -> None:
    d = _get(hid).get_data()
    shp = (ctypes.c_uint64 * 4).from_address(shape_addr)
    shp[0], shp[1], shp[2], shp[3] = d.shape


def io_copy_data(hid: int, oaddr: int) -> int:
    return _copy_out(_get(hid).get_data(), oaddr)


def io_get_label_shape(hid: int, shape_addr: int) -> None:
    lab = _get(hid).get_label()
    shp = (ctypes.c_uint64 * 2).from_address(shape_addr)
    shp[0], shp[1] = lab.shape


def io_copy_label(hid: int, oaddr: int) -> int:
    return _copy_out(_get(hid).get_label(), oaddr)
