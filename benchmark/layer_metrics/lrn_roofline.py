"""The Pallas LRN kernels' share of their roofline. Time: the device
events named `lrn_fwd` / `lrn_bwd`. Work: the least bytes the algorithm
moves at the conf's LRN shapes in the conf's dtype (forward reads x and
writes y; backward reads x and dy and writes dx), over the chip's HBM
bandwidth. Bound: memory. Silent where the trace holds no such event."""

from benchmark import kernel_work, peaks, trace_reduce

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def read(obs):
    events = obs.device_events
    fwd = trace_reduce.named(events, "lrn_fwd")
    bwd = trace_reduce.named(events, "lrn_bwd")
    layers = [l for l in obs.net.layers if l.type == "lrn"]
    if not fwd or not bwd or not layers:
        return None
    item = _ITEMSIZE[obs.net.get("dtype", "float32")]
    shapes = [(obs.rows,) + tuple(l.out_shape) for l in layers]
    fwd_bytes = sum(kernel_work.lrn_fwd_bytes(s, item) for s in shapes)
    bwd_bytes = sum(kernel_work.lrn_bwd_bytes(s, item) for s in shapes)
    # each step runs every LRN layer once each way
    total = (len(fwd) * fwd_bytes + len(bwd) * bwd_bytes) / len(layers)
    seconds = trace_reduce.kernel_ns(events, ("lrn_fwd", "lrn_bwd")) / 1e9
    bandwidth = peaks.peaks_for(obs.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (total / bandwidth) / seconds
