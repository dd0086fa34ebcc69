"""The flash attention kernels' share of the chip's bf16 peak under the
`gqa` layers. Time: the device events named `flash_fwd` / `flash_dq` /
`flash_dkv` (full layers) and `flash_win_fwd` / `flash_win_dq` /
`flash_win_dkv` (window layers), summed, the second forward that
`remat` runs among them. Work: what the conf's `gqa` layers require
(`kernel_work_attention.causal_attention_flop`: the pairs a query sees
and no other, two products forward and five backward, nothing
recomputed). Bound: compute. Silent on a net without `gqa` layers and
where the trace holds no such event."""

from benchmark import kernel_work_attention, peaks, trace_reduce

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv",
           "flash_win_fwd", "flash_win_dq", "flash_win_dkv")


def read(obs):
    layers = [l for l in obs.net.layers if l.type == "gqa"]
    ns = trace_reduce.kernel_ns(obs.device_events, KERNELS)
    if not layers or not ns or not obs.window.steps:
        return None
    flop = sum(kernel_work_attention.causal_attention_flop(
        obs.rows, l.in_shapes[0][0], int(l.get("nhead", "0")),
        int(l.get("head_dim", "0")), int(l.get("window", "0")))
        for l in layers) * obs.window.steps
    peak = peaks.peaks_for(obs.device_kind)["bf16_flop_per_s"]
    return 100.0 * (flop / peak) / (ns / 1e9)
