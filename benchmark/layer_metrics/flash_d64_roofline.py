"""The flash attention kernels' share of the chip's bf16 peak at a
64-wide head, half a lane tile (the score product contracts over 64 of
the MXU's 128 rows). Time: the device events named `flash_fwd` /
`flash_dq` / `flash_dkv`, summed, the second forward that `remat` runs
among them. Work: what the conf's `gqa` layers of `head_dim = 64`
require (`kernel_work_attention.causal_attention_flop`: the pairs a
query sees and no other, two products forward and five backward, nothing
recomputed). Bound: compute. Silent on a net without such a layer, on
one whose other layers share those kernels (`mla`, a `gqa` layer of
another width: their time would be counted against this work) and where
the trace holds no such event."""

from benchmark import kernel_work_attention, peaks, scope_keys, trace_reduce

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def is_d64(layer) -> bool:
    """A full causal layer of 64-wide heads (a window layer runs the
    `flash_win_*` kernels)."""
    return int(layer.get("head_dim", "0")) == 64 \
        and not scope_keys.has_window(layer)


def read(obs):
    layers = scope_keys.conf_layers(obs, "gqa", is_d64)
    others = [l for l in obs.net.layers if l.type in ("gqa", "mla")
              and l not in layers]
    ns = trace_reduce.kernel_ns(obs.device_events, KERNELS)
    if not layers or others or not ns or not obs.window.steps:
        return None
    flop = sum(kernel_work_attention.causal_attention_flop(
        obs.rows, l.in_shapes[0][0], int(l.get("nhead", "0")), 64)
        for l in layers) * obs.window.steps
    peak = peaks.peaks_for(obs.device_kind)["bf16_flop_per_s"]
    return 100.0 * (flop / peak) / (ns / 1e9)
