"""The whole step's share of the chip's bf16 peak: the FLOP the conf's
conv and fullc layers require (benchmark/flops.py) for all images of the
traced window, over the window's wall time and the peak of the device
kind (benchmark/peaks.json). Bound: compute."""

from benchmark import flops, peaks


def read(obs):
    if obs.window.steps == 0 or obs.device_kind == "cpu":
        return None
    peak = peaks.peaks_for(obs.device_kind)["bf16_flop_per_s"]
    work = flops.train_flop_per_image(obs.net) * obs.window.images
    return 100.0 * work / (obs.window.wall_s * peak * obs.cell.chips)
