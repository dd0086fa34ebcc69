"""Device time a step in the `gqa` layers whose heads are 64 wide, half
a lane tile (projections, QK-norm, rotary, the flash kernels, the output
projection), both directions, as `gqa_full_ms` reads the 128-wide
layers of another cell: the keys whose conf layer says `head_dim = 64`.
Silent on a net without such a layer."""

from benchmark import scope_keys


def is_d64(layer) -> bool:
    return int(layer.get("head_dim", "0")) == 64


def read(obs):
    return scope_keys.ms_a_step(obs, "gqa", is_d64)
