"""Device time a step in the backward pass of the `conv` layers, data
and weight gradients with the updates fused into the latter:
`transpose(jvp(conv.<key>))` (benchmark/scope_map.py). Silent on a net
without such a layer."""

from benchmark import scope_map


def read(obs):
    return scope_map.ms_a_step(obs, "bwd", kind="conv")
