"""Device time a step in the backward pass of the `max_pooling` layers:
`transpose(jvp(max_pooling.<key>))` (benchmark/scope_map.py). Silent on
a net without such a layer."""

from benchmark import scope_map


def read(obs):
    return scope_map.ms_a_step(obs, "bwd", kind="max_pooling")
