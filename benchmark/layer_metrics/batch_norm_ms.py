"""Device time a step in the `batch_norm` layers, both directions:
`jvp(batch_norm.<key>)` and `transpose(jvp(batch_norm.<key>))`
(benchmark/scope_map.py). Silent on a net without such a layer."""

from benchmark import scope_map


def read(obs):
    return scope_map.ms_a_step(obs, "fwd", "bwd", kind="batch_norm")
