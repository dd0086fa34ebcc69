"""Device time a step in operations of the updater alone: traced under
`update/<key>` and not fused into a layer's gradient
(benchmark/scope_map.py)."""

from benchmark import scope_map


def read(obs):
    return scope_map.ms_a_step(obs, "update")
