"""Device time a step in the backward pass: the operations traced under
`transpose(jvp(<type>.<key>))`. With `update_period = 1` a weight
gradient's fusion also holds that weight's update and counts here
(benchmark/scope_map.py says why; `mixed_pct` on standard error says how
much)."""

from benchmark import scope_map


def read(obs):
    return scope_map.ms_a_step(obs, "bwd")
