"""Device time a step in the forward pass: the operations traced under
`jvp(<type>.<key>)`, every layer of `Network.forward`, summed over the
traced window and divided by its steps (benchmark/scope_map.py)."""

from benchmark import scope_map


def read(obs):
    return scope_map.ms_a_step(obs, "fwd")
