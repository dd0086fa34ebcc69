"""Device time a step in the `gqa` layers WITHOUT a window (full causal
attention, no positional encoding: projections, the flash kernels, the
output projection), both directions: `jvp(gqa.<key>)` and
`transpose(jvp(gqa.<key>))` of the keys whose conf layer says
`window = 0`; under `remat = 1` the backward's share holds the layer's
second forward. Loops are counted once (`scope_leaf.table`). Silent on
a net without such a layer."""

from benchmark import scope_keys


def read(obs):
    return scope_keys.ms_a_step(
        obs, "gqa", lambda l: not scope_keys.has_window(l))
