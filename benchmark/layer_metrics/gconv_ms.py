"""Device time a step in the `gconv` layers (the double-gated short
convolution: the 3 x hidden projection, B * z, the causal depthwise
conv, C * c, the output projection), both directions: `jvp(gconv.<key>)`
and `transpose(jvp(gconv.<key>))`; under `remat = 1` the backward's
share holds the layer's second forward. Loops are counted once
(`scope_leaf.table`). Silent on a net without such a layer."""

from benchmark import scope_keys


def read(obs):
    return scope_keys.ms_a_step(obs, "gconv", lambda l: True)
