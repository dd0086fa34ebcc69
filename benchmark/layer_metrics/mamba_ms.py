"""Device time a step in the `mamba2` layers (the input projection, the
causal conv, the SSD in chunks, the gated group norm, the output
projection), both directions: `jvp(mamba2.<key>)` and
`transpose(jvp(mamba2.<key>))`; under `remat = 1` the backward's share
holds the layer's second forward. Loops are counted once
(`scope_leaf`'s table, made from `scope_sub`'s one compile). Silent on
a net without such a layer."""

from benchmark import scope_keys, scope_sub


def read(obs):
    if not any(l.type == "mamba2" for l in obs.net.layers):
        return None
    scope_sub.leaf_table(obs)
    return scope_keys.ms_a_step(obs, "mamba2", lambda l: True)
