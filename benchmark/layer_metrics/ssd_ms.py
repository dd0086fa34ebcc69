"""Device time a step under the `ssd` sub-scope of the `mamba2` layers
(ops/ssd.py: the step's softplus and decays, the pair matrices of every
chunk, the chunks' contributions to the state, the scan over chunks and
the outputs from the state, and the D term), both directions, a
checkpointed layer's second forward among the backward's. Read by
sub-scope (`scope_sub`), loops left out. Silent on a net without such a
layer and on a program without the scope."""

from benchmark import scope_sub


def read(obs):
    return scope_sub.sub_ms_a_step(obs, "mamba2", "ssd")
