"""Device time a step in the `lrn` layers, both directions:
`jvp(lrn.<key>)` and `transpose(jvp(lrn.<key>))`
(benchmark/scope_map.py): the kernels or fusions of whichever route the
layer took, and every layout copy around them, which `lrn_roofline`
does not see. Silent on a net without such a layer."""

from benchmark import scope_map


def read(obs):
    return scope_map.ms_a_step(obs, "fwd", "bwd", kind="lrn")
