"""Device time a step in the two Pallas kernels of KDA's chunk-local
part (`cxxnet_tpu/ops/pallas_kda.py`): the device events named
`kda_local_fwd` / `kda_local_bwd`, which XLA wraps
(`jvp_kda_local_fwd_.3`) but keeps. With `remat = 1` a step runs the
forward kernel twice and the backward once a `kda` layer. `kda_ms` less
this is what the layer spends outside the kernels. Silent where the
trace holds no such event: the step took `route.xla`, or the program
has no such kernels."""

from benchmark import trace_reduce

KERNELS = ("kda_local_fwd", "kda_local_bwd")


def read(obs):
    events = obs.device_events
    if not any(trace_reduce.named(events, k) for k in KERNELS):
        return None
    return trace_reduce.kernel_ns(events, KERNELS) / obs.window.steps / 1e6
