"""Device time a step in the `gqa` layers WITH a window (rotary, the
window flash kernels), both directions, as `gqa_full_ms` reads the
others: the keys whose conf layer says `window` > 0. A window layer
walks only the score tiles of its band, so a layer here should cost
clearly less than a layer there. Silent on a net without such a
layer."""

from benchmark import scope_keys


def read(obs):
    return scope_keys.ms_a_step(obs, "gqa", scope_keys.has_window)
