"""Where XLA's persistent compilation cache lives.

An AlexNet-sized TPU compile costs tens of seconds; the persistent
cache turns every later run of the same program (resume, pred, serve,
a second benchmark run) into a load. The directory is part of the
cache key's lookup, so it must be the SAME path run after run: either
the one the environment names, or one fixed path inside the checkout -
never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compile_cache() -> str:
    """Place the compile cache before the first compile; returns the
    directory in use. Called by every entry point that compiles
    (main.py, chip_smoke.py, benchmark/run.py).

    `JAX_COMPILATION_CACHE_DIR` set: jax read it at import and nothing
    is set in code - no subdirectory, no override - so whoever runs the
    program decides where its cache lives. Unset:
    `<checkout>/.jax_cache` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
