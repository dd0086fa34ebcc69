"""Test configuration: run JAX on a virtual 8-device CPU platform.

Multi-chip sharding is validated on a host-platform mesh (the analog of the
reference's "local" parameter-server flavor standing in for the distributed
one - SURVEY.md par.4). Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# No persistent compile cache in the suite, in this process or in the
# CLI subprocesses the tests start: an entry XLA:CPU loads is announced
# on stderr, and some tests compare stderr bytes. The directory is
# named from outside as well, so that nothing a test runs aims at
# <checkout>/.jax_cache (utils/platform.py sets nothing in code when
# the environment names one).
import tempfile  # noqa: E402

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    tempfile.gettempdir(), "cxn_test_jax_cache")

# a pytest plugin may have imported jax before this file ran, in which
# case the env var was read too late; the config update binds either way
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
