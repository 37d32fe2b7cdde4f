"""Test configuration.

JAX tests run on the CPU backend with 8 virtual devices so DP/TP/FSDP mesh
code is exercised without TPU hardware (SURVEY.md §4: the "multi-node without
a cluster" strategy).  Env vars must be set before jax is first imported,
which is why this lives at conftest import time.
"""

import os
import sys

# the suite runs on the CPU whatever the environment says, and says so by
# name: the serving stack refuses a non-TPU backend nobody asked for
# (operator_tpu/utils/platform.py)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["OPERATOR_TPU_PLATFORM"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    # persistent XLA compile cache for the suite: the compile-heavy JAX
    # tests re-lower the same tiny-test programs on every run; the cache
    # rule (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache)
    # is the program's own
    from operator_tpu.utils.platform import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
