"""Test harness: force an 8-virtual-device CPU mesh so multi-chip sharding
paths are exercised without TPU hardware (SURVEY.md §4 implication: simulated
N-device mesh via JAX's multi-device CPU backend).

Hard-override JAX_PLATFORMS: unit tests never take the chip, so a test run
beside a serving process cannot steal its device.
"""

import importlib
import os
import pkgutil

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Importing the package places the persistent compile cache
# (utils/compilecache.enable): JAX_COMPILATION_CACHE_DIR when set, else
# <checkout>/.jax_cache.  The suite only fits its time limit on a warm
# cache, so that directory has to stay where it is between runs.
import baikaldb_tpu  # noqa: E402,F401

# The AOT artifact tier is OFF for the suite: many tests pin exact
# trace/compile counts (xla_retraces, compiles-per-query), and an artifact
# persisted by a previous run would serve those compiles from disk — same
# results, different counters, flaky pins.  tests/test_aot_cache.py turns
# it on explicitly against tmp directories.
from baikaldb_tpu.utils.flags import set_flag  # noqa: E402

set_flag("aot_cache", False)


@pytest.fixture(scope="session")
def all_flags():
    """The flag registry after every module of the package has been
    imported: flags are defined at their point of use."""
    from baikaldb_tpu.utils.flags import FLAGS

    for m in pkgutil.walk_packages(baikaldb_tpu.__path__, "baikaldb_tpu."):
        if not m.name.endswith("__main__"):
            importlib.import_module(m.name)
    return FLAGS
