"""Test configuration: an 8-device virtual CPU mesh + fast profile.

The TPU-native analog of "multi-node testing without a cluster" (SURVEY.md
§4): all distributed/sharding tests run on 8 virtual CPU devices via
``--xla_force_host_platform_device_count``.  The suite is a CPU suite by
design — the chip is exercised by ``chip_smoke.py`` and ``benchmark/run.py`` — so
``JAX_PLATFORMS=cpu`` is put into the environment before jax is imported:
jax honours it, and the CLI-subprocess tests inherit it.

Fast profile: long-running tests (end-to-end training, multiprocess
integration, full-size weight conversion, parametrized-sweep duplicates
whose contract keeps one representative in the fast tier, ...) carry
``@pytest.mark.slow`` and are skipped unless ``--runslow`` is passed — so
the default ``python -m pytest tests/ -x -q`` is the quick contract and
``--runslow`` is the full nightly sweep (see .github/workflows/tests.yml).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Persistent XLA compile cache for the suite: XLA recompiles dominate
# wall-time on few-core boxes, so repeat runs (and CI with an actions/cache
# step) skip straight to execution.  Placed through jax's own variables,
# before jax is imported, so this process and the CLI-subprocess tests read
# the same directory; normalised because the path is part of the cache key.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  os.pardir, ".cache", "xla_tests")))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow (the full sweep)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded unless --runslow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: pass --runslow to include")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
