"""Test bootstrap: force an 8-device CPU JAX platform.

The driver validates multi-chip sharding on a virtual CPU mesh
(xla_force_host_platform_device_count), so the unit suite runs on 8 virtual
CPU devices.  What matters is naming the cpu platform and setting XLA_FLAGS
*before the first backend initialization*, which this conftest does at
import time.

Set SRT_TESTS_ON_TPU=1 to run the suite against the real TPU instead.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("SRT_TESTS_ON_TPU") != "1":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    # no persistent compile cache on the CPU: few of the suite's programs
    # take long enough to be stored, hashing every one for its cache key
    # costs about 3% of the run, and the suite's time (it runs at its
    # limit) must not depend on what an earlier run left on disk
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    import jax
    jax.config.update("jax_platforms", "cpu")
    assert jax.default_backend() == "cpu", (
        "tests must run on the CPU platform; a backend was already "
        "initialized before conftest ran")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def session():
    import spark_rapids_tpu as srt
    return srt.Session.get_or_create()


@pytest.fixture()
def fresh_session():
    import spark_rapids_tpu as srt
    srt.Session.reset()
    s = srt.Session.get_or_create()
    yield s
    srt.Session.reset()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(20260729)


def pytest_collection_modifyitems(config, items):
    """Collection-time static analysis: ONE cached srtlint scan
    (tools/srtlint — AST engine, thirteen passes over a single shared
    parse) replaces the five regex lints that each re-read the whole
    tree here.  The scan is keyed by per-file CONTENT hashes: an
    unchanged tree re-verifies in milliseconds, and a changed tree
    re-verifies incrementally (only edited files + passes whose scope
    the edit touches re-run); any unsuppressed finding fails the run
    before a single test executes.  Rule docs: python -m tools.srtlint
    --explain <rule>, or docs/static_analysis.md."""
    from tools.srtlint import run_for_pytest
    report = run_for_pytest()
    if report.failing:
        lines = "\n".join(
            f"  {f.path}:{f.line}: [{f.rule}] {f.message}"
            for f in report.failing)
        raise pytest.UsageError(
            "srtlint found invariant violations (python -m tools.srtlint"
            f" --explain <rule> for the contract):\n{lines}")
