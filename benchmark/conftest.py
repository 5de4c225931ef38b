"""Four virtual CPU devices for ``benchmark/tests``, named before anything
initialises a backend (as ``tests/conftest.py`` does with eight): the mesh
of the four-chip cell has four.  pytest loads this file before any module
under it, whichever of them are run."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
