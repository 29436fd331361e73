"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding tests
run without TPU hardware — the test-tier the reference left empty (its CI
covered distribution only via local-mode Spark, SURVEY.md §4).
"""

import os

# Force CPU whatever the ambient environment selects: unit tests model
# multi-chip behavior with virtual CPU devices (tests/test_tpu_compile.py
# compiles for a described v5e without one attached); chip_smoke.py is
# the real-TPU path.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from predictionio_tpu.data import storage as storage_mod  # noqa: E402


@pytest.fixture()
def mem_storage():
    """A fresh in-memory storage universe installed as the process default."""
    s = storage_mod.memory_storage()
    storage_mod.set_storage(s)
    yield s
    storage_mod.set_storage(None)
