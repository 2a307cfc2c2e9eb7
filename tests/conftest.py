"""Test harness: simulate an 8-device TPU mesh on CPU.

The reference's "multi-node without a cluster" answer is a localhost ZMQ ring
inside one process (``/root/reference/utils/node_profiler.py:1174-1236``); the
JAX-idiomatic replacement is ``--xla_force_host_platform_device_count`` CPU
devices (SURVEY.md §4).

The suite always runs on the CPU backend, whatever the host has: the
platform is forced through ``jax.config`` after import (so a run that forgot
``JAX_PLATFORMS=cpu`` on a machine with an accelerator still cannot take the
chip), and XLA_FLAGS must be set before the first backend use. The chip is
exercised by ``chip_smoke.py``, never by pytest.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Free compiled XLA:CPU executables between test modules. The suite
    compiles hundreds of distinct programs; past ~180 tests in one process
    the CPU backend segfaults inside backend_compile (deterministic by
    position, not by test — an accumulation limit, observed r5 when the
    suite grew to 193 tests). Dropping dead executables per module keeps the
    process far from the edge; live fixtures just recompile on next use."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()
