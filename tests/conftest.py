"""Shared fixtures. NOTE: no xla_force_host_platform_device_count here —
smoke tests and benches must see 1 device (multi-device tests subprocess)."""

import gc

import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _free_reference_cycles():
    """Collect what each test leaves in reference cycles. A jitted closure
    over its owner keeps the owner's compiled programs alive until the
    collector runs, and a profiler trace lists every live executable: a
    later test's trace would otherwise hold earlier tests' programs."""
    yield
    gc.collect()
