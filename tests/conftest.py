"""Shared test fixtures. NOTE: no XLA_FLAGS by default — smoke tests and
benches must see 1 device; only the dry-run (and the subprocess sharding
tests) force host platform device counts.

Multi-device tier (`make test-shard`): setting REPRO_TEST_DEVICES=N in the
environment makes this conftest inject
`--xla_force_host_platform_device_count=N` BEFORE jax is imported (the flag
is read at backend init, so it cannot be a fixture) — the shard_map parity
tests in test_shard_map.py then see N host devices; without the variable
they skip via the `shard_devices` fixture and the full gate covers them
through a subprocess wrapper instead.
"""
import os

if os.environ.get("REPRO_TEST_DEVICES"):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count="
            + os.environ["REPRO_TEST_DEVICES"]).strip()

import jax
import numpy as np
import pytest

# the launch drivers point JAX's persistent compilation cache at the
# checkout (`repro.launch.compile_cache`); a test that calls one in-process
# must not make every later compile in its worker read and write that cache
jax.config.update("jax_enable_compilation_cache", False)


def pytest_collection_modifyitems(items):
    # tier-1 verify loop = everything that isn't a multi-minute subprocess
    # compile; `make test-fast` runs `-m "tier1"`.
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.tier1)


@pytest.fixture(scope="session", autouse=True)
def _x64():
    # kernels/core are validated in f64 where exactness matters; individual
    # tests opt in via the helpers below rather than globally.
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def shard_devices():
    """>= 8 host devices, or skip (run this tier via `make test-shard`)."""
    if jax.device_count() < 8:
        pytest.skip("needs >= 8 devices: run `make test-shard` "
                    "(REPRO_TEST_DEVICES=8)")
    return jax.devices()[:8]


def make_qkv(rng, b, hq, hkv, n, d, dv, dtype=np.float32, normalized=False):
    import jax.numpy as jnp
    from repro.core.ref import normalize_qk
    q = jnp.asarray(rng.normal(size=(b, hq, n, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, n, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, n, dv)), dtype)
    if normalized:
        q, k = normalize_qk(q), normalize_qk(k)
    return q, k, v
