"""Test bootstrap: force an 8-device virtual CPU mesh.

Tests exercise the multi-chip sharding paths on virtual CPU devices. The
virtual mesh comes from `XLA_FLAGS` (set below unless the caller already
chose a device count) and from nothing else; `jax_platforms` is pinned to
"cpu" before the first backend initialization (backends init lazily at
first use). The chip is reached only through `chip_smoke.py`.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pytest  # noqa: E402


def pytest_report_header(config):
    return f"jax devices: {jax.devices()}"


@pytest.fixture(autouse=True)
def _fast_resilience(monkeypatch):
    """Keep retry backoff near-instant and isolate breaker state.

    Production defaults sleep up to seconds between attempts; a suite
    full of injected persistent faults would crawl. Per-endpoint
    breakers are process-wide, so one test's fault barrage must not
    fast-fail the next test's IO."""
    from delta_tpu import resilience

    monkeypatch.setenv("DELTA_TPU_RETRY_BASE_MS", "1")
    monkeypatch.setenv("DELTA_TPU_RETRY_CAP_MS", "5")
    monkeypatch.setenv("DELTA_TPU_RETRY_DEADLINE_S", "10")
    resilience.reset()
    yield
    resilience.reset()


@pytest.fixture
def tmp_table_path(tmp_path):
    return str(tmp_path / "table")


@pytest.fixture
def host_engine():
    from delta_tpu.engine.host import HostEngine

    return HostEngine()


@pytest.fixture
def tpu_engine():
    from delta_tpu.engine.tpu import TpuEngine

    return TpuEngine()


@pytest.fixture
def sample_data():
    rng = np.random.default_rng(7)
    n = 1000
    return pa.table(
        {
            "id": pa.array(np.arange(n, dtype=np.int64)),
            "value": pa.array(rng.normal(size=n)),
            "category": pa.array([f"cat{i % 5}" for i in range(n)]),
            "date": pa.array([f"2024-01-{(i % 28) + 1:02d}" for i in range(n)]),
        }
    )


@pytest.fixture
def coordinated_path(tmp_table_path):
    """A coordinated-commits table backed by the in-memory coordinator."""
    import numpy as np
    import pyarrow as pa

    import delta_tpu.api as dta
    from delta_tpu.coordinatedcommits import (
        COORDINATOR_NAME_KEY,
        InMemoryCommitCoordinator,
        register_coordinator,
    )

    register_coordinator("test-coord", InMemoryCommitCoordinator(batch_size=3))
    dta.write_table(
        tmp_table_path,
        pa.table({"id": pa.array(np.arange(5, dtype=np.int64))}),
        properties={COORDINATOR_NAME_KEY: "test-coord"},
    )
    return tmp_table_path
