"""Test config: a deterministic 8-device CPU mesh, so sharding tests run
without TPU hardware.  The tests choose the CPU here, by environment,
before jax is imported; nothing else in the tree picks a backend.  The
chip is exercised by `chip_smoke.py` through the chip tool, never by
pytest.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (multichip dryruns); excluded from tier-1 "
        "via -m 'not slow'")


@pytest.fixture
def tmp_warehouse(tmp_path):
    return str(tmp_path / "warehouse")


@pytest.fixture(scope="session")
def lint_report():
    """ONE whole-program analysis pass (paimon_tpu/analysis/) shared
    by every tier-1 lint test — one parse per file per test session,
    replacing the seven independent full-tree AST walks the old
    tests/test_lint_swallow.py performed."""
    from paimon_tpu.analysis import default_report
    return default_report()
