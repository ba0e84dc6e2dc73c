"""Test environment: eight virtual CPU devices.

Tests run on the CPU (``JAX_PLATFORMS=cpu``); mesh/collective tests use
XLA's CPU multi-device simulation (SURVEY §4: this replaces the
reference's multi-process localhost NCCL harness). Both are set here,
before jax is imported. The program itself runs on the chip —
``python chip_smoke.py`` — never from a test."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# a pytest plugin may have imported jax before this file ran, in which
# case the variable above came too late for its config
jax.config.update("jax_platforms", "cpu")


@pytest.fixture
def cpu_peaks(monkeypatch):
    """A denominator for tests that compute an MFU on the CPU: there is
    no DEVICE_PEAKS row for kind 'cpu' (monitor/perf.py machine_spec
    raises), so such a test passes its own — deliberately the v5e row's
    values, which the pre-existing assertions were written against."""
    monkeypatch.setenv("PT_PERF_PEAK_FLOPS", "197e12")
    monkeypatch.setenv("PT_PERF_HBM_BW", "819e9")
    monkeypatch.setenv("PT_PERF_ICI_BW", "45e9")


def pytest_configure(config):
    # tier-1 verify runs `-m 'not slow'`; register the marker so strict
    # runs don't warn and the expression always resolves
    config.addinivalue_line(
        "markers", "slow: long-running gates (the live 7B plan compile) "
        "excluded from the tier-1 sweep")
