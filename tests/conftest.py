"""Test environment: eight virtual CPU devices.

Tests run on the CPU (``JAX_PLATFORMS=cpu``); mesh/collective tests use
XLA's CPU multi-device simulation (SURVEY §4: this replaces the
reference's multi-process localhost NCCL harness). Both are set here,
before jax is imported. The program itself runs on the chip —
``python chip_smoke.py`` — never from a test.

Tests that start ranks as processes go through
``dist_utils.run_ranks``, which waits on one deadline for the whole
group (at most 180 s) and kills every rank past it. Every test's setup,
call and teardown each get ``TEST_LIMIT_S`` (300 s): past it the hook
below dumps every thread's stack and fails that test, and the run goes
on. It is a constant, not a knob: it only has to sit above every group
deadline, so that a hung group is reported by run_ranks, which names
the ranks, and below the suite's own limit, so that one hang costs one
test and not the run. A test that arms its own ``signal.alarm`` (or
runs a tool whose ``main()`` does) replaces this one for the rest of
that phase, which then runs without the limit."""
import faulthandler
import os
import signal
import threading

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


TEST_LIMIT_S = 300


def _past_limit(signum, frame):
    faulthandler.dump_traceback(all_threads=True)
    pytest.fail("test phase ran past the %s s limit (stacks on stderr)"
                % TEST_LIMIT_S, pytrace=False)


@pytest.hookimpl(hookwrapper=True)
def _limited(item):
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    old = signal.signal(signal.SIGALRM, _past_limit)
    signal.alarm(TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


pytest_runtest_setup = pytest_runtest_call = pytest_runtest_teardown = \
    _limited


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
