"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The tests run on the CPU; sharding correctness is validated on virtual
CPU devices exactly as the driver's multichip dry-run does.  Must run
before jax is imported anywhere.

Platform pinning lives in smartbft_tpu.utils.jaxenv so standalone drive
scripts get the identical environment.  (tests/test_chip_compile.py asks
the TPU's compiler for a described, unattached chip — still on the CPU
backend.)
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from smartbft_tpu.utils.jaxenv import force_cpu  # noqa: E402

force_cpu(virtual_devices=8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soaks excluded from tier-1 (-m 'not slow'); "
        "run explicitly or via python -m smartbft_tpu.testing.chaos --soak",
    )


def tight_verify_policy(**kw):
    """Sub-100ms verify-plane fault policy shared by the mesh/gating
    suites: the deadline → retry → breaker → canary cycle completes in
    well under a second of wall clock.  Override any knob per test."""
    from smartbft_tpu.crypto.provider import VerifyFaultPolicy

    base = dict(launch_timeout=0.08, launch_retries=2, backoff_base=0.01,
                backoff_max=0.04, backoff_jitter=0.0, breaker_threshold=3,
                probe_interval=0.02, probe_backoff_max=0.05)
    base.update(kw)
    return VerifyFaultPolicy(**base)


def require_native(available: bool, what: str) -> None:
    """Gate a test on a native backend — loudly.

    Default: skip when the backend didn't build (a laptop without g++ can
    still run the suite).  With SMARTBFT_REQUIRE_NATIVE=1 (CI on build-
    capable hosts) the missing backend FAILS instead, so the native oracles
    can't silently vanish from the suite.
    """
    import os

    import pytest

    if available:
        return
    if os.environ.get("SMARTBFT_REQUIRE_NATIVE") == "1":
        pytest.fail(
            f"{what} unavailable but SMARTBFT_REQUIRE_NATIVE=1 — the native "
            "library failed to build/load on a host that requires it"
        )
    pytest.skip(f"{what} unavailable")


#: seconds one test may take before it is failed.  The slowest take two to
#: three minutes (an interpret-mode kernel launch, an XLA compile); the
#: whole run has 1470 s and takes about 360.
TEST_TIME_LIMIT = 700.0


@pytest.fixture(autouse=True)
def fail_a_test_that_waits_for_ever():
    """A test that hangs (an await nothing resolves, a lock nobody frees)
    holds its worker until the run's own time limit cuts the whole run,
    and a cut run counts only the tests before it.  An alarm in the main
    thread raises in whatever the test is blocked on, so the hang costs
    that one test.  (Seen once in PR 28 in
    ``test_censoring_leader_detected_under_open_loop_load``, not
    reproduced in thirty further runs.)"""
    import signal
    import threading

    if not hasattr(signal, "SIGALRM") \
            or threading.current_thread() is not threading.main_thread():
        yield
        return

    def on_alarm(_signum, _frame):
        raise TimeoutError(f"test exceeded {TEST_TIME_LIMIT:g} s")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
