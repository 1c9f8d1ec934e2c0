"""A test that waits fails by name (tests/conftest.py: `time_limit`), from
the line it waited at, and costs the run one failure: not its exit code and
every count behind it, as a stall under the driver's `timeout` does."""

import signal
import time

import pytest

from conftest import TEST_TIME_LIMIT_S, time_limit


def test_a_test_that_waits_fails_with_the_place_it_waited():
    """conftest.py's limit is running around this very test; given 50 ms,
    it fails a sleep of 30 s from inside it and says which line slept."""
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= TEST_TIME_LIMIT_S
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as failed:
        with time_limit(0.05, "a case that sleeps"):
            time.sleep(30)
    assert time.monotonic() - began < 10
    said = str(failed.value)
    assert "a case that sleeps ran past its limit of 0.05 s" in said
    assert __file__ in said and "time.sleep(30)" in said
    # the test's own limit is the one running again
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= TEST_TIME_LIMIT_S
