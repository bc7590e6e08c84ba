"""Test bootstrap: force an 8-device virtual CPU mesh before jax imports.

All kernel tests run on CPU devices so they are hermetic; the chip is
reached through `python chip_smoke.py` (one process owns it).
"""

import faulthandler
import os
import signal
import sys
import threading

# Tests run on a virtual 8-device CPU mesh.  The pin is set in the
# environment too, so every process a test spawns inherits it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

#: Seconds the call of one test may take.  A test measured to need
#: more says so itself: @pytest.mark.time_limit(seconds).  ROADMAP D10
#: holds the measurements both were chosen from.
DEFAULT_TIME_LIMIT_S = 300.0
#: Once a limit has fired the alarm repeats at this interval, for a
#: test whose clean-up hangs too: an interrupt that lands between a
#: task's step and its rescheduling leaves a task that asyncio.run's
#: cancel-and-wait never sees finish.
_ALARM_AGAIN_S = 5.0


class TimeLimitExceeded(TimeoutError):
    """The failure of a test that ran past its time limit."""


class _Alarm(KeyboardInterrupt):
    """What the SIGALRM handler raises.  Only a KeyboardInterrupt
    unwinds asyncio.run from wherever the main thread stands: any
    other exception raised inside a task's step or a loop callback
    becomes that task's result or a log line, and a TimeoutError would
    be taken by the code under test for the timeout of an op of its
    own.  It never reaches pytest: pytest_runtest_call turns it into
    TimeLimitExceeded."""


def pytest_collection_modifyitems(items):
    """The tests with a limit of their own are the long ones, and under
    `--dist loadfile` a file is one worker's: they start first, so the
    suite is as long as the longest of them and not that plus the wait
    for a free worker (ROADMAP D10)."""
    items.sort(key=lambda item: item.get_closest_marker("time_limit") is None)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Every test has a limit from outside.  A timer signal interrupts
    the worker's main thread between two bytecodes, so it also ends a
    loop that is busy and never idle; the test FAILS with the stack it
    stood in (all threads' stacks on stderr) and the worker goes on."""
    if not hasattr(signal, "SIGALRM") or \
            threading.current_thread() is not threading.main_thread():
        return (yield)
    mark = item.get_closest_marker("time_limit")
    limit = float(mark.args[0]) if mark else DEFAULT_TIME_LIMIT_S

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        raise _Alarm()

    old = signal.signal(signal.SIGALRM, on_alarm)
    try:
        signal.setitimer(signal.ITIMER_REAL, limit, _ALARM_AGAIN_S)
        return (yield)
    except _Alarm as e:
        raise TimeLimitExceeded(
            f"{item.nodeid} ran past its time limit of {limit:g} s"
        ).with_traceback(e.__traceback__) from None
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def ctx():
    from ceph_tpu.common.context import Context
    return Context("client.test")
