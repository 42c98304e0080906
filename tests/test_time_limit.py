"""The per-test time limit of ``conftest.py``: a test that waits past its
limit fails with its traceback in about the limit, not at the outer
clock; one stuck where the alarm cannot reach has its stack dumped."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from conftest import TEST_TIME_LIMIT, time_limit

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _wait_past_the_limit():
    time.sleep(30)


def test_a_wait_past_the_limit_fails_with_its_traceback():
    started = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match="over its time limit of 1 s") as failed:
        with time_limit(1):
            _wait_past_the_limit()
    assert time.monotonic() - started < 5
    assert any(entry.name == "_wait_past_the_limit"
               and "time.sleep(30)" in str(entry.statement)
               for entry in failed.traceback)
    # this test's own limit is armed again with what is left of it
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert TEST_TIME_LIMIT - 10 < left <= TEST_TIME_LIMIT


def test_the_backstop_dumps_a_stack_the_alarm_cannot_reach(tmp_path):
    """With SIGALRM blocked on the main thread, as it is in effect for a
    test stuck in C code, the alarm cannot interrupt the wait: the
    backstop names the waiting line, and the test fails when the wait
    returns."""
    script = tmp_path / "stuck.py"
    script.write_text(textwrap.dedent(f"""
        import signal, sys, time
        import pytest
        sys.path.insert(0, {TESTS_DIR!r})
        from conftest import time_limit
        signal.pthread_sigmask(signal.SIG_BLOCK, {{signal.SIGALRM}})
        try:
            with time_limit(0.5, backstop=0.5):
                time.sleep(2)
        except pytest.fail.Exception as e:
            print("failed when the wait returned:", e)
    """))
    line = 1 + script.read_text().splitlines().index(
        "        time.sleep(2)")
    started = time.monotonic()
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "failed when the wait returned: over its time limit" in \
        proc.stdout
    assert f'File "{script}", line {line} in <module>' in proc.stderr, \
        proc.stderr[-2000:]
    assert time.monotonic() - started < 30
