"""Test configuration: force JAX onto a virtual 8-device CPU mesh, and
give every test a time limit.

Tests never touch a TPU: ``JAX_PLATFORMS=cpu`` is set before jax is
imported, and sharding tests use
``--xla_force_host_platform_device_count=8``.

Time limit. Each phase of a test (setup, call, teardown) has
``TEST_TIME_LIMIT`` seconds (240). The suite runs under one outer clock
(the command's ``timeout``); without a limit of its own, a test that
waits forever spends that whole clock and reports nothing. Two guards,
in :func:`time_limit`:

* a ``SIGALRM`` timer whose handler fails the test where it stands, so
  the report carries the test's traceback down to the line that waited;
* a backstop: ``faulthandler`` dumps every thread's stack to the
  worker's real stderr ``BACKSTOP`` seconds (60) after the limit. Python
  runs a signal handler only between bytecodes, so a test stuck in C
  code (an XLA compile, a native wait with no timeout) never sees the
  alarm; the dump still says where it is, in the run's log.

Tests marked ``slow`` (drills that run a script in a subprocess) are
outside the limit: each bounds its own subprocess. A child process a
test starts gets a bound below the limit (``CHILD_TIME_LIMIT``), so a
hung child is reported by the test, with its output, not by the alarm.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

#: seconds each phase of a test may take
TEST_TIME_LIMIT = 240
#: seconds after the limit at which every thread's stack is dumped
BACKSTOP = 60
#: seconds a child process of a test may take: under the test's limit
CHILD_TIME_LIMIT = 200

#: where the backstop writes: the process's stderr as it was before
#: pytest captured it (set in ``pytest_configure``)
_dump_to = sys.stderr


@contextlib.contextmanager
def time_limit(seconds: float, backstop: float = BACKSTOP):
    """Fail the code inside with its traceback after ``seconds``; dump
    every thread's stack ``backstop`` seconds later if it is stuck where
    the alarm cannot reach. Nests: an enclosing limit is re-armed with
    what is left of it on the way out. Off the main thread (where no
    signal can be delivered) it does nothing."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expire(signum, frame):
        pytest.fail(f"over its time limit of {seconds} s")

    previous = signal.signal(signal.SIGALRM, expire)
    outer, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    faulthandler.dump_traceback_later(seconds + backstop, file=_dump_to)
    started = time.monotonic()
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)
        if outer:
            left = max(outer - (time.monotonic() - started), 1e-3)
            signal.setitimer(signal.ITIMER_REAL, left)
            faulthandler.dump_traceback_later(left + backstop,
                                              file=_dump_to)


class _TimeLimitEachPhase:
    """Runs setup, call and teardown of every test not marked ``slow``
    under :func:`time_limit`."""

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_setup(self, item):
        return (yield from self._limited(item))

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_call(self, item):
        return (yield from self._limited(item))

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_teardown(self, item):
        return (yield from self._limited(item))

    @staticmethod
    def _limited(item):
        if item.get_closest_marker("slow") is not None:
            return (yield)
        with time_limit(TEST_TIME_LIMIT):
            return (yield)


def pytest_configure(config):
    global _dump_to
    config.addinivalue_line(
        "markers",
        "pallas_interpret: CPU interpret-mode Pallas kernel parity "
        "suites (corr, gru, msda, motion) — selectable as one group, "
        "e.g. -m 'not pallas_interpret' for a conv-path-only run")
    config.addinivalue_line(
        "markers",
        "slow: long-running drills excluded from the tier-1 command "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "multidevice: needs the forced multi-device CPU topology "
        "(--xla_force_host_platform_device_count in XLA_FLAGS); skips "
        "cleanly — instead of erroring — when the suite runs with the "
        "forcing env absent or on fewer than 2 devices")
    capture = config.pluginmanager.getplugin("capturemanager")
    with (capture.global_and_fixture_disabled() if capture
          else contextlib.nullcontext()):
        _dump_to = os.fdopen(os.dup(2), "w")
    config.pluginmanager.register(_TimeLimitEachPhase(), "time-limit")


def pytest_sessionfinish(session):
    """On an xdist worker, drop JAX's caches before the worker reports
    it has finished. After that report the controller ends the worker,
    and execnet sends a worker still busy 5 s later a SIGINT; pytest's
    cleanup then runs five garbage collections, each a walk of every
    traced and compiled program the worker's tests left cached (0.6 s a
    pass after three of the heavier files on an 8-core CPU, several
    times that on a loaded host). The SIGINT landed in JAX's gc callback
    as a ``KeyboardInterrupt`` in the log. With the caches dropped here,
    the five passes take a second."""
    if hasattr(session.config, "workerinput"):
        jax.clear_caches()


def pytest_runtest_setup(item):
    if item.get_closest_marker("multidevice") is None:
        return
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        pytest.skip("forced host-device env absent "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
    if jax.device_count() < 2:
        pytest.skip(f"multidevice test needs >= 2 devices, "
                    f"have {jax.device_count()}")


def jit_train_state(*args, **kwargs):
    """``create_train_state(*args, **kwargs)`` compiled as one program:
    the same state, value for value, as the eager call, at a fraction of
    its time (eagerly each of the init's ops compiles on its own: 21 s
    for RAFT-small's state on an 8-core CPU, 6.6 s jitted)."""
    from raft_tpu.parallel import create_train_state
    return jax.jit(lambda: create_train_state(*args, **kwargs))()


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """JAX's persistent compile cache on for the module that asks, in a
    directory of its own, as ``raft_tpu.utils.compile_cache`` turns it on
    in the CLI entry points before they train; off again after the
    module. ``train()`` builds its step anew on each call, so a second
    run of one configuration compiles it again: with the cache, it reads
    back what the first compiled."""
    from jax.experimental.compilation_cache import compilation_cache
    settings = {"jax_compilation_cache_dir":
                str(tmp_path_factory.mktemp("compile_cache")),
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    was = {name: getattr(jax.config, name) for name in settings}
    for name, value in settings.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield
    for name, value in was.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def multidevice_child():
    """Run a code snippet in a FRESH interpreter pinned to the forced
    8-device CPU topology (the round-5 spatial-parity harness pattern:
    the child owns its backend config, so the outer process's device
    count — possibly 1 — never matters). The snippet must print one
    ``RESULT <json>`` line; the fixture returns the parsed dict. A child
    that outlasts ``timeout`` is killed and reported with its output."""
    import json
    import subprocess
    import textwrap

    tests_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(tests_dir)
    prelude = textwrap.dedent("""
        import json, os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8")
        import jax
    """)

    def run(body: str, timeout: int = CHILD_TIME_LIMIT) -> dict:
        code = prelude + textwrap.dedent(body)
        env = {**os.environ,
               "PYTHONPATH": os.pathsep.join([repo_root, tests_dir])}
        try:
            proc = subprocess.run([sys.executable, "-c", code],
                                  capture_output=True, text=True,
                                  timeout=timeout, env=env)
        except subprocess.TimeoutExpired as e:
            said = b"".join(x for x in (e.stdout, e.stderr) if x)
            pytest.fail(f"child over its {timeout} s:\n"
                        f"{said.decode(errors='replace')[-2000:]}")
        tail = (proc.stdout + proc.stderr)[-2000:]
        assert proc.returncode == 0, tail
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        assert lines, f"no RESULT line in child output:\n{tail}"
        return json.loads(lines[-1][len("RESULT "):])

    return run
