"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests never touch a TPU: ``JAX_PLATFORMS=cpu`` is set before jax is
imported, and sharding tests use
``--xla_force_host_platform_device_count=8``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
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


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def multidevice_child():
    """Run a code snippet in a FRESH interpreter pinned to the forced
    8-device CPU topology (the round-5 spatial-parity harness pattern:
    the child owns its backend config, so the outer process's device
    count — possibly 1 — never matters). The snippet must print one
    ``RESULT <json>`` line; the fixture returns the parsed dict."""
    import json
    import subprocess
    import sys
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

    def run(body: str, timeout: int = 600) -> dict:
        code = prelude + textwrap.dedent(body)
        env = {**os.environ,
               "PYTHONPATH": os.pathsep.join([repo_root, tests_dir])}
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
        tail = (proc.stdout + proc.stderr)[-2000:]
        assert proc.returncode == 0, tail
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        assert lines, f"no RESULT line in child output:\n{tail}"
        return json.loads(lines[-1][len("RESULT "):])

    return run
