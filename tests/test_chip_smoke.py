"""CPU rehearsal of ``chip_smoke.py``: its phase functions at a tiny size.

The script itself refuses to run without a TPU (and is rehearsed end to
end there); these tests import its phases and drive them on the CPU with
raft-small widths — allowed here only — so a wrong path, argument or
control flow is found before any chip time is spent. No kernel is
expected in the compiled programs (``auto`` keeps the XLA path off-TPU),
so ``expect=()``.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from conftest import CHILD_TIME_LIMIT  # noqa: E402

SMALL = os.path.join(REPO, "assets", "golden", "weights_small.npz")


@pytest.fixture(scope="module", autouse=True)
def _leave_the_compile_cache_as_found():
    """The serve phase turns the persistent compile cache on for the
    process (as it must on the chip); later tests in this worker get the
    setting back."""
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.fixture(scope="module", autouse=True)
def rehearsal(tmp_path_factory):
    """``--multichip``'s two comparisons on four virtual CPU devices, in
    a fresh interpreter that owns its device count, started with the
    module: it runs beside the phases below, and its test collects it.
    The persistent compile cache is on, in a directory of its own, as
    ``main()`` turns it on before the phases: the one-device side's eager
    init reads the data-parallel side's small executables back."""
    out = tmp_path_factory.mktemp("rehearsal")
    code = (
        "import chip_smoke as cs\n"
        "from raft_tpu.utils.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "cs.multichip_train(hw=(64, 64), batch=4, iters=2, small=True,"
        " steps=2)\n"
        f"cs.multichip_predict(weights={SMALL!r}, hw=(64, 96),"
        " fallback_hw=(64, 96), iters=2, small=True)\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_COMPILATION_CACHE_DIR": str(out / "compile_cache")}
    with open(out / "said.txt", "w") as said:
        proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                                cwd=REPO, stdout=said,
                                stderr=subprocess.STDOUT)
    yield proc, out / "said.txt"
    if proc.poll() is None:
        proc.kill()
        proc.wait()


@pytest.fixture(scope="module")
def predictor():
    return chip_smoke.phase_predict(
        weights=SMALL, hw=(60, 100), batches=(1, 2), iters=2, small=True,
        golden=False, expect=())


def test_predict_phase(predictor, capsys):
    assert predictor.iters == 2
    assert predictor.model.config.mixed_precision


def test_serve_phase(predictor, capsys):
    chip_smoke.phase_serve(predictor, hw=(60, 100), max_batch=2, requests=5)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[serve]")][-1]
    fields = json.loads(line[len("[serve] "):])
    assert fields["post_warmup_compiles"] == 0
    assert fields["max_abs_diff_vs_direct"] <= chip_smoke.SERVE_REPLY_BOUND


def test_train_phase(tmp_path, capsys):
    chip_smoke.phase_train(str(tmp_path), hw=(64, 64), batch=8, iters=2,
                           small=True, steps=2, expect=())
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[train]")][-1]
    fields = json.loads(line[len("[train] "):])
    assert fields["steps"] == 2 and fields["skipped_steps"] == 0
    assert fields["checkpoint_restored_equal"] and fields["saved_step"] == 2
    assert fields["augment_backend"] in ("native", "numpy")


def test_a_phase_without_its_kernel_fails():
    with pytest.raises(SystemExit, match="expected kernels"):
        chip_smoke.require_kernels(
            "predict", {"corr_fwd": 2},
            ("corr_fwd", (("step",), ("motion", "gru"))))
    chip_smoke.require_kernels(
        "predict", {"corr_fwd": 2, "motion": 1, "gru": 1},
        ("corr_fwd", (("step",), ("motion", "gru"))))


def test_no_chip_means_failure_and_no_result_line():
    """Run as the driver runs it, on this CPU-only host: non-zero exit
    and no result line."""
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True,
                          timeout=CHILD_TIME_LIMIT,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.multidevice
def test_multichip_rehearsal_on_four_virtual_devices(rehearsal):
    """Both comparisons of the rehearsal the module started hold."""
    proc, said = rehearsal
    try:
        proc.wait(timeout=CHILD_TIME_LIMIT)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    out = said.read_text()
    assert proc.returncode == 0, out[-3000:]
    lines = {ln.split("]")[0][1:]: json.loads(ln.split("] ", 1)[1])
             for ln in out.splitlines() if ln.startswith("[multi")}
    assert lines["multichip-train"]["devices_holding"]["data_parallel"] == {
        "batch": 4, "params": 4}
    assert lines["multichip-predict"]["devices_holding_output"] == 4
    assert lines["multichip-train"]["ok"] and lines["multichip-predict"]["ok"]


def test_serving_parents_and_loader_import_never_touch_a_backend(tmp_path):
    """One process per chip: a chip host runs one worker process per
    chip, and the processes that only route — supervisor, gateway, edge —
    must not initialise a backend (a parent that holds the chip starves
    its worker children). The same for importing ``raft_tpu.data``, which
    every process-loader worker does. Checked in a fresh interpreter:
    import, construct and start all three, and jax is never imported."""
    code = f"""
import sys
from raft_tpu.serving import edge, gateway, netproto, supervisor
import raft_tpu.data
store = netproto.FileLeaseStore({str(tmp_path)!r})
sup = supervisor.WorkerSupervisor([], store).start()
gw = gateway.ServingGateway(store).start()
front = edge.EdgeServer(gw, edge.EdgeConfig(port=0)).start_in_thread()
front.shutdown_sync()
sup.stop()
assert "jax" not in sys.modules, "a routing parent imported jax"
print("CLEAN")
"""
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env={**os.environ, "PYTHONPATH": REPO},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "CLEAN" in proc.stdout, \
        (proc.stdout + proc.stderr)[-2000:]
