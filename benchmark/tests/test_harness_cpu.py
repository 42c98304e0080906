"""Rehearsal of the harness on the CPU: every cell's driver runs end to
end at a tiny size and its result line is well formed; the command
itself refuses to run without a TPU or without the program."""

import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stderr, redirect_stdout

import pytest

from benchmark import harness
from benchmark.tests.tiny import run_tiny

MANIFEST = harness.read_json(harness.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_and_prints_a_well_formed_line(name):
    result, compared = run_tiny(name)
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        harness.emit(result, compared)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True, err.getvalue()
    assert line["device"]["platform"] == "cpu"      # a rehearsal, no more
    wanted = {m["name"] for m in MANIFEST["end_to_end"]
              if "workloads" not in m or name in m["workloads"]}
    assert set(line["metrics"]) == wanted
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    # each compared number stands beside its limit, on stderr too
    for row, spec in line["compared"].items():
        assert set(spec) == {"value", "limit"}
        assert f"compared {row}:" in err.getvalue()
    assert err.getvalue().strip().splitlines()[-1].startswith("compared ")


def _run_command(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_measure_without_a_tpu():
    done = _run_command(harness.ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr


def test_the_command_refuses_in_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run_command(tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_every_per_layer_metric_has_its_file_and_reader():
    import importlib
    names = set()
    for entry in MANIFEST["per_layer"]:
        spec = harness.read_json(harness.BENCH_DIR, "metrics",
                                 entry["name"] + ".json")
        for key in ("name", "unit", "layer", "moves", "source", "better"):
            assert spec[key] == entry[key], (entry["name"], key)
        assert spec.get("workloads") == entry.get("workloads")
        module, _, func = spec["reader"].partition(":")
        assert callable(getattr(importlib.import_module(
            f"benchmark.readers.{module}"), func))
        names.add(entry["name"])
    on_disk = {f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                       "metrics"))}
    assert names == on_disk


def test_every_cell_finds_its_files_by_name():
    for name in CELLS:
        cell = harness.load_cell(name)
        assert cell["cell"]["name"] == name
        assert cell["config"]["name"] == name.split(".")[0]
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "drivers", cell["traffic"]["kind"] + ".py"))
        assert os.path.exists(os.path.join(
            harness.ROOT, cell["config"]["reference"]["module"]))
