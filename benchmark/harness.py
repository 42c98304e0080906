"""What every driver shares: finding a cell's files by name, the chip
check, the compile cache and compile counter, the trace, the compared
numbers and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
The harness finds, by those names and nothing else::

    benchmark/configs/<config>.json     sizes, source, reference, control
    benchmark/traffic/<traffic>.json    parameters of the mix and its `kind`
    benchmark/cells/<cell>.json         what belongs to the pair: expected
                                        kernels, limits of `correct`
    benchmark/drivers/<kind>.py         the generator and window for a kind
    benchmark/metrics/<metric>.json     a per-layer metric and its reader
    benchmark/readers/<module>.py       readers, named by metric files

so a later PR adds a cell, a configuration, a mix or a metric with new
files and new entries of ``BENCHMARK.json`` only.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Refused(SystemExit):
    """The run cannot be made here (no chip, no program): exit code,
    no result line."""


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """Everything that describes one cell, found by name."""
    manifest = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; "
                      f"known: {sorted(cells)}")
    entry = cells[workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    cell = read_json(BENCH_DIR, "cells", workload + ".json")
    traffic = read_json(BENCH_DIR, "traffic", entry["traffic"] + ".json")
    traffic.update(cell.get("traffic_overrides", {}))
    return {"name": workload, "chips": entry["chips"],
            "config": read_json(ROOT, config_entry["file"]),
            "traffic": traffic, "cell": cell, "manifest": manifest}


def metrics_for(cell: dict, section: str) -> List[dict]:
    """The manifest's metrics of ``section`` that this cell reports."""
    return [m for m in cell["manifest"][section]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def load_driver(kind: str):
    return importlib.import_module(f"benchmark.drivers.{kind}")


# ------------------------------------------------------------------ the chip

def require_devices(chips: int):
    """The devices the cell asks for, or no run at all."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise Refused(f"this cell needs {chips} TPU chip(s); JAX found "
                      f"{len(devices)} x {devices[0].platform}")
    return devices[:chips]


def device_facts(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> dict:
    """Peak bytes held on the fullest device. The TPU backend keeps two
    counters: ``peak_bytes_in_use`` for the arrays the process holds
    (arguments, results, weights) and ``peak_bytes_reserved`` for what
    loaded executables reserve "at the bottom of memory" to work in
    (their temporaries). A program's footprint is the two together
    (PERF.md, Findings of PR 26: the first alone reads 1.9 GB while a
    program with 11.4 GB of temporaries runs, and 4 GB of ballast then
    makes it fail to load)."""
    stats = max(((d.memory_stats() or {}) for d in devices),
                key=lambda s: (s.get("peak_bytes_in_use", 0)
                               + s.get("peak_bytes_reserved", 0)))
    in_use = int(stats.get("peak_bytes_in_use", 0))
    reserved = int(stats.get("peak_bytes_reserved", 0))
    return {"memory_peak_bytes": in_use + reserved,
            "peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved,
            "bytes_limit": int(stats.get("bytes_limit", 0))}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits and misses
    through ``jax.monitoring``; register once, read deltas."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += float(duration)

    def _event(self, event: str, **_):
        if event == self.HIT:
            self.cache_hits += 1
        elif event == self.MISS:
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles,
                "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


# ------------------------------------------------------------------ the trace

class Trace:
    """A profiler trace of device operations around a stretch of the
    run, with the benchmark's own host spans put on its clock.

    The host tracer stays off: on this runtime it records some twenty
    megabytes of futex events a second and slows transfers by seconds a
    batch (PERF.md, Findings of PR 26). Instead a tiny jitted marker
    runs on the device just after the trace starts and just before it
    stops; the host's clock around each marker and the marker's own
    start in the trace tie the two clocks together."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.reduced: Optional[dict] = None
        self.facts: dict = {}
        self._dir = None
        self._stopped = False
        self._host_spans: List[list] = []
        self._anchors: List[List[float]] = []
        if enabled:
            import jax
            import jax.numpy as jnp

            def bench_marker(x):
                return x + 1

            # compiled ahead of time: a call then never retraces, whatever
            # mesh context the program has entered by then
            self._one = jnp.zeros((8, 128), jnp.float32)
            self._marker = jax.jit(bench_marker).lower(self._one).compile()
            self._marker(self._one).block_until_ready()

    @property
    def running(self) -> bool:
        return self._dir is not None and not self._stopped

    def _mark(self):
        before = time.perf_counter_ns()
        self._marker(self._one).block_until_ready()
        self._anchors.append([before, time.perf_counter_ns()])

    def start(self):
        if not self.enabled:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._mark()

    def stop(self, host_spans):
        """Stop the profiler. ``host_spans``: ``[name, start, duration]``
        on the host's ``perf_counter_ns`` clock, the traced window's own
        span among them."""
        if not self.running:
            return
        import jax
        self._mark()
        jax.profiler.stop_trace()
        self._host_spans = list(host_spans)
        self._stopped = True

    def read(self):
        """Read, reduce and remove the trace's files (slow for a large
        trace, so a driver calls it once its window has closed)."""
        if not self._stopped:
            return
        from benchmark import trace_reduce
        try:
            files = glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            t0 = time.perf_counter()
            raw = trace_reduce.load_xplane(files[0])
            raw["host_spans"] = trace_reduce.host_to_trace_clock(
                self._host_spans, self._anchors, raw["markers"])
            self.reduced = trace_reduce.reduce_trace(raw)
            self.facts = {"trace_bytes": os.path.getsize(files[0]),
                          "trace_read_s": time.perf_counter() - t0,
                          "clock_drift_us": trace_reduce.clock_drift_us(
                              self._anchors, raw["markers"])}
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
            self._stopped = False


class Phases:
    """Consecutive host spans on the ``perf_counter_ns`` clock:
    ``switch(name)`` ends the current one and begins the next."""

    def __init__(self):
        self._name = None
        self._t0 = 0
        self.seconds: Dict[str, float] = {}
        self.log: List[list] = []

    def switch(self, name: Optional[str]):
        now = time.perf_counter_ns()
        if self._name is not None:
            self.seconds[self._name] = (self.seconds.get(self._name, 0.0)
                                        + (now - self._t0) / 1e9)
            self.log.append(["bench." + self._name, self._t0,
                             now - self._t0])
        self._name, self._t0 = name, now


# -------------------------------------------------------- compared and result

class Compared:
    """The numbers that decide ``correct``, each beside its limit."""

    def __init__(self):
        self.rows: List[dict] = []

    def add(self, name: str, value, limit, note: str = ""):
        """``value`` may not pass ``limit`` (both floats or ints); a
        value that is missing or not finite fails."""
        ok = (value is not None and value == value
              and float(value) <= float(limit))
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok), **({"note": note} if note else {})})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self) -> dict:
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}


def per_layer_metrics(cell: dict, context: dict) -> Dict[str, dict]:
    """Run the reader of every per-layer metric this cell reports. A
    reader that finds nothing to read returns ``None`` and the metric is
    left out of the line."""
    out: Dict[str, dict] = {}
    for entry in metrics_for(cell, "per_layer"):
        spec = read_json(BENCH_DIR, "metrics", entry["name"] + ".json")
        module, _, func = spec["reader"].partition(":")
        reader: Callable = getattr(
            importlib.import_module(f"benchmark.readers.{module}"), func)
        value = reader(context, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def durations(marks, start: float) -> Dict[str, float]:
    """``[(name, time)]`` marks to seconds since the mark before."""
    times = [start] + [t for _, t in marks]
    return {name: times[i + 1] - times[i]
            for i, (name, _) in enumerate(marks)}


def metrics_of(cell: dict, tracer: "Trace", run_facts: dict, device: dict,
               end_to_end: Dict[str, float]):
    """The run's ``metrics`` and what else its line carries: with a
    trace the cell's per-layer metrics, ``busy_s`` and ``window_s`` on
    ``device`` and the ``breakdown``; without one the cell's end-to-end
    metrics out of ``end_to_end``."""
    if tracer.reduced is None:
        return {m["name"]: {"value": end_to_end[m["name"]],
                            "unit": m["unit"]}
                for m in metrics_for(cell, "end_to_end")}, {}
    from benchmark import trace_reduce
    reduced = tracer.reduced
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    context = {"trace": reduced, "run": run_facts, "cell": cell,
               "device": device}
    extra = {"breakdown": {
        "device_ops": trace_reduce.top(reduced["ops"]),
        "idle_gaps": trace_reduce.top(reduced["gaps"])}, **tracer.facts}
    return per_layer_metrics(cell, context), extra


def emit(result: dict, compared: Compared) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output, with the compared
    numbers as its last key."""
    sys.stdout.flush()
    for row in compared.rows:
        print(f"compared {row['name']}: value {row['value']} "
              f"limit {row['limit']} {'ok' if row['ok'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["compared"] = compared.as_dict()
    print(json.dumps(result), flush=True)
