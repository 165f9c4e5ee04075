"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by its name:

* ``BENCHMARK.json`` (checkout root): the cells and the metrics;
* ``bench/configs/<config>.json`` and the plain reference it names;
* ``bench/traffic/<traffic>.json``: the traffic mix, read by the one
  generator in ``benchlib/traffic.py`` and the job its ``"job"`` names;
  a mix whose job needs more of the reference than the configuration's
  (a training policy's quantizers) names its own under ``"reference"``;
* ``bench/cells/<cell>.json``: the limits of the cell's check, with the
  readings they were set from;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple


# traffic "job" -> (module under benchlib, function)
JOBS = {"train": ("train_job", "run"),
        "serve_closed": ("serve_job", "run_closed"),
        "serve_open": ("serve_job", "run_open")}


class NoChip(RuntimeError):
    """The run needs accelerators that this machine does not have."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A cell of the manifest with every file it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    manifest: dict
    bench: Path

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        manifest = load_json(root / "BENCHMARK.json")
        entry = next((w for w in manifest["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            known = [w["name"] for w in manifest["workloads"]]
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {known}")
        bench = root / "bench"
        conf = next(c for c in manifest["configs"]
                    if c["name"] == entry["config"])
        return cls(name=name, chips=int(entry["chips"]),
                   config=load_json(root / conf["file"]),
                   traffic=load_json(bench / "traffic"
                                     / f"{entry['traffic']}.json"),
                   limits=load_json(bench / "cells" / f"{name}.json")[
                       "limits"],
                   manifest=manifest, bench=bench)

    def reference(self):
        name = self.traffic.get("reference", self.config["reference"])
        return load_module(self.bench / "configs" / name,
                           "bench_ref_" + name.replace(".", "_"))

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        moved = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]


class CompileClock:
    """Counts XLA backend compiles (persistent-cache hits are not
    compiles) and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


@dataclasses.dataclass
class Outcome:
    """What a job hands back: the end-to-end values it measured, counts,
    the numbers of its check beside their limits, and facts (counts,
    sizes, times) that per-layer readers use."""

    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Tuple[float, float]]
    facts: Dict[str, Any]


class Run:
    """The state a job sees: the cell, the seed, the clock, the window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, device, clock: CompileClock):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.device = device
        self.clock = clock
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.window_compiles: Optional[int] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_dir: Optional[str] = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span in the profiler's trace (no-op cost when off)."""
        import jax
        with jax.profiler.TraceAnnotation("bench." + name):
            yield

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; with
        ``--trace 1`` the profiler records it; the device's memory peak is
        read as it closes."""
        import jax
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        c0 = self.clock.count
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        try:
            with self.span("window"):
                yield self
        finally:
            self.window_s = time.perf_counter() - t0
            self.window_compiles = self.clock.count - c0
            if self.trace:
                jax.profiler.stop_trace()
            stats = self.device.memory_stats() or {}
            self.memory_peak_bytes = stats.get("peak_bytes_in_use")


def _setup_jax(root: Path) -> None:
    import jax
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {devs[0].platform}; the "
                     "benchmark runs only on the chip")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def prepare(root: Path, name: str, seed: int, seconds: float, trace: bool,
            t_start: float, require_tpu: bool = True):
    """(Run, job function, devices) for one run of cell ``name``."""
    cell = Cell.load(root, name)
    _setup_jax(root)
    devs = _device(cell.chips, require_tpu)
    run = Run(cell, seed, seconds, trace, t_start, devs[0], CompileClock())
    module, fn = JOBS[cell.traffic["job"]]
    return run, getattr(importlib.import_module("benchlib." + module),
                        fn), devs


def run_cell(root: Path, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             **job_args) -> dict:
    """Run ``name`` once and return the result line (a dict).
    ``job_args`` are for the check's readings and tests, never the
    benchmark's own runs: ``fault`` plants a fault in the timed path;
    ``control=True`` puts the control (the plain reference in the nearest
    lower precision) in the program's place, so ``checks`` and ``correct``
    judge the control, and ``numbers`` keeps the program's own."""
    from benchlib import peaks as peaks_mod
    from benchlib import trace as trace_mod

    run, job, devs = prepare(root, name, seed, seconds, trace, t_start,
                             require_tpu)
    cell = run.cell
    out: Outcome = job(run, **job_args)

    reduced = None
    if run.trace_dir is not None:
        try:
            events = trace_mod.load_xplane(trace_mod.find_xplane(
                run.trace_dir))
            reduced = trace_mod.reduce(events)
        finally:
            shutil.rmtree(run.trace_dir, ignore_errors=True)

    metrics: Dict[str, dict] = {}
    if not trace:
        values = dict(out.end_to_end, setup_s=run.setup_s)
        for m in cell.end_to_end():
            if m["name"] not in values:
                raise KeyError(f"the job measured no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        reader = Reading(cell, run, out, reduced,
                         peaks_mod.for_kind(run.device.device_kind)
                         if require_tpu else None)
        for m in cell.per_layer():
            mod = load_module(cell.bench / "metrics" / f"{m['name']}.py",
                              "bench_metric_" + m["name"].replace(".", "_"))
            v = mod.read(reader)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    correct = bool(out.checks) and all(
        math.isfinite(v) and v <= lim for v, lim in out.checks.values())
    device = {"platform": run.device.platform,
              "kind": run.device.device_kind,
              "count": len(devs),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_ns * 1e-9
        device["window_s"] = reduced.window_ns * 1e-9
        line["breakdown"] = reduced.breakdown()
    if "load" in out.facts:
        line["load"] = out.facts["load"]
    line["window_compiles"] = run.window_compiles
    if job_args:  # the readings of the check: the program's numbers
        line["numbers"] = out.facts.get("numbers")
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets."""

    cell: Cell
    run: Run
    outcome: Outcome
    trace: Any            # trace.Reduced, or None when nothing was traced
    peaks: Any            # peaks.Peaks of the device

    @property
    def facts(self) -> Dict[str, Any]:
        return self.outcome.facts

    @property
    def config(self) -> dict:
        return self.cell.config
