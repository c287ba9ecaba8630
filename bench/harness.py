"""The benchmark's harness: finds every piece of a cell by name and turns one
run into the result line.

Everything is data found under the checkout root:

* ``BENCHMARK.json``: configurations, cells (``workloads``), metrics;
* ``bench/configs/<config>.json``: one deployment (its ``file`` entry),
  whose ``generator`` names its data generator,
  ``bench/generators/<generator>.py``;
* ``bench/traffic/<traffic>.json``: one traffic mix; its ``kind`` names the
  driver ``bench/kinds/<kind>.py`` that sets up, runs the window and checks;
* ``bench/limits/<cell>.json``: the limits of the cell's correctness checks;
* ``bench/layer_metrics/<metric>.py``: one reader per per-layer metric,
  ``read(inputs) -> float | None``;
* ``bench/work/<kernel>.py``: ``work(shapes) -> (flops, bytes)`` of one call;
* ``bench/peaks.json``: the chips' peaks, keyed by ``device_kind``.

A new configuration, mix, metric or kernel is a new file plus a
``BENCHMARK.json`` entry; no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (metric files may have dots in their names)."""
    name = "bench_plugin_" + "".join(c if c.isalnum() else "_"
                                     for c in str(path.relative_to(path.anchor)))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    root: Path
    spec: Dict[str, Any]
    workload: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, float]

    @property
    def name(self) -> str:
        return self.workload["name"]

    def kind(self):
        return load_module(self.root / "bench" / "kinds"
                           / f"{self.mix['kind']}.py")

    def _applies(self, entry: Dict[str, Any], e2e_names: List[str]) -> bool:
        if "workloads" in entry:
            return self.name in entry["workloads"]
        return entry.get("moves", entry["name"]) in e2e_names

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [e for e in self.spec["end_to_end"]
                if "workloads" not in e or self.name in e["workloads"]]

    def per_layer(self) -> List[Dict[str, Any]]:
        names = [e["name"] for e in self.end_to_end()]
        return [e for e in self.spec["per_layer"] if self._applies(e, names)]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(root=root, spec=spec, workload=w,
                config=load_json(root / conf["file"]),
                mix=load_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=load_json(root / "bench" / "limits"
                                 / f"{workload}.json"))


def peaks_for(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """Peak FLOP/s and bytes/s of one chip; an unknown kind is an error."""
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


def work_fn(kernel: str, root: Path = ROOT) -> Callable[[Dict], tuple]:
    return load_module(root / "bench" / "work" / f"{kernel}.py").work


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """A number compared against its limit (pass when value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a kind's run returns to the harness."""
    metrics: Dict[str, float]          # end-to-end values by metric name
    attempted: int
    failed: int
    checks: List[Check]
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class LayerInputs:
    """What a per-layer reader sees."""
    cell: Cell
    counters: Dict[str, Any]
    trace: Any                          # bench.trace.Trace or None
    peaks: Optional[Dict[str, float]]   # None when the device is not a chip
    root: Path = ROOT

    def work(self, kernel: str):
        return work_fn(kernel, self.root)


class RunContext:
    """Handed to a kind's ``run``: the cell, the run's arguments, and the
    marks the harness needs (end of set-up, traced window, memory peak)."""

    WINDOW_SPAN = "bench/window"

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, control: bool = False,
                 trace_dir: Optional[Path] = None) -> None:
        self.cell = cell
        self.config = cell.config
        self.mix = cell.mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = bool(control)
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.reduced_trace = None
        self._trace_dir = trace_dir or (cell.root / ".bench_traces"
                                        / f"{cell.name}-{seed}")
        self._window = None

    def setup_done(self) -> None:
        """Mark the end of set-up: the next operation is timed."""
        if self.setup_s is None:
            self.setup_s = time.perf_counter() - self.t_start

    def read_memory_peak(self) -> None:
        import jax

        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in jax.local_devices()]
        self.memory_peak_bytes = max(peaks) if peaks else 0

    def start_trace(self) -> None:
        """Start the profiler and open the window span (``--trace 1``)."""
        if not self.trace or self._window is not None:
            return
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self._trace_dir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(self.WINDOW_SPAN)
        self._window.__enter__()

    def stop_trace(self) -> None:
        """Close the window, stop the profiler and reduce its trace."""
        if self._window is None:
            return
        import os
        import sys
        import jax
        from bench import trace as T

        self._window.__exit__(None, None, None)
        self._window = None
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        path = T.find_xplane(self._trace_dir)
        t1 = time.perf_counter()
        self.reduced_trace = T.Trace.from_file(path, window=self.WINDOW_SPAN)
        print(f"trace: {os.path.getsize(path)} bytes, "
              f"{len(self.reduced_trace.op_start)} device ops in the window, "
              f"stop {t1 - t0:.1f} s, reduce {time.perf_counter() - t1:.1f} s",
              file=sys.stderr, flush=True)
        shutil.rmtree(self._trace_dir, ignore_errors=True)

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed window (a no-op unless ``--trace 1``)."""
        self.start_trace()
        try:
            yield
        finally:
            self.stop_trace()

    def trace_slice(self, seconds: float) -> threading.Thread:
        """Profile the next ``seconds`` of wall time from a thread of its
        own, while this thread goes on (a no-op unless ``--trace 1``); join
        the returned thread before reading the trace."""

        def run():
            try:
                self.start_trace()
                time.sleep(seconds)
            finally:
                self.stop_trace()

        th = threading.Thread(target=run, name="bench-trace-slice")
        if self.trace:
            th.start()
        return th


def result_line(cell: Cell, ctx: RunContext, out: Outcome,
                devices) -> Dict[str, Any]:
    """The contract's last line of standard output."""
    d0 = devices[0]
    device: Dict[str, Any] = {"platform": d0.platform, "kind": d0.device_kind,
                              "count": len(devices),
                              "memory_peak_bytes": ctx.memory_peak_bytes or 0}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if ctx.trace:
        tr = ctx.reduced_trace
        peaks = (peaks_for(d0.device_kind, cell.root)
                 if d0.platform == "tpu" else None)
        counters = {**out.counters,
                    "memory_peak_bytes": ctx.memory_peak_bytes}
        inputs = LayerInputs(cell=cell, counters=counters, trace=tr,
                             peaks=peaks, root=cell.root)
        for e in cell.per_layer():
            reader = load_module(cell.root / "bench" / "layer_metrics"
                                 / f"{e['name']}.py")
            v = reader.read(inputs)
            if v is not None:
                metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}
        if tr is not None:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s()
            breakdown = tr.breakdown()
    else:
        for e in cell.end_to_end():
            v = ctx.setup_s if e["name"] == "setup_s" else out.metrics.get(
                e["name"])
            if v is not None:
                metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}
    line: Dict[str, Any] = {
        "correct": bool(out.failed == 0 and out.checks
                        and all(c.ok for c in out.checks)),
        "attempted": int(out.attempted), "failed": int(out.failed),
        "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line
