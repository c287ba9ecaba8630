"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy intervals, its ops and programs, and the
host spans, on one clock.

* device ops: the events of the ``XLA Ops`` line of each ``/device:TPU:<i>``
  plane (their names are the HLO instruction text); busy time is the union
  of their intervals;
* programs: the events of the ``XLA Modules`` line (one per executable run);
* host spans: host events named like a path (``divide/level3/cluster``,
  ``conquer/solve``, ``bench/window``), which ``repro.obs.spans.span`` and
  the harness write as ``jax.profiler.TraceAnnotation``s.

The window is the host span the harness puts around the traced work; every
number is clipped to it.  The trace puts host and device on one clock only
to within about a millisecond (a chip trace showed device ops starting up
to 1 ms before the host span that launched them), so attribution to spans
is good to that.  Ops are kept as arrays, not objects: a traced fit holds
millions of them.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_NAME = re.compile(r"^[a-z_]+(/[A-Za-z0-9_.-]+)+$")


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, (k, 2) sorted and disjoint."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    # a new run starts where the start passes every earlier end
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    idx = np.nonzero(new)[0]
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([iv[idx, 0], ends[last]], axis=1)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted interval
    sets."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns, the trace's clock
    dur: float            # ns
    device: int = 0

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]       # ns
    names: List[str]                  # distinct op names
    op_start: np.ndarray              # ns
    op_dur: np.ndarray                # ns
    op_name: np.ndarray               # index into ``names``
    op_dev: np.ndarray
    modules: List[Event]              # device program runs
    spans: List[Event]                # host spans (the window excluded)

    # -- building --------------------------------------------------------
    @classmethod
    def from_file(cls, path, window: str = "bench/window") -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(str(path))
        index: Dict[str, int] = {}
        start, dur, name, dev = [], [], [], []
        modules: List[Event] = []
        spans: List[Event] = []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                d = int(plane.name.rsplit(":", 1)[1])
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        for e in line.events:
                            n = e.name
                            start.append(e.start_ns)
                            dur.append(e.duration_ns)
                            name.append(index.setdefault(n, len(index)))
                            dev.append(d)
                    elif line.name == MODULES_LINE:
                        modules.extend(Event(e.name, e.start_ns,
                                             e.duration_ns, d)
                                       for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events
                                 if SPAN_NAME.match(e.name))
        ops = (list(index), np.asarray(start, float), np.asarray(dur, float),
               np.asarray(name, np.int64), np.asarray(dev, np.int64))
        return cls.build(ops, modules, spans, window)

    @classmethod
    def from_events(cls, ops: Sequence[Event], modules, spans,
                    window: str = "bench/window") -> "Trace":
        index: Dict[str, int] = {}
        ids = [index.setdefault(e.name, len(index)) for e in ops]
        arrays = (list(index), np.array([e.start for e in ops], float),
                  np.array([e.dur for e in ops], float),
                  np.array(ids, np.int64),
                  np.array([e.device for e in ops], np.int64))
        return cls.build(arrays, list(modules), list(spans), window)

    @classmethod
    def build(cls, ops, modules, spans, window) -> "Trace":
        names, start, dur, name, dev = ops
        wins = [s for s in spans if s.name == window]
        if not wins:
            raise ValueError(f"trace has no {window!r} span")
        w0, w1 = wins[0].start, wins[0].end
        keep = (start + dur > w0) & (start < w1)
        return cls((w0, w1), names, start[keep], dur[keep], name[keep],
                   dev[keep],
                   [e for e in modules if e.end > w0 and e.start < w1],
                   [s for s in spans if s.end > w0 and s.start < w1
                    and s.name != window])

    # -- intervals -------------------------------------------------------
    def _clip(self, iv: np.ndarray) -> np.ndarray:
        if len(iv) == 0:
            return iv
        iv = np.clip(iv, self.window[0], self.window[1])
        return iv[iv[:, 1] > iv[:, 0]]

    def devices(self) -> List[int]:
        return sorted(set(self.op_dev.tolist())) or [0]

    def busy_intervals(self, device: int) -> np.ndarray:
        m = self.op_dev == device
        iv = np.stack([self.op_start[m], self.op_start[m] + self.op_dur[m]],
                      axis=1)
        return merge(self._clip(iv))

    def span_intervals(self, pred: Callable[[str], bool]) -> np.ndarray:
        iv = np.array([[s.start, s.end] for s in self.spans
                       if pred(s.name)]).reshape(-1, 2)
        return merge(self._clip(iv))

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        devs = self.devices()
        return sum(float(np.sum(np.diff(self.busy_intervals(d), axis=1)))
                   for d in devs) * 1e-9 / len(devs)

    def busy_in(self, pred: Callable[[str], bool]) -> float:
        """Device-busy seconds inside the host spans ``pred`` selects,
        averaged over the chips used."""
        sp = self.span_intervals(pred)
        devs = self.devices()
        return sum(overlap(self.busy_intervals(d), sp)
                   for d in devs) * 1e-9 / len(devs)

    def span_at(self, t: float) -> str:
        """The innermost host span covering ``t`` ("host" if none)."""
        best, dur = "host", float("inf")
        for s in self.spans:
            if s.start <= t < s.end and s.dur < dur:
                best, dur = s.name, s.dur
        return best

    # -- events ----------------------------------------------------------
    def ops(self, pred: Callable[[str], bool]) -> List[Event]:
        """The op events whose name ``pred`` selects."""
        ids = [i for i, n in enumerate(self.names) if pred(n)]
        m = np.isin(self.op_name, ids)
        return [Event(self.names[k], s, d, v) for k, s, d, v in zip(
            self.op_name[m], self.op_start[m], self.op_dur[m],
            self.op_dev[m])]

    def programs(self, pred: Callable[[str], bool]) -> List[Event]:
        return [e for e in self.modules if pred(e.name)]

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device ops that took most time, and the longest idle gaps
        named by the host span they fall in."""
        tot = np.bincount(self.op_name, weights=self.op_dur,
                          minlength=len(self.names)) * 1e-9
        order = np.argsort(-tot)[:top]
        ops = [[short(self.names[i]), float(tot[i])] for i in order
               if tot[i] > 0]
        busy = self.busy_intervals(self.devices()[0])
        edges = np.concatenate([[self.window[0]], busy.reshape(-1),
                                [self.window[1]]]).reshape(-1, 2)
        gaps = sorted(((e - s, s) for s, e in edges if e > s),
                      reverse=True)[:top]
        return {"device_ops": ops,
                "idle_gaps": [[self.span_at(s + g / 2), g * 1e-9]
                              for g, s in gaps]}


def short(op: str, limit: int = 120) -> str:
    """An op's HLO text cut to its name, its result shape and what it is:
    ``%while.22 = (f32[4,1,2500]...) while`` -> ``%while.22 while``."""
    lhs, _, rhs = op.partition(" = ")
    if not rhs:
        return op[:limit]
    m = re.search(r"\}?\s([a-z][a-z0-9_.-]*)\(", rhs)
    kind = m.group(1) if m else rhs.split("(")[0]
    tgt = re.search(r'custom_call_target="([^"]+)"', rhs)
    return f"{lhs} {kind}" + (f" {tgt.group(1)}" if tgt else "")[:limit]
