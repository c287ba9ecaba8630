"""Span-tree wall-clock tracing with jax.profiler hooks.

``span("divide/level0/solve")`` wraps a fit phase.  Every span enters a
``jax.profiler.TraceAnnotation`` with the same name, so when the user runs
the XLA profiler the device timeline carries the identical labels as our
host-side tree — that naming contract is the whole point (DESIGN.md §13).
``span("serve/compute", batch=7)`` adds identifiers: they ride on the
annotation as stats (its event name stays the bare span name) and on the
tracer's record; ``with span(...) as tag: ...; tag(steps=n)`` adds them at
the end of the phase.

Host-side recording only happens while a ``SpanTracer`` is activated
(``with tracer.activate(): fit(...)``); otherwise ``span`` costs one
TraceAnnotation enter/exit, which is a no-op when no profiler session is
running.  The open span lives in a context variable, so every thread and
every asyncio task nests its own spans: the serving engine's event loop
(spans held open across ``await``) and its executor build correct trees;
every span records its thread.  Spans are timed on the monotonic
``perf_counter_ns``; the tracer takes the wall clock's offset once, so its
Chrome trace-event JSON (complete ``X`` events, microsecond timestamps,
one ``tid`` per thread — loadable in Perfetto / chrome://tracing) lies on
the timeline of the profiler's host tracer, which stamps ``time.time_ns()``.
The tracer also prints an aggregated text summary table.

Collector pauses: a ``gc.callbacks`` hook, installed once at import, wraps
every collection in a ``host/gc`` annotation, and while a tracer is active
appends ``(start_ns, end_ns, generation, thread)`` to ``SpanTracer.gc``
(outside the span tree); the Chrome export shows them as ``host/gc``
events on their threads.
"""
from __future__ import annotations

import gc
import json
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax

# Module-global active tracer; spans on every thread record into it when
# set (each thread and task nesting its own, ``_OPEN``).  A plain global
# keeps the hot path one attribute load.
_ACTIVE: Optional["SpanTracer"] = None

GC_SPAN = "host/gc"

# The innermost open span of this thread or asyncio task, with its tracer.
# Every task runs in a copy of its creator's context and every thread
# starts empty, so a span held open across ``await`` parents only what its
# own task opens.
_OPEN: ContextVar[Optional[Tuple["SpanTracer", "Span"]]] = ContextVar(
    "repro_open_span", default=None)


@dataclass
class Span:
    name: str
    t0: int                               # time.perf_counter_ns()
    t1: Optional[int] = None
    children: List["Span"] = field(default_factory=list)
    ids: Dict[str, Any] = field(default_factory=dict)
    thread: int = 0                       # threading.get_native_id()

    @property
    def duration(self) -> float:
        """Seconds."""
        return ((self.t1 if self.t1 is not None else time.perf_counter_ns())
                - self.t0) * 1e-9


class SpanTracer:
    """Collects a tree of wall-clock spans for one fit/serve run."""

    def __init__(self) -> None:
        self.roots: List[Span] = []
        # collector pauses while active: (start_ns, end_ns, generation, tid)
        self.gc: List[Tuple[int, int, int, int]] = []
        # perf_counter_ns -> time.time_ns(), for the exports
        self._wall_offset_ns = time.time_ns() - time.perf_counter_ns()

    @contextmanager
    def span(self, name: str, **ids: Any) -> Iterator[Span]:
        open_ = _OPEN.get()
        s = Span(name=name, t0=time.perf_counter_ns(), ids=ids,
                 thread=threading.get_native_id())
        if open_ is not None and open_[0] is self:
            open_[1].children.append(s)
        else:
            self.roots.append(s)
        token = _OPEN.set((self, s))
        try:
            yield s
        finally:
            s.t1 = time.perf_counter_ns()
            _OPEN.reset(token)

    @contextmanager
    def activate(self) -> Iterator["SpanTracer"]:
        global _ACTIVE
        prev = _ACTIVE
        _ACTIVE = self
        try:
            yield self
        finally:
            _ACTIVE = prev

    # -- exports ---------------------------------------------------------
    def _walk(self):
        stack = [(s, 0) for s in reversed(self.roots)]
        while stack:
            s, depth = stack.pop()
            yield s, depth
            stack.extend((c, depth + 1) for c in reversed(s.children))

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON: complete ``X`` events, ts/dur in µs of
        the wall clock (the profiler's), one ``tid`` per thread; collector
        pauses are ``host/gc`` events with their generation."""
        off = self._wall_offset_ns
        events = []
        for s, _ in self._walk():
            ev = {"name": s.name, "ph": "X", "ts": (s.t0 + off) / 1e3,
                  "dur": max(s.duration, 0.0) * 1e6, "pid": 0,
                  "tid": s.thread}
            if s.ids:
                ev["args"] = dict(s.ids)
            events.append(ev)
        for t0, t1, gen, tid in self.gc:
            events.append({"name": GC_SPAN, "ph": "X", "ts": (t0 + off) / 1e3,
                           "dur": (t1 - t0) / 1e3, "pid": 0, "tid": tid,
                           "args": {"generation": gen}})
        events.sort(key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def summary(self) -> str:
        """Aggregated text table: per-name count, total and self seconds."""
        agg: Dict[str, List[float]] = {}
        for s, _ in self._walk():
            child_total = sum(c.duration for c in s.children)
            tot, own, cnt = agg.get(s.name, (0.0, 0.0, 0))
            agg[s.name] = [tot + s.duration,
                           own + max(s.duration - child_total, 0.0),
                           cnt + 1]
        rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
        w = max([len("span")] + [len(k) for k in agg])
        lines = [f"{'span':<{w}}  {'count':>5}  {'total_s':>9}  {'self_s':>9}",
                 f"{'-' * w}  {'-' * 5}  {'-' * 9}  {'-' * 9}"]
        for name, (tot, own, cnt) in rows:
            lines.append(f"{name:<{w}}  {cnt:>5}  {tot:>9.4f}  {own:>9.4f}")
        return "\n".join(lines)


@contextmanager
def span(name: str, **ids: Any) -> Iterator[Callable[..., None]]:
    """Name a program phase: host span tree (when a tracer is active) +
    profiler annotation (always — free unless a profiler session runs).
    ``ids`` (e.g. ``batch=7``) go to both as identifiers; so do those given
    to the yielded ``tag(**ids)``, for counts known only at the end of the
    phase."""
    tracer = _ACTIVE
    with jax.profiler.TraceAnnotation(name, **ids) as ann:
        if tracer is None:
            yield ann.set_metadata
        else:
            with tracer.span(name, **ids) as s:
                def tag(**more: Any) -> None:
                    ann.set_metadata(**more)
                    s.ids.update(more)
                yield tag


# The collection in progress: (annotation, start_ns or None).  Collections
# never overlap (the interpreter runs one at a time, start and stop on the
# collecting thread), so one slot is enough.
_gc_open: List[Any] = [None, None]


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        a = jax.profiler.TraceAnnotation(GC_SPAN)
        a.__enter__()
        _gc_open[0] = a
        _gc_open[1] = time.perf_counter_ns() if _ACTIVE is not None else None
        return
    a, t0 = _gc_open
    if a is None:
        return
    _gc_open[0] = _gc_open[1] = None
    a.__exit__(None, None, None)
    tracer = _ACTIVE
    if tracer is not None and t0 is not None:
        tracer.gc.append((t0, time.perf_counter_ns(), int(info["generation"]),
                          threading.get_native_id()))


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
