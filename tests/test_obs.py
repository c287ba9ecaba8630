"""Telemetry subsystem (repro.obs): convergence-trace rings, span tracing,
serving metrics, and the bit-identity / host-sync contracts they must keep.

The load-bearing guarantees pinned here:

* ``trace=None`` (the default) leaves every solver trajectory bit-identical
  to the untraced build — tracing is a pure observer, and enabling it must
  not move the iterate either.
* A trace-enabled matvec solve stays free of device->host syncs (the ring
  lives on device; the fetch happens once, after).
* The ring keeps the LAST ``cap`` samples with an exact dropped count.
* Chrome trace exports are schema-valid (complete ``X`` events, sorted,
  non-negative durations); histograms/registries expose Prometheus text.
"""
import gc
import glob
import json
import math
import threading
import time
from functools import partial

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import Kernel
from repro.core.solver import (solve_box_qp, solve_box_qp_block,
                               solve_box_qp_matvec, solve_eq_qp,
                               solve_eq_qp_block, solve_eq_qp_matvec,
                               solve_eq_qp_shrink, solve_with_shrinking)
from repro.data import gaussian_mixture
from repro.obs.metrics import Counter, Gauge, LatencyHistogram, MetricsRegistry
from repro.obs.spans import SpanTracer, span
from repro.obs.trace import (TRACE_COLS, ConvTrace, trace_fetch, trace_init,
                             trace_record, trace_summary)

KERN = Kernel("rbf", gamma=4.0)


def _problem(n=96, seed=0):
    X, y = gaussian_mixture(jax.random.PRNGKey(seed), n, d=5,
                            modes_per_class=3)
    return X, y


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------

def test_trace_truncated_fill():
    tr = trace_init(8)
    for i in range(3):
        tr = trace_record(tr, pg_max=float(i), objective=float(10 + i))
    out = trace_fetch(tr)
    assert out["samples"] == 3 and out["dropped"] == 0
    assert out["pg_max"] == [0.0, 1.0, 2.0]
    assert out["objective"] == [10.0, 11.0, 12.0]
    # never-recorded columns are omitted, not NaN-filled
    assert "gamma" not in out and "cache_hits" not in out


def test_trace_wraparound_keeps_last_cap_in_order():
    tr = trace_init(4)
    for i in range(10):
        tr = trace_record(tr, pg_max=float(i))
    out = trace_fetch(tr)
    assert out["samples"] == 4 and out["dropped"] == 6
    assert out["pg_max"] == [6.0, 7.0, 8.0, 9.0]   # chronological tail
    s = trace_summary(out)
    assert s["pg_first"] == 6.0 and s["pg_last"] == 9.0


def test_trace_init_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        trace_init(0)


def test_trace_record_under_jit_and_vmap():
    def record_k(pg):
        tr = trace_init(4)
        def body(i, t):
            return trace_record(t, pg_max=pg * (i + 1.0))
        return jax.lax.fori_loop(0, 3, body, tr)

    tr = jax.jit(jax.vmap(record_k))(jnp.asarray([1.0, 10.0]))
    out = trace_fetch(tr)
    assert isinstance(out, list) and len(out) == 2
    assert out[0]["pg_max"] == [1.0, 2.0, 3.0]
    assert out[1]["pg_max"] == [10.0, 20.0, 30.0]
    merged = trace_summary(out)
    assert merged["samples"] == 6 and merged["pg_last"] == 30.0


# ---------------------------------------------------------------------------
# solver bit-identity: tracing observes, never steers
# ---------------------------------------------------------------------------

def _box(solver, seed, **kw):
    X, y = _problem(seed=seed)
    Q = (y[:, None] * y[None, :]) * KERN.pairwise(X, X)
    return partial(solver, Q, 2.0, **kw)


def _eq(solver, seed, **kw):
    X, _ = _problem(seed=seed)
    return partial(solver, KERN.pairwise(X, X), 1.0, 1.0, 0.3 * X.shape[0],
                   tol=1e-4, **kw)


def _matvec(**kw):
    X, y = _problem(seed=5)
    return partial(solve_box_qp_matvec, X, y, KERN, 2.0, tol=1e-4,
                   max_iters=400, block=16, sweeps=2, **kw)


def _eq_matvec(**kw):
    X, _ = _problem(seed=6)
    return partial(solve_eq_qp_matvec, X, jnp.ones(X.shape[0]), KERN, 1.0,
                   1.0, 0.3 * X.shape[0], tol=1e-4, max_iters=600, **kw)


# entry -> (builds the solve, whether its last sample is the returned
# stopping value: the shrinking wrappers recompute it on the full problem
# and the equality loops return the fresh-gradient violation instead)
TRACED_SOLVES = {
    "solve_box_qp": (lambda: _box(solve_box_qp, 0, tol=1e-5,
                                  max_iters=2000), True),
    "solve_box_qp_block": (lambda: _box(solve_box_qp_block, 7, tol=1e-5,
                                        max_iters=2000, block=8), True),
    "solve_with_shrinking": (lambda: _box(solve_with_shrinking, 1, tol=1e-4,
                                          max_iters=4000, rounds=3), False),
    "solve_box_qp_matvec-xla": (_matvec, True),
    "solve_box_qp_matvec-cached": (lambda: _matvec(cache_cap=48), True),
    "solve_box_qp_matvec-pallas": (lambda: _matvec(use_pallas=True), True),
    "solve_eq_qp": (lambda: _eq(solve_eq_qp, 2, max_iters=4000), False),
    "solve_eq_qp_block": (lambda: _eq(solve_eq_qp_block, 3, max_iters=2000,
                                      block=4), False),
    "solve_eq_qp_shrink": (lambda: _eq(solve_eq_qp_shrink, 4,
                                       max_iters=4000, rounds=3), False),
    "solve_eq_qp_matvec-b1": (_eq_matvec, False),
    "solve_eq_qp_matvec-b4": (lambda: _eq_matvec(block=4), False),
}


@pytest.mark.parametrize("entry", list(TRACED_SOLVES))
def test_traced_solve_is_bit_identical(entry):
    """Tracing observes, never steers: every engine's traced solve returns
    the untraced result bit for bit, with one sample per iteration."""
    build, last_is_stop = TRACED_SOLVES[entry]
    solve = build()
    r0 = solve(trace=None)
    r1 = solve(trace=trace_init(32))
    for field in ("alpha", "grad", "iters", "pg_max"):
        assert np.array_equal(np.asarray(getattr(r0, field)),
                              np.asarray(getattr(r1, field))), field
    assert r0.trace is None
    out = trace_fetch(r1.trace)
    assert out["samples"] > 0
    assert out["samples"] + out["dropped"] == int(r1.iters)
    # the recorded columns carry real values
    n = r1.alpha.shape[0]
    assert all(f == int(f) and 0 <= f <= n for f in out["n_free"])
    if last_is_stop:
        assert out["pg_max"][-1] == pytest.approx(float(r1.pg_max), rel=1e-6)
    assert ("cache_hits" in out) == (r1.cache_hits is not None)


def test_traced_matvec_solve_stays_host_sync_free():
    """The trace ring must live on device: recording adds no host round-trip
    to the matvec CD loop (same pin as the cache/spill counters)."""
    X, y = _problem(n=128, seed=3)
    kw = dict(tol=1e-4, max_iters=2000, block=16, sweeps=2)
    r0 = solve_box_qp_matvec(X, y, KERN, 2.0, **kw)
    # warm the traced program (compilation may inspect host values)
    solve_box_qp_matvec(X, y, KERN, 2.0, trace=trace_init(32), **kw)
    with jax.transfer_guard_device_to_host("disallow"):
        r1 = solve_box_qp_matvec(X, y, KERN, 2.0, trace=trace_init(32), **kw)
        r1.alpha.block_until_ready()
    assert np.array_equal(np.asarray(r0.alpha), np.asarray(r1.alpha))
    assert trace_fetch(r1.trace)["samples"] > 0


def test_fit_trace_config_is_bit_identical_and_fetched_once():
    from repro.core.dcsvm import DCSVMConfig, fit

    X, y = _problem(n=120, seed=4)
    base = dict(kernel=KERN, C=2.0, k=2, levels=1, m=64, tol=1e-4,
                max_iters=2000, seed=0)
    m0 = fit(DCSVMConfig(**base), X, y)
    m1 = fit(DCSVMConfig(**base, trace=16), X, y)
    assert np.array_equal(np.asarray(m0.alpha), np.asarray(m1.alpha))
    st0, st1 = m0.level_stats[-1], m1.level_stats[-1]
    assert "trace" not in st0                       # default: no trace key
    assert st1["trace_summary"]["samples"] > 0
    assert st1["trace_summary"]["pg_last"] <= st1["trace_summary"]["pg_first"]


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_span_tree_chrome_trace_schema(tmp_path):
    tracer = SpanTracer()
    with tracer.activate():
        with span("fit"):
            with span("divide/level1/solve"):
                pass
            with span("conquer/solve"):
                pass
    with span("outside"):                           # inactive: not recorded
        pass
    ct = tracer.chrome_trace()
    events = ct["traceEvents"]
    assert [e["name"] for e in events][0] == "fit"
    assert {e["name"] for e in events} == {"fit", "divide/level1/solve",
                                           "conquer/solve"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
    assert all(events[i]["ts"] <= events[i + 1]["ts"]
               for i in range(len(events) - 1))
    # parent span covers its children
    fit_ev = next(e for e in events if e["name"] == "fit")
    child_dur = sum(e["dur"] for e in events if e["name"] != "fit")
    assert fit_ev["dur"] >= child_dur * (1 - 1e-6)
    path = tmp_path / "trace.json"
    tracer.write_chrome_trace(str(path))
    assert json.loads(path.read_text())["traceEvents"]
    table = tracer.summary()
    assert "fit" in table and "conquer/solve" in table


def test_span_nesting_restores_active_tracer():
    t1, t2 = SpanTracer(), SpanTracer()
    with t1.activate():
        with span("outer"):
            with t2.activate():
                with span("inner"):
                    pass
            with span("outer2"):
                pass
    assert {s.name for s in t1.roots} == {"outer"}
    assert {s.name for s in t2.roots} == {"inner"}
    assert [c.name for c in t1.roots[0].children] == ["outer2"]


def test_span_threads_build_separate_trees():
    """Two threads open nested spans at the same time under one tracer: the
    barrier makes each open and close while the other holds its span open,
    so a shared stack would pop the wrong span.  Each gets its own tree."""
    tracer = SpanTracer()
    step = threading.Barrier(2, timeout=10)
    tids = {}

    def worker(tag):
        tids[tag] = threading.get_native_id()
        with span(f"outer/{tag}"):
            step.wait()
            with span(f"inner/{tag}", batch=tag):
                step.wait()
            step.wait()
            with span(f"second/{tag}"):
                step.wait()

    with tracer.activate():
        ths = [threading.Thread(target=worker, args=(t,)) for t in "ab"]
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=30)
    assert not any(th.is_alive() for th in ths)
    roots = {r.name: r for r in tracer.roots}
    assert set(roots) == {"outer/a", "outer/b"}
    for tag in "ab":
        r = roots[f"outer/{tag}"]
        assert [c.name for c in r.children] == [f"inner/{tag}",
                                                f"second/{tag}"]
        assert r.children[0].ids == {"batch": tag}
        assert {r.thread} | {c.thread for c in r.children} == {tids[tag]}
        assert all(r.t0 <= c.t0 <= c.t1 <= r.t1 for c in r.children)
    assert tids["a"] != tids["b"]


def test_span_tasks_nest_across_await():
    """Two asyncio tasks on one thread hold spans open across ``await`` and
    interleave: ``outer/a`` closes while ``outer/b`` is open, and each
    child nests under its own task's span."""
    import asyncio

    tracer = SpanTracer()

    async def main():
        a_open, b_open, a_done = (asyncio.Event() for _ in range(3))

        async def a():
            with span("outer/a"):
                a_open.set()
                await b_open.wait()
                with span("inner/a"):
                    pass
            a_done.set()

        async def b():
            await a_open.wait()
            with span("outer/b"):
                b_open.set()
                await a_done.wait()
                with span("inner/b"):
                    pass

        await asyncio.gather(a(), b())

    with tracer.activate():
        asyncio.run(main())
        with span("after"):
            pass
    assert [r.name for r in tracer.roots] == ["outer/a", "outer/b", "after"]
    for r, tag in zip(tracer.roots, "ab"):
        assert [c.name for c in r.children] == [f"inner/{tag}"]
    assert tracer.roots[2].children == []


def test_gc_pause_recorded_outside_the_tree():
    tracer = SpanTracer()
    gc.collect()                                    # not active: not kept
    with tracer.activate():
        with span("work"):
            gc.collect()
    assert [r.name for r in tracer.roots] == ["work"]
    assert tracer.roots[0].children == []
    full = [g for g in tracer.gc if g[2] == 2]
    assert full, tracer.gc
    t0, t1, _, tid = full[-1]
    w = tracer.roots[0]
    assert w.t0 <= t0 <= t1 <= w.t1
    assert tid == threading.get_native_id()
    n = len(tracer.gc)
    gc.collect()
    assert len(tracer.gc) == n
    # the Chrome export shows each pause on its thread, inside ``work``
    ev = tracer.chrome_trace()["traceEvents"]
    pauses = [e for e in ev if e["name"] == "host/gc"]
    assert len(pauses) == n
    p = [e for e in pauses if e["args"] == {"generation": 2}][-1]
    (wev,) = [e for e in ev if e["name"] == "work"]
    assert p["tid"] == tid
    assert wev["ts"] <= p["ts"] <= p["ts"] + p["dur"] <= wev["ts"] + wev["dur"]
    assert p["dur"] == pytest.approx((t1 - t0) / 1e3)


def test_span_ids_kept():
    tracer = SpanTracer()
    with tracer.activate():
        with span("serve/batch", batch=3):
            with span("serve/assemble"):
                pass
    b = tracer.roots[0]
    assert b.ids == {"batch": 3} and b.children[0].ids == {}
    ev = {e["name"]: e for e in tracer.chrome_trace()["traceEvents"]}
    assert ev["serve/batch"]["args"] == {"batch": 3}
    assert "args" not in ev["serve/assemble"]
    assert ev["serve/batch"]["tid"] == threading.get_native_id()


def test_span_tag_adds_ids_at_the_end(tmp_path):
    """``tag(**ids)`` from ``with span(...) as tag`` adds identifiers known
    only at the end of the phase, to the tracer's record and to the
    profiler annotation, and costs nothing without a tracer."""
    from jax.profiler import ProfileData

    tracer = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.activate():
            with span("tag/late", batch=2) as tag:
                tag(redirected=5, steps=3)
        with span("tag/untraced") as tag:
            tag(steps=4)
    finally:
        jax.profiler.stop_trace()
    (s,) = tracer.roots
    assert s.ids == {"batch": 2, "redirected": 5, "steps": 3}
    ev = {e["name"]: e for e in tracer.chrome_trace()["traceEvents"]}
    assert ev["tag/late"]["args"] == {"batch": 2, "redirected": 5, "steps": 3}
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stats = {}
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tag/"):
                        stats[e.name] = dict(e.stats)
    assert stats == {"tag/late": {"batch": 2, "redirected": 5, "steps": 3},
                     "tag/untraced": {"steps": 4}}


def test_span_clock_agrees_with_the_profiler(tmp_path):
    """The tracer and the profiler's host tracer stamp the same spans: the
    distance between two span starts agrees within 0.2 ms, and an
    identifier rides on the annotation as a stat while the event keeps the
    bare name."""
    from jax.profiler import ProfileData

    tracer = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.activate():
            wall0 = time.time_ns()
            with span("clock/first", batch=11):
                time.sleep(0.01)
            time.sleep(0.02)
            with span("clock/second"):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    ev = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("clock/"):
                        ev[e.name] = e
    assert set(ev) == {"clock/first", "clock/second"}
    assert dict(ev["clock/first"].stats).get("batch") == 11
    a, b = tracer.roots
    d_tracer = (b.t0 - a.t0) * 1e-9
    d_profiler = (ev["clock/second"].start_ns - ev["clock/first"].start_ns) \
        * 1e-9
    assert d_tracer > 0.02
    assert abs(d_tracer - d_profiler) < 0.2e-3
    assert abs(a.duration - ev["clock/first"].duration_ns * 1e-9) < 0.2e-3
    # the export stamps the wall clock the profiler's host tracer uses
    # (ProfileData's starts are relative to the session, so the check is
    # against time.time_ns() read just before the first span)
    ts = {e["name"]: e["ts"] for e in tracer.chrome_trace()["traceEvents"]}
    assert abs(ts["clock/first"] * 1e3 - wall0) < 1e6


# ---------------------------------------------------------------------------
# serving metrics
# ---------------------------------------------------------------------------

def test_latency_histogram_streaming_stats():
    h = LatencyHistogram()
    vals = [1e-4, 2e-4, 5e-4, 1e-3, 5e-3, 2e-2, 0.5]
    for v in vals:
        h.observe(v)
    assert h.total == len(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.vmin == min(vals) and h.vmax == max(vals)
    assert min(vals) <= h.quantile(0.5) <= max(vals)
    assert h.quantile(0.5) <= h.quantile(0.95) <= h.quantile(0.99)
    j = h.to_json()
    assert j["count"] == len(vals)
    assert sum(j["buckets"].values()) == len(vals)
    # an observation past the top bound lands in +Inf
    h.observe(100.0)
    assert h.to_json()["buckets"]["+Inf"] == 1


def test_latency_histogram_empty():
    j = LatencyHistogram().to_json()
    assert j["count"] == 0 and j["p50"] is None and j["buckets"] == {}
    assert math.isnan(LatencyHistogram().quantile(0.5))


def test_metrics_registry_labels_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", strategy="early").inc(3)
    reg.counter("serve_requests_total", strategy="exact").inc()
    assert reg.counter("serve_requests_total", strategy="early").value == 3
    h = reg.histogram("serve_latency_seconds", strategy="early")
    h.observe(1e-3)
    h.observe(2e-3)
    j = reg.to_json()
    assert j["counters"]['serve_requests_total{strategy="early"}'] == 3
    assert j["counters"]['serve_requests_total{strategy="exact"}'] == 1
    text = reg.to_prometheus_text()
    assert "# TYPE serve_requests_total counter" in text
    assert "# TYPE serve_latency_seconds histogram" in text
    # cumulative buckets: the +Inf bucket equals _count
    inf_line = [l for l in text.splitlines()
                if l.startswith("serve_latency_seconds_bucket")
                and 'le="+Inf"' in l]
    assert inf_line and inf_line[0].split()[-1] == "2"
    assert 'serve_latency_seconds_count{strategy="early"} 2' in text


def test_prometheus_type_lines_not_shared_across_kinds():
    """Regression: ``to_prometheus_text`` used ONE ``seen_types`` set for
    counters and histograms, so a histogram sharing a counter's base name
    lost its ``# TYPE`` line.  Per-kind tracking emits both."""
    reg = MetricsRegistry()
    reg.counter("serve_work").inc(2)
    reg.histogram("serve_work").observe(0.5)     # same base name, other kind
    text = reg.to_prometheus_text()
    assert "# TYPE serve_work counter" in text
    assert "# TYPE serve_work histogram" in text
    # and each exposition family got a HELP line
    assert text.count("# HELP serve_work ") == 2


def test_prometheus_empty_registry_is_empty_string():
    """Regression: an empty registry emitted ``"\\n"`` (one blank line) —
    scrapers treat that differently from "no metrics"."""
    assert MetricsRegistry().to_prometheus_text() == ""


def test_prometheus_help_and_gauge_exposition():
    reg = MetricsRegistry()
    reg.describe("serve_queue_depth", "query rows currently queued")
    g = reg.gauge("serve_queue_depth")
    g.set(7)
    g.inc(2)
    g.dec()
    assert isinstance(g, Gauge) and g.value == 8
    text = reg.to_prometheus_text()
    assert "# HELP serve_queue_depth query rows currently queued" in text
    assert "# TYPE serve_queue_depth gauge" in text
    assert "serve_queue_depth 8" in text
    assert text.endswith("\n")
    # undescribed metrics fall back to the base name as HELP text
    reg.counter("serve_requests_total").inc()
    assert ("# HELP serve_requests_total serve_requests_total"
            in reg.to_prometheus_text())
    # gauges only appear in to_json when present (schema compatibility)
    assert "gauges" in reg.to_json()
    assert MetricsRegistry().to_json().keys() == {"counters", "histograms"}


def test_metrics_registry_dump(tmp_path):
    reg = MetricsRegistry()
    reg.counter("requests_total").inc(5)
    reg.histogram("latency_seconds").observe(0.01)
    jpath = tmp_path / "metrics.json"
    prom = reg.dump(str(jpath))
    assert json.loads(jpath.read_text())["counters"]["requests_total"] == 5
    assert prom.endswith(".prom")
    assert "latency_seconds_bucket" in open(prom).read()


def test_counter_is_plain_int():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5


# ---------------------------------------------------------------------------
# benchmark artifact merge
# ---------------------------------------------------------------------------

def test_emit_json_merge_keeps_other_sections(tmp_path, monkeypatch):
    from benchmarks.common import emit_json

    path = str(tmp_path / "BENCH.json")
    emit_json(path, {"kernels": {"a": 1}})
    emit_json(path, {"outofcore": {"b": 2}}, merge=True)
    d = json.load(open(path))
    assert d["kernels"] == {"a": 1} and d["outofcore"] == {"b": 2}
    # merge replaces a same-named section wholesale
    emit_json(path, {"outofcore": {"c": 3}}, merge=True)
    assert json.load(open(path))["outofcore"] == {"c": 3}
    # a corrupt artifact starts fresh instead of crashing the bench
    with open(path, "w") as f:
        f.write("{not json")
    emit_json(path, {"trace": {"d": 4}}, merge=True)
    assert json.load(open(path))["trace"] == {"d": 4}
