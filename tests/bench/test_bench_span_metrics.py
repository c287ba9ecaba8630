"""The per-layer metrics that read the program's spans, against numbers
worked out by hand, and against a program without those spans (each reads
nothing there)."""
import asyncio
import glob

import numpy as np
import pytest

import tinyroot

from bench import harness as H
from bench import trace as T

FIT = ("divide_host_s", "interlevel_s", "fit_self_s")
SERVE = ("dispatch_ms.serve", "sync_ms.serve", "handoff_ms.serve",
         "idle_with_work.serve", "gc_share.serve")


def reader(name):
    return H.load_module(tinyroot.REPO / "bench" / "layer_metrics"
                         / f"{name}.py")


def read(name, counters=None, trace=None):
    return reader(name).read(H.LayerInputs(cell=None, counters=counters or {},
                                           trace=trace, peaks=None))


def ev(name, start, dur):
    return T.Event(name, float(start), float(dur))


# ---------------------------------------------------------------------------
# fit: seconds per fit by span name, as the fit kind hands them over
# ---------------------------------------------------------------------------

SPANS = {
    "fit": 4.0,
    "divide/level2/cluster": 0.5, "divide/level2/fetch": 0.1,
    "divide/level2/balance": 0.25, "divide/level2/partition": 0.05,
    "interlevel/level2/gather": 0.1, "divide/level2/solve": 1.0,
    "interlevel/level2/select": 0.02,
    "divide/level1/cluster": 0.3, "divide/level1/fetch": 0.05,
    "divide/level1/balance": 0.1, "divide/level1/partition": 0.04,
    "interlevel/level1/gather": 0.2, "divide/level1/solve": 1.5,
    "interlevel/level1/select": 0.03,
    "conquer/refine": 0.1, "conquer/solve": 0.05,
    "interlevel/level0/select": 0.01,
}
# the parent's tree: no root, no host parts, no interlevel spans
OLD_SPANS = {k: v for k, v in SPANS.items()
             if k.endswith(("/cluster", "/solve", "/refine"))}


def test_fit_span_metrics_by_hand():
    c = {"spans": SPANS}
    # balance + partition: 0.25 + 0.05 + 0.1 + 0.04
    assert read("divide_host_s", c) == pytest.approx(0.44)
    # gathers 0.1 + 0.2, selections 0.02 + 0.03 + 0.01
    assert read("interlevel_s", c) == pytest.approx(0.36)
    # 4.0 - clusters 0.8 - solves 2.5 - interlevel 0.36 - conquer 0.15;
    # fetch, balance and partition lie inside their cluster span
    assert read("fit_self_s", c) == pytest.approx(0.19)


def test_old_fit_metrics_read_the_same_spans():
    """No new name falls under ``divide_s``, ``cluster_solve_s`` or
    ``conquer_s``: they read the same with and without the new spans."""
    for name, want in (("divide_s", 0.8), ("cluster_solve_s", 2.5),
                       ("conquer_s", 0.15)):
        assert read(name, {"spans": SPANS}) == pytest.approx(want)
        assert read(name, {"spans": OLD_SPANS}) == pytest.approx(want)


@pytest.mark.parametrize("name", FIT)
def test_fit_span_metrics_absent(name):
    assert read(name, {"spans": OLD_SPANS}) is None
    assert read(name, {"spans": {}}) is None
    assert read(name, {}) is None


# ---------------------------------------------------------------------------
# serve: the engine's annotations in a traced window
# ---------------------------------------------------------------------------

def serve_trace():
    """Window [0, 1000) ns.  The loop waits in [0, 100), [300, 500) and
    [800, 1000).  Batch 1 [100, 300): assemble 20, compute [130, 270)
    (dispatch 50, sync 75), resolve 15.  Batch 2 [500, 800): assemble 30,
    compute [540, 760) (dispatch 100, sync 100), resolve 20.  Collections
    [200, 240), [600, 620) and [610, 640).  Device ops [150, 180),
    [160, 170), [200, 260), [450, 520), [560, 700), [950, 1100)."""
    spans = [ev("bench/window", 0, 1000),
             ev("serve/idle", 0, 100), ev("serve/idle", 300, 200),
             ev("serve/idle", 800, 200),
             ev("serve/batch", 100, 200), ev("serve/assemble", 100, 20),
             ev("serve/compute", 130, 140), ev("serve/dispatch", 135, 50),
             ev("serve/sync", 190, 75), ev("serve/resolve", 280, 15),
             ev("serve/batch", 500, 300), ev("serve/assemble", 500, 30),
             ev("serve/compute", 540, 220), ev("serve/dispatch", 545, 100),
             ev("serve/sync", 650, 100), ev("serve/resolve", 770, 20),
             ev("host/gc", 200, 40), ev("host/gc", 600, 20),
             ev("host/gc", 610, 30)]
    ops = [ev("a", 150, 30), ev("b", 160, 10), ev("c", 200, 60),
           ev("d", 450, 70), ev("e", 560, 140), ev("f", 950, 150)]
    return T.Trace.from_events(ops, [], spans)


def test_serve_span_metrics_by_hand():
    t = serve_trace()
    ms = 1e-6                                            # ns -> ms
    assert read("dispatch_ms.serve", trace=t) == pytest.approx(75 * ms)
    assert read("sync_ms.serve", trace=t) == pytest.approx(87.5 * ms)
    # (200 + 300 - (20 + 30) - (140 + 220) - (15 + 20)) / 2 batches
    assert read("handoff_ms.serve", trace=t) == pytest.approx(27.5 * ms)
    # busy 30 + 60 + 70 + 140 + 50 (clipped) = 350, of it inside the
    # waits [450, 500) and [950, 1000): 100; waits 500:
    # ((1000 - 350) - (500 - 100)) / 1000
    assert read("idle_with_work.serve", trace=t) == pytest.approx(25.0)
    # union [200, 240) + [600, 640) = 80 of 1000
    assert read("gc_share.serve", trace=t) == pytest.approx(8.0)


def test_idle_with_work_overlapping_intervals():
    """Window [0, 100), the loop waits in [20, 60), ops [10, 30) and
    [50, 70) each straddle an end of the wait.  Outside the wait the device
    is idle in [0, 10) and [70, 100): 40 of 100."""
    t = T.Trace.from_events([ev("a", 10, 20), ev("b", 50, 20)], [],
                            [ev("bench/window", 0, 100),
                             ev("serve/idle", 20, 40),
                             ev("serve/batch", 60, 40)])
    assert read("idle_with_work.serve", trace=t) == pytest.approx(40.0)
    assert read("gc_share.serve", trace=t) == 0.0       # engine, no pause


@pytest.mark.parametrize("name", SERVE)
def test_serve_span_metrics_absent(name):
    # the parent's window: device ops and the window span, no annotations
    t = T.Trace.from_events([ev("a", 10, 20)], [],
                            [ev("bench/window", 0, 100)])
    assert read(name, trace=t) is None
    assert read(name, trace=None) is None


# ---------------------------------------------------------------------------
# the program's own spans reach the readers (CPU, tiny sizes)
# ---------------------------------------------------------------------------

def test_fit_spans_reach_the_readers():
    import jax

    from repro.core import DCSVMConfig, Kernel, fit
    from repro.data import gaussian_mixture
    from repro.obs.spans import SpanTracer

    from bench.kinds.fit import span_seconds

    X, y = gaussian_mixture(jax.random.PRNGKey(3), 600, d=6,
                            modes_per_class=2, spread=0.2)
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=4.0), C=2.0, k=2, levels=2,
                      m=100, tol=1e-3)
    tracer = SpanTracer()
    with tracer.activate():
        fit(cfg, X, y)
    c = {"spans": span_seconds(tracer)}
    got = {name: read(name, c) for name in FIT}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["fit_self_s"] < c["spans"]["fit"]


def test_engine_annotations_reach_the_readers(tmp_path):
    """A CPU profile of the engine serving a few requests: the annotations
    keep their bare names (the ``batch`` id rides as a stat), so every
    serving reader finds its spans."""
    import jax

    from repro.core import DCSVMConfig, Kernel, fit
    from repro.data import gaussian_mixture
    from repro.launch.engine import AsyncServingEngine, EngineConfig
    from repro.launch.registry import ModelRegistry

    X, y = gaussian_mixture(jax.random.PRNGKey(4), 400, d=6,
                            modes_per_class=2, spread=0.2)
    model = fit(DCSVMConfig(kernel=Kernel("rbf", gamma=4.0), C=2.0, k=2,
                            levels=1, m=100, tol=1e-3), X, y)
    reg = ModelRegistry()
    reg.register("m", model, with_bcm=False)
    engine = AsyncServingEngine(reg, EngineConfig(max_batch=32))
    engine.warmup("m", strategies=["exact"])
    Xq = np.asarray(X)

    async def main():
        async with engine:
            for i in range(4):
                await engine.submit(Xq[i * 7: i * 7 + 5 + i], "m",
                                    strategy="exact")
                await asyncio.sleep(0.005)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            asyncio.run(main())
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    t = T.Trace.from_file(path)
    names = [s.name for s in t.spans]
    assert names.count("serve/batch") == names.count("serve/compute") == 4
    for name in SERVE:
        v = read(name, trace=t)
        assert v is not None and v >= 0, (name, v)
