"""Async serving engine + versioned registry tests.

Covers the ragged-batch recompile fixes (bucket-derived capacity, ONE
compile across ragged sizes sharing a bucket), queue/bucketing determinism
(async results bit-equal to direct ``serve_batch``), registry
resolve/hot-swap under in-flight requests, manifest round-trips for all
three tasks, and the engine's zero-recompiles-after-warmup invariant under
a Poisson trace with mixed request sizes and two registered versions.
"""
import asyncio
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import DCSVMConfig, Kernel, fit, fit_ova
from repro.core.predict import _early_program, bucket_size
from repro.core.tasks import EpsilonSVR, OneClassSVM
from repro.data import (
    friedman1,
    gaussian_mixture_multiclass,
    gaussian_with_outliers,
    train_test_split,
)
from repro.launch.engine import (
    AsyncServingEngine,
    DeadlineExceeded,
    EngineConfig,
    EngineOverloaded,
)
from repro.launch.registry import ModelManifest, ModelRegistry
from repro.launch.serve_svm import (
    export_serving_model,
    run_request_loop,
    serve_batch,
    serving_cache_size,
)

KERN = Kernel("rbf", gamma=16.0)


@pytest.fixture(scope="module")
def ova_models():
    """Two versions of a 3-class OVA model (different C) + a query pool."""
    X, y = gaussian_mixture_multiclass(jax.random.PRNGKey(0), 700,
                                       n_classes=3, d=8, spread=0.10)
    Xtr, ytr, Xte, yte = train_test_split(jax.random.PRNGKey(1), X, y)
    cfg1 = DCSVMConfig(kernel=KERN, C=4.0, k=4, levels=1, m=200, tol=1e-3)
    cfg2 = DCSVMConfig(kernel=KERN, C=2.0, k=4, levels=1, m=200, tol=1e-3)
    return fit_ova(cfg1, Xtr, ytr), fit_ova(cfg2, Xtr, ytr), np.asarray(Xte)


@pytest.fixture(scope="module")
def registry2(ova_models):
    m1, m2, _ = ova_models
    reg = ModelRegistry()
    reg.register("mix", m1)
    reg.register("mix", m2)
    return reg


def _mixed_batches(Xpool, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [Xpool[rng.integers(0, Xpool.shape[0], size=s)] for s in sizes]


# ---------------------------------------------------------------------------
# bucket-shape capacity: the ragged-batch recompile fixes
# ---------------------------------------------------------------------------

def test_bucket_size_policy():
    assert [bucket_size(n) for n in (0, 1, 7, 8, 9, 64, 100, 300)] == \
        [8, 8, 8, 8, 16, 64, 128, 512]
    # past hi: multiples of hi, not the next power of two
    assert bucket_size(5000, hi=4096) == 8192
    assert bucket_size(9000, hi=4096) == 12288


def test_one_compile_across_ragged_sizes(ova_models):
    """THE recompile bug: unbucketed, every distinct batch size is a fresh
    ``early_capacity`` static arg and a fresh compile of the early program.
    Bucketed, ragged sizes sharing one bucket share ONE compile."""
    m1, _, Xpool = ova_models
    sm = export_serving_model(m1)
    sizes = [33, 50, 64, 40, 57]                  # all bucket to 64
    batches = _mixed_batches(Xpool, sizes)
    before = _early_program._cache_size()
    for b in batches:
        serve_batch(sm, jnp.asarray(b), KERN, "early", bucket=64)
    assert _early_program._cache_size() - before == 1
    # the unbucketed path compiles per distinct size (the defect this PR
    # fixes in every serving loop; kept for single-shot compatibility).
    # size 64 is excluded: its raw signature equals the warmed bucket's.
    ragged = [b for b in batches if b.shape[0] != 64]
    before = _early_program._cache_size()
    for b in ragged:
        serve_batch(sm, jnp.asarray(b), KERN, "early")
    assert _early_program._cache_size() - before == len(ragged)


@pytest.mark.parametrize("strategy", ["exact", "early", "bcm"])
def test_bucketed_bit_identical_to_unbucketed(ova_models, strategy):
    """Padding rows must not perturb the real rows: bucketed scores are
    bit-identical to the unbucketed ``serve_batch`` on the same rows."""
    m1, _, Xpool = ova_models
    sm = export_serving_model(m1)
    for size in (3, 17, 33):
        Xq = jnp.asarray(_mixed_batches(Xpool, [size], seed=size)[0])
        pred_u, scores_u = serve_batch(sm, Xq, KERN, strategy)
        pred_b, scores_b = serve_batch(sm, Xq, KERN, strategy,
                                       bucket=bucket_size(size))
        np.testing.assert_array_equal(np.asarray(scores_u),
                                      np.asarray(scores_b))
        np.testing.assert_array_equal(np.asarray(pred_u), np.asarray(pred_b))


def test_serve_batch_rejects_undersized_bucket(ova_models):
    m1, _, Xpool = ova_models
    sm = export_serving_model(m1)
    with pytest.raises(ValueError, match="bucket"):
        serve_batch(sm, jnp.asarray(Xpool[:32]), KERN, "early", bucket=16)


def test_request_loop_warms_every_ragged_shape(ova_models):
    """Pre-fix, ``run_request_loop`` warmed only the first batch's shape, so
    ragged streams compiled INSIDE the timed region (corrupting p95/p99).
    Now every distinct bucket signature is warmed first: the report's
    ``compiles_timed`` (jit-cache growth across the timed loop) is zero."""
    m1, _, Xpool = ova_models
    sm = export_serving_model(m1)
    batches = _mixed_batches(Xpool, [5, 12, 33, 64, 9, 50, 2])
    rep = run_request_loop(sm, KERN, "early", batches, warmup=1,
                           bucketed=True)
    assert rep["compiles_timed"] == 0
    assert rep["batch"] == 0 and rep["batches"] == 7
    assert rep["queries"] == 5 + 12 + 33 + 64 + 9 + 50 + 2
    assert rep["lat_ms_p99"] >= rep["lat_ms_p50"] > 0


# ---------------------------------------------------------------------------
# manifests
# ---------------------------------------------------------------------------

def test_manifest_roundtrip_all_tasks():
    """svc / svr / ocsvm (incl. per-cluster rho_c of an early-stopped
    one-class model) manifests all survive the JSON round trip."""
    reg = ModelRegistry()
    kern = Kernel("rbf", gamma=4.0)
    # svc
    X, y = gaussian_mixture_multiclass(jax.random.PRNGKey(2), 300,
                                       n_classes=3, d=6, spread=0.1)
    cfg = DCSVMConfig(kernel=kern, C=4.0, k=2, levels=1, m=100, tol=1e-2)
    reg.register("svc", fit_ova(cfg, X, y))
    # svr
    Xr, yr = friedman1(jax.random.PRNGKey(3), 300)
    reg.register("svr", fit(cfg, Xr, yr, task=EpsilonSVR(eps=0.2)),
                 with_bcm=False)
    # ocsvm, early-stopped => per-cluster rho_c
    Xo, _ = gaussian_with_outliers(jax.random.PRNGKey(4), 300)
    cfg_o = DCSVMConfig(kernel=kern, C=1.0, k=2, levels=1, m=100, tol=1e-2,
                        early_stop_level=1)
    reg.register("ocsvm", fit(cfg_o, Xo, task=OneClassSVM(nu=0.2)))

    for name, task, n_classes in (("svc", "svc", 3), ("svr", "svr", 0),
                                  ("ocsvm", "ocsvm", 1)):
        man = reg.resolve(name).manifest
        assert man.task == task and man.n_classes == n_classes
        rt = ModelManifest.from_json(man.to_json())
        assert rt == man
        assert rt.make_kernel() == kern
    assert reg.resolve("svr").manifest.eps == pytest.approx(0.2)
    assert reg.resolve("svr").manifest.strategies == ("exact", "early")
    oc = reg.resolve("ocsvm").manifest
    assert oc.nu == pytest.approx(0.2)
    assert len(oc.rho_c) == 2            # k=2 per-cluster offsets survived
    # manifests JSON is what --registry dumps
    j = reg.to_json()
    assert {m["name"] for m in j["models"]} == {"svc", "svr", "ocsvm"}


def test_registry_versioning_and_routing(registry2):
    assert registry2.versions("mix") == [1, 2]
    assert registry2.default_version("mix") == 1        # first stays default
    assert registry2.resolve("mix").version == 1
    assert registry2.resolve("mix", 2).version == 2
    with pytest.raises(KeyError):
        registry2.resolve("mix", 9)
    with pytest.raises(KeyError):
        registry2.resolve("nope")
    with pytest.raises(ValueError, match="default"):
        registry2.drop("mix", 1)                        # routed default
    with pytest.raises(ValueError, match="registered"):
        registry2.register("mix", object(), version=2)  # duplicate version


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def test_async_bit_equal_to_direct_serve(registry2):
    """Queue/bucketing determinism: whatever the batch manager merges, each
    request's rows come back bit-identical to a direct ``serve_batch`` on
    those rows (per-row scores are independent of batch-mates/padding)."""
    Xpool = np.asarray(registry2.resolve("mix").sm.Xall)   # any pool works
    sizes = [1, 7, 33, 12, 64, 50, 3, 28]
    reqs = _mixed_batches(Xpool, sizes, seed=5)

    async def main():
        engine = AsyncServingEngine(registry2, EngineConfig(max_batch=64))
        engine.warmup("mix", strategies=["early", "exact"])
        async with engine:
            outs = await asyncio.gather(*[
                engine.submit(r, "mix", strategy="early") for r in reqs])
        return outs

    outs = asyncio.run(main())
    entry = registry2.resolve("mix")
    for r, (pred, scores) in zip(reqs, outs):
        dp, ds = serve_batch(entry.sm, jnp.asarray(r), entry.kern, "early",
                             bucket=bucket_size(len(r)))
        np.testing.assert_array_equal(np.asarray(scores), np.asarray(ds))
        np.testing.assert_array_equal(np.asarray(pred), np.asarray(dp))


def test_engine_zero_compiles_after_warmup_poisson(registry2):
    """Acceptance: Poisson arrivals, mixed sizes, BOTH registered versions —
    zero recompiles after warmup, pinned by the compile counter AND the raw
    jit-cache size."""
    Xpool = np.asarray(registry2.resolve("mix").sm.Xall)
    rng = np.random.default_rng(7)
    n_req = 40
    sizes = rng.choice([1, 4, 16, 64], size=n_req, p=[0.35, 0.3, 0.25, 0.1])
    gaps = rng.exponential(1.0 / 2000.0, size=n_req)

    engine = AsyncServingEngine(registry2, EngineConfig(max_batch=64))
    engine.warmup("mix", strategies=["early"])
    cache_after_warmup = serving_cache_size()

    async def main():
        async with engine:
            async def one(i):
                await asyncio.sleep(float(np.sum(gaps[: i + 1])))
                X = Xpool[rng.integers(0, Xpool.shape[0], size=int(sizes[i]))]
                return await engine.submit(X, "mix", version=1 + i % 2,
                                           strategy="early")
            await asyncio.gather(*[one(i) for i in range(n_req)])

    asyncio.run(main())
    assert serving_cache_size() == cache_after_warmup
    st = engine.stats()
    assert st["compiles_after_warmup"] == 0
    assert st["requests"] == n_req and st["queries"] == int(sizes.sum())
    # engine metrics made it through: per-version latency histograms,
    # fill-ratio histogram, queue-depth gauge
    j = engine.metrics.to_json()
    assert any('version="1"' in k for k in j["histograms"])
    assert any('version="2"' in k for k in j["histograms"])
    assert any(k.startswith("serve_batch_fill_ratio")
               for k in j["histograms"])
    assert j["gauges"]["serve_queue_depth"] == 0


def test_hot_swap_under_inflight_requests(ova_models):
    """Swap repoints NEW submits atomically; requests already queued on the
    old version drain on it, then the old version is dropped."""
    m1, m2, Xpool = ova_models
    reg = ModelRegistry()
    reg.register("m", m1)
    reg.register("m", m2)
    results = {}

    async def main():
        engine = AsyncServingEngine(reg, EngineConfig(max_batch=32))
        engine.warmup("m", strategies=["early"])
        async with engine:
            pre = [asyncio.ensure_future(
                engine.submit(Xpool[i * 8:(i + 1) * 8], "m",
                              strategy="early")) for i in range(4)]
            # let the submit coroutines run to their enqueue point so they
            # resolve v1 (the route table as of NOW) before the swap lands
            await asyncio.sleep(0)
            old = await engine.swap("m", 2)
            assert old == 1
            post = await engine.submit(Xpool[:8], "m", strategy="early")
            results["pre"] = [await f for f in pre]
            results["post"] = post
        assert reg.versions("m") == [2]       # drained, then dropped
        assert reg.default_version("m") == 2

    asyncio.run(main())
    # pre-swap requests were served by v1, post-swap by v2 — each matches a
    # direct serve against the respective model
    sm2 = reg.resolve("m", 2).sm
    sm1 = export_serving_model(m1)
    for i, (pred, scores) in enumerate(results["pre"]):
        _, ref = serve_batch(sm1, jnp.asarray(Xpool[i * 8:(i + 1) * 8]),
                             KERN, "early", bucket=bucket_size(8))
        np.testing.assert_array_equal(np.asarray(scores), np.asarray(ref))
    _, ref2 = serve_batch(sm2, jnp.asarray(Xpool[:8]), KERN, "early",
                          bucket=bucket_size(8))
    np.testing.assert_array_equal(np.asarray(results["post"][1]),
                                  np.asarray(ref2))


def test_engine_rejects_unserveable_strategy(ova_models):
    """A with_bcm=False export's manifest caps the strategy set; the engine
    refuses at submit instead of crashing inside the batch loop."""
    m1, _, Xpool = ova_models
    reg = ModelRegistry()
    reg.register("m", m1, with_bcm=False)

    async def main():
        async with AsyncServingEngine(reg) as engine:
            with pytest.raises(ValueError, match="does not serve"):
                await engine.submit(Xpool[:4], "m", strategy="bcm")

    asyncio.run(main())


def test_engine_submit_requires_running_loop(registry2):
    engine = AsyncServingEngine(registry2)
    with pytest.raises(RuntimeError, match="not running"):
        asyncio.run(engine.submit(np.zeros((2, 8), np.float32), "mix"))


def test_engine_spans_per_batch(registry2):
    """Under a SpanTracer each served batch gives one ``serve/batch`` on the
    loop thread (children ``serve/assemble``, ``serve/resolve``) and one
    ``serve/compute`` on the executor thread (children ``serve/dispatch``,
    ``serve/sync``) with the same batch id; waits for work are
    ``serve/idle``."""
    from repro.obs.spans import SpanTracer

    Xpool = np.asarray(registry2.resolve("mix").sm.Xall)
    reqs = _mixed_batches(Xpool, [3, 17, 40, 8, 1, 30], seed=11)
    engine = AsyncServingEngine(registry2, EngineConfig(max_batch=64))
    engine.warmup("mix", strategies=["exact"])
    tracer = SpanTracer()

    async def main():
        async with engine:
            for i in range(0, len(reqs), 2):       # bursts of two, then idle
                await asyncio.gather(*[engine.submit(r, "mix",
                                                     strategy="exact")
                                       for r in reqs[i:i + 2]])
                await asyncio.sleep(0.01)

    with tracer.activate():
        asyncio.run(main())
    loop_tid = threading.get_native_id()       # asyncio.run's loop thread
    roots = tracer.roots
    batches = [r for r in roots if r.name == "serve/batch"]
    computes = {r.ids["batch"]: r for r in roots if r.name == "serve/compute"}
    assert {r.name for r in roots} == {"serve/batch", "serve/compute",
                                       "serve/idle"}
    n = engine.metrics.histogram("serve_batch_fill_ratio").total
    assert len(batches) == n == len(computes) >= 3
    assert sorted(b.ids["batch"] for b in batches) == sorted(computes)
    for b in batches:
        assert b.thread == loop_tid
        assert [c.name for c in b.children] == ["serve/assemble",
                                                "serve/resolve"]
        c = computes[b.ids["batch"]]
        assert c.thread != loop_tid
        assert [k.name for k in c.children] == ["serve/dispatch",
                                                "serve/sync"]
        assert b.t0 <= c.t0 <= c.t1 <= b.t1
    idle = [r for r in roots if r.name == "serve/idle"]
    assert idle and all(r.thread == loop_tid and not r.children
                        for r in idle)


# ---------------------------------------------------------------------------
# overload robustness: shed / deadlines / liveness / supervision
# ---------------------------------------------------------------------------

class _GatedServe:
    """Wraps ``serve_batch`` behind a threading gate: the batch loop's
    executor thread blocks in ``__call__`` until ``release`` is set, giving
    tests a deterministic window in which the loop is mid-batch (popped,
    computing) while the event loop itself stays live."""

    def __init__(self):
        self.release = threading.Event()
        self.entered = threading.Event()

    def __call__(self, *a, **kw):
        self.entered.set()
        assert self.release.wait(30), "gate never released"
        return serve_batch(*a, **kw)


async def _until_inflight(gate: _GatedServe) -> None:
    while not gate.entered.is_set():
        await asyncio.sleep(0.001)


def _hist_count(engine, name):
    return sum(h["count"] for k, h in
               engine.metrics.to_json()["histograms"].items()
               if k.startswith(name))


def test_engine_death_surfaces_in_stop_submit_drain(ova_models):
    """Satellite 1 regression: a poisoned registry entry kills the batch
    loop at batch formation; pre-fix, ``stop()``/``drain()`` spun forever
    on a queue that never empties and the task's exception was swallowed.
    Now the death is supervised: queued futures fail, ``submit`` re-raises,
    and ``stop()`` surfaces the error in bounded time."""
    m1, _, Xpool = ova_models
    reg = ModelRegistry()
    reg.register("m", m1)

    async def main():
        engine = AsyncServingEngine(reg, EngineConfig(max_batch=32))
        engine.warmup("m", strategies=["early"])
        await engine.start()
        fut = asyncio.ensure_future(
            engine.submit(Xpool[:8], "m", strategy="early"))
        await asyncio.sleep(0)           # submit enqueued; loop not yet run
        reg._entries[("m", 1)] = None    # poison: formation resolve raises
        await asyncio.sleep(0.05)        # let the loop die on the poison
        # the queued request's future was failed by the supervisor
        with pytest.raises(KeyError, match="version"):
            await fut
        # submit fails fast with the loop's exception, not a hang
        with pytest.raises(KeyError, match="version"):
            await engine.submit(Xpool[:4], "m", strategy="early")
        # drain and stop surface the death in bounded time (pre-fix: hang)
        with pytest.raises(KeyError, match="version"):
            await asyncio.wait_for(engine.drain(), timeout=10)
        with pytest.raises(KeyError, match="version"):
            await asyncio.wait_for(engine.stop(), timeout=10)

    asyncio.run(main())


def test_cancelled_request_not_served_not_observed(ova_models, monkeypatch):
    """Satellite 2 regression: a caller-cancelled request must be reaped
    before batch formation — its rows never reach the device and it never
    lands in the latency histogram (pre-fix it was concatenated, served,
    and observed, skewing p99)."""
    import repro.launch.engine as engine_mod

    m1, _, Xpool = ova_models
    reg = ModelRegistry()
    reg.register("m", m1)
    engine = AsyncServingEngine(reg, EngineConfig(max_batch=64))
    engine.warmup("m", strategies=["early"])
    gate = _GatedServe()
    monkeypatch.setattr(engine_mod, "serve_batch", gate)

    async def main():
        async with engine:
            fA = asyncio.ensure_future(
                engine.submit(Xpool[:8], "m", strategy="early"))
            await _until_inflight(gate)            # A popped, mid-batch
            fB = asyncio.ensure_future(
                engine.submit(Xpool[:5], "m", strategy="early"))
            await asyncio.sleep(0)                 # B enqueued
            fB.cancel()                            # caller gave up (e.g.
            await asyncio.sleep(0)                 # asyncio.wait_for)
            gate.release.set()
            predA, _ = await fA
            assert predA.shape[0] == 8
            with pytest.raises(asyncio.CancelledError):
                await fB
            await engine.drain()                   # loop reaps B

    asyncio.run(main())
    st = engine.stats()
    # B's 5 rows never entered a batch; only A was delivered and observed
    assert st["queries"] == 8 and st["requests"] == 1
    assert _hist_count(engine, "serve_latency_seconds") == 1
    assert _hist_count(engine, "serve_queue_wait_seconds") == 1
    assert st["queue_depth"] == 0


def test_shed_at_max_queue_rows(ova_models, monkeypatch):
    """Admission control: with the loop mid-batch, submits past
    ``max_queue_rows`` fail fast with the typed ``EngineOverloaded`` and
    count into ``serve_shed_total``; admitted requests all deliver."""
    import repro.launch.engine as engine_mod

    m1, _, Xpool = ova_models
    reg = ModelRegistry()
    reg.register("m", m1)
    engine = AsyncServingEngine(
        reg, EngineConfig(max_batch=64, max_queue_rows=32))
    engine.warmup("m", strategies=["early"])
    gate = _GatedServe()
    monkeypatch.setattr(engine_mod, "serve_batch", gate)

    async def main():
        async with engine:
            fA = asyncio.ensure_future(
                engine.submit(Xpool[:8], "m", strategy="early"))
            await _until_inflight(gate)            # loop blocked mid-batch
            subs = [asyncio.ensure_future(
                engine.submit(Xpool[i * 8:(i + 1) * 8], "m",
                              strategy="early")) for i in range(10)]
            await asyncio.sleep(0)                 # all ten hit admission
            shed = [t for t in subs if t.done()]
            # 32-row bound admits exactly the first four 8-row requests
            assert len(shed) == 6
            for t in shed:
                with pytest.raises(EngineOverloaded, match="queue full"):
                    await t
            gate.release.set()
            await fA
            for t in subs:
                if t not in shed:
                    pred, _ = await t
                    assert pred.shape[0] == 8

    asyncio.run(main())
    st = engine.stats()
    assert st["shed"] == 6
    assert st["requests"] == 5 and st["queries"] == 40   # A + 4 admitted


def test_deadline_expiry_while_queued(ova_models, monkeypatch):
    """A queued request whose deadline expires mid-batch (the event loop
    stays live during device compute) resolves with ``DeadlineExceeded``
    and is reaped before the next batch forms — no device time burned."""
    import repro.launch.engine as engine_mod

    m1, _, Xpool = ova_models
    reg = ModelRegistry()
    reg.register("m", m1)
    engine = AsyncServingEngine(reg, EngineConfig(max_batch=64))
    engine.warmup("m", strategies=["early"])
    gate = _GatedServe()
    monkeypatch.setattr(engine_mod, "serve_batch", gate)

    async def main():
        async with engine:
            fA = asyncio.ensure_future(
                engine.submit(Xpool[:8], "m", strategy="early"))
            await _until_inflight(gate)
            fB = asyncio.ensure_future(
                engine.submit(Xpool[:5], "m", strategy="early",
                              timeout_s=0.02))
            # the timer fires while the loop is still blocked in compute —
            # liveness: deadline timers don't wait for the batch
            await asyncio.sleep(0.1)
            assert fB.done()
            with pytest.raises(DeadlineExceeded, match="expired"):
                await fB
            gate.release.set()
            await fA
            await engine.drain()

    asyncio.run(main())
    st = engine.stats()
    assert st["deadline_exceeded"] == 1
    assert st["queries"] == 8 and st["requests"] == 1    # B never served
    assert _hist_count(engine, "serve_latency_seconds") == 1


def test_pre_expired_deadline_never_enqueues(registry2):
    """``timeout_s<=0`` is already expired at submit: it resolves with
    ``DeadlineExceeded`` immediately, without enqueueing or burning a
    batch slot (the bench's deterministic deadline probe)."""
    engine = AsyncServingEngine(registry2, EngineConfig(max_batch=64))
    engine.warmup("mix", strategies=["early"])
    Xpool = np.asarray(registry2.resolve("mix").sm.Xall)

    async def main():
        async with engine:
            with pytest.raises(DeadlineExceeded):
                await engine.submit(Xpool[:4], "mix", strategy="early",
                                    timeout_s=0.0)

    asyncio.run(main())
    st = engine.stats()
    assert st["deadline_exceeded"] == 1
    assert st["queries"] == 0 and st["queue_depth"] == 0


def test_deadline_vs_hot_swap_drain(ova_models, monkeypatch):
    """Swap/drain interaction: a queued old-version request that expires
    during the drain is reaped, not served — the drain completes, the old
    version drops, and the caller sees ``DeadlineExceeded``."""
    import repro.launch.engine as engine_mod

    m1, m2, Xpool = ova_models
    reg = ModelRegistry()
    reg.register("m", m1)
    reg.register("m", m2)
    engine = AsyncServingEngine(reg, EngineConfig(max_batch=32))
    engine.warmup("m", strategies=["early"])
    gate = _GatedServe()
    monkeypatch.setattr(engine_mod, "serve_batch", gate)

    async def main():
        async with engine:
            fA = asyncio.ensure_future(
                engine.submit(Xpool[:8], "m", strategy="early"))
            await _until_inflight(gate)
            fB = asyncio.ensure_future(
                engine.submit(Xpool[:5], "m", strategy="early",
                              timeout_s=0.02))
            await asyncio.sleep(0)                 # B queued on v1
            swap = asyncio.ensure_future(engine.swap("m", 2))
            await asyncio.sleep(0.1)               # B expires mid-drain
            gate.release.set()
            await fA                               # v1's in-flight batch
            assert await asyncio.wait_for(swap, timeout=10) == 1
            with pytest.raises(DeadlineExceeded):
                await fB
            post, _ = await engine.submit(Xpool[:4], "m", strategy="early")

    asyncio.run(main())
    assert reg.versions("m") == [2]                # drained, then dropped
    assert engine.stats()["deadline_exceeded"] == 1


def test_drain_bounded_wakeups(registry2):
    """Satellite 3 regression: ``drain`` is event-driven (one wakeup per
    queue progression), not a 100%-CPU ``sleep(0)`` busy-wait — draining a
    long queue costs O(batches) loop wakeups."""
    class _CountingEvent(asyncio.Event):
        def __init__(self):
            super().__init__()
            self.waits = 0

        async def wait(self):
            self.waits += 1
            return await super().wait()

    Xpool = np.asarray(registry2.resolve("mix").sm.Xall)
    engine = AsyncServingEngine(registry2, EngineConfig(max_batch=64))
    engine.warmup("mix", strategies=["early"])
    counted = {}

    async def main():
        async with engine:
            ev = _CountingEvent()
            engine._served = ev
            subs = [asyncio.ensure_future(
                engine.submit(Xpool[i * 16:(i + 1) * 16], "mix",
                              strategy="early")) for i in range(12)]
            await asyncio.sleep(0)                 # all twelve enqueue
            await engine.drain()
            counted["waits"] = ev.waits
            for t in subs:
                await t

    asyncio.run(main())
    # 12 x 16 rows / 64-row batches = 3 batches; a few extra wakeups for
    # pops that interleave with the drain loop are fine — hundreds are not
    assert counted["waits"] <= 8, counted


def test_registry_version_coercion(ova_models):
    """Satellite 4 regression: ``register(version="2")`` must coerce once
    at entry — pre-fix the duplicate check keyed ``(name, int(v))`` but the
    insert used ``(name, v)``, so "2" and 2 silently coexisted."""
    m1, _, _ = ova_models
    reg = ModelRegistry()
    man = reg.register("m", m1, version="2")
    assert man.version == 2
    assert reg.versions("m") == [2]
    assert reg.resolve("m").version == 2
    assert reg.resolve("m", "2").version == 2
    with pytest.raises(ValueError, match="registered"):
        reg.register("m", m1, version=2)
    with pytest.raises(ValueError, match="registered"):
        reg.register("m", m1, version="2")


def test_zero_compiles_after_warmup_under_overload(registry2):
    """Acceptance: an overload burst against a bounded queue with default
    deadlines sheds/expires some requests and delivers the rest — and the
    jit cache stays exactly at its warmup mark throughout."""
    Xpool = np.asarray(registry2.resolve("mix").sm.Xall)
    engine = AsyncServingEngine(
        registry2, EngineConfig(max_batch=64, max_queue_rows=64,
                                timeout_s=0.25))
    engine.warmup("mix", strategies=["early"])
    mark = serving_cache_size()
    rng = np.random.default_rng(11)
    sizes = rng.choice([1, 4, 16, 64], size=60, p=[0.35, 0.3, 0.25, 0.1])

    async def main():
        async with engine:
            async def one(i):
                X = Xpool[rng.integers(0, Xpool.shape[0],
                                       size=int(sizes[i]))]
                return await engine.submit(X, "mix", version=1 + i % 2,
                                           strategy="early")
            return await asyncio.gather(
                *[one(i) for i in range(60)], return_exceptions=True)

    outs = asyncio.run(main())
    ok = [o for o in outs if not isinstance(o, BaseException)]
    bad = [o for o in outs if isinstance(o, BaseException)]
    assert all(isinstance(o, (EngineOverloaded, DeadlineExceeded))
               for o in bad), bad
    assert ok, "burst delivered nothing"
    assert serving_cache_size() == mark
    st = engine.stats()
    assert st["compiles_after_warmup"] == 0
    assert st["requests"] == len(ok)


def test_slo_report_schema(registry2):
    """The SLO driver's per-QPS record carries the dashboard keys."""
    from benchmarks.bench_slo import _drive

    Xpool = np.asarray(registry2.resolve("mix").sm.Xall)

    async def main():
        engine = AsyncServingEngine(registry2, EngineConfig(max_batch=64))
        engine.warmup("mix", strategies=["early"])
        async with engine:
            return await _drive(engine, Xpool, qps=500.0, n_requests=12,
                                seed=0)

    rec = asyncio.run(main())
    for key in ("offered_qps", "achieved_rps", "achieved_qps", "requests",
                "queries", "p50_ms", "p95_ms", "p99_ms", "mean_ms"):
        assert key in rec, f"SLO record missing {key}"
    assert rec["requests"] == 12
    assert np.isfinite(rec["p99_ms"]) and rec["p99_ms"] >= rec["p50_ms"] > 0
