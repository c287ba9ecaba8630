"""Compiled SVM serving engine for DC-SVM models of every task.

Turns a trained ``DCSVMModel`` (binary / weighted C-SVC or epsilon-SVR) or
``MulticlassModel`` into a compacted, device-resident ``ServingModel`` and
serves batched requests through one jitted program per strategy —
regression models flow through the same route→gather→score program and
only skip the final argmax (``ServingModel.task``):

* ``exact`` — K(Xq, SV-union) @ W, argmax over classes (paper eq. 10).
* ``early`` — paper eq. 11: route each query to its nearest kernel-kmeans
  cluster and score against ONLY that cluster's packed SV block (the 1/k
  serving win).  Routing + bucketed scoring + argmax is one fused program
  (``predict.bucketed_cluster_scores``).
* ``bcm``   — precision-weighted combination of the k local models; the
  per-cluster regularized SV Grams are prefactored at export time.

Export drops every non-SV, packs the per-cluster SV blocks into a dense
(k, max_sv, d) layout with masks (zero weights on padding slots, masked
kernel columns where padding would leak — see DESIGN.md §5), and
``device_put``s the whole model once; the request loop never touches host
memory.

    PYTHONPATH=src python -m repro.launch.serve_svm --n 4000 --classes 3 \
        --strategy early --batch 256 --batches 50
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.dcsvm import DCSVMConfig, DCSVMModel
from repro.core.kernels import (HIGHEST, Kernel, f32_matmul, gram,
                                resolve_use_pallas)
from repro.core.kkmeans import KKMeansModel
from repro.core.multiclass import MulticlassModel, fit_ova
from repro.core.predict import _early_program, bucket_size, early_capacity
from repro.obs.metrics import MetricsRegistry

Array = jax.Array


class ServingModel(NamedTuple):
    """Device-resident compacted model (a pytree — passes through jit).

    Binary classifiers are exported with two weight columns (-w, +w) and
    classes (-1, +1) so the argmax request loop is identical for every
    model.  Regression (epsilon-SVR) models are exported with ONE weight
    column of collapsed beta coefficients and an EMPTY ``classes`` array —
    the ``task`` field is derived from that static shape, so the jitted
    route→gather→score program is shared and only the final argmax is
    skipped for regression.  One-class SVM models are exported with one
    beta column, a length-1 ``classes`` array (the static task marker) and
    the decision offset ``rho``: predictions are sign(score - rho), +1 =
    inlier.  Two-constraint nu-SVC (``NuSVC(with_bias=True)``) shares this
    offset-threshold path with ``rho = -b`` (the recovered bias), so its
    biased decision function round-trips through serving with no extra
    machinery.
    """

    # routing (implicit kernel-kmeans centers, empty centers masked upstream)
    Xm: Array          # (m, d)
    Wm: Array          # (m, k)
    sm: Array          # (k,)
    # early strategy: per-cluster packed SV blocks
    Xsv: Array         # (k, max_sv, d)
    Wsv: Array         # (k, max_sv, n_classes)  zero on padding
    svmask: Array      # (k, max_sv)             True on real SVs
    # exact strategy: SV union
    Xall: Array        # (ns, d)
    Wall: Array        # (ns, n_classes)
    # bcm strategy: Cholesky factor of the regularized masked SV Gram per
    # cluster (identity padding) — factored ONCE at export, so a request
    # only pays triangular solves
    Lchol: Array       # (k, max_sv, max_sv) lower-triangular
    classes: Array     # (n_classes,) — empty for regression, (1,) for ocsvm
    rho: Array = np.float32(0.0)   # decision offset (one-class SVM only)
    rho_c: Array = np.zeros((0,), np.float32)   # (k,) per-cluster offsets of
                       # an early-stopped one-class export (empty otherwise):
                       # the early strategy subtracts the routed cluster's
                       # local multiplier inside the fused program

    @property
    def k(self) -> int:
        return self.Xsv.shape[0]

    @property
    def n_classes(self) -> int:
        return self.classes.shape[0]

    @property
    def task(self) -> str:
        """"svr" | "ocsvm" | "svc" — derived from the static ``classes``
        shape so the branch is jit-safe (no host sync, no non-array pytree
        leaf): 0 classes = regression, 1 = one-class, >= 2 = classifier."""
        if self.classes.shape[0] == 0:
            return "svr"
        if self.classes.shape[0] == 1:
            return "ocsvm"
        return "svc"


def export_serving_model(model, noise: float = 1e-2,
                         max_sv_per_cluster: int = 4096,
                         with_bcm: bool = True) -> ServingModel:
    """Compact a trained model for serving: drop non-SVs, pack per-cluster
    SV blocks, prefactor the BCM Grams, device_put once.

    Clusters holding more than ``max_sv_per_cluster`` SVs are strided down
    to bound the packed block size — that makes ``early``/``bcm`` serving
    an approximation of the training-side decision (a warning is emitted);
    raise the cap for an exact round-trip.

    ``with_bcm=False`` skips building/factoring the k (max_sv, max_sv) BCM
    Grams — they are the export's dominant memory cost (k * max_sv^2
    floats), wasted if only ``exact``/``early`` will be served.
    """
    part = model.partition
    if part is None:
        raise ValueError("serving export requires a partitioned model")
    kern = model.config.kernel
    alpha = np.asarray(model.alpha)
    task = getattr(model, "task", None)
    rho = 0.0
    rho_c = np.zeros((0,), np.float32)
    model_rho_c = getattr(model, "rho_clusters", None)
    if model_rho_c is not None:
        rho_c = np.asarray(model_rho_c, np.float32)
    if task is not None and getattr(task, "has_rho_offset", False):
        # one-class: one beta column + the offset; classes has the static
        # length-1 marker shape and serve_batch thresholds score - rho at 0
        w = np.asarray(model.weights)
        W = w[:, None]
        classes = np.asarray([1.0], np.float32)
        active = w != 0
        rho = float(model.rho or 0.0)
    elif task is not None and task.is_regression:
        # regression: one beta column, no classes — serve_batch skips argmax
        w = np.asarray(model.weights)                        # collapsed beta
        W = w[:, None]                                       # (n, 1)
        classes = np.zeros((0,), np.float32)
        active = w != 0
    elif isinstance(model, DCSVMModel) or alpha.ndim == 1:
        w = np.asarray(model.weights)                        # y * alpha
        W = np.stack([-w, w], axis=1)                        # (n, 2)
        classes = np.array([-1.0, 1.0], np.float32)
        active = w != 0
    else:
        W = np.asarray(model.alpha * model.Y).T              # (n, n_classes)
        classes = np.asarray(model.classes)
        active = np.any(alpha > 0, axis=0)

    X = np.asarray(model.X)
    n_cls = W.shape[1]
    d = X.shape[1]

    sv_lists = []
    n_thinned = 0
    for c in range(part.k):
        members = part.idx[c][part.mask[c]]
        sv = members[active[members]]
        if len(sv) > max_sv_per_cluster:
            sv = sv[:: len(sv) // max_sv_per_cluster + 1]
            n_thinned += 1
        sv_lists.append(sv)
    if n_thinned:
        import warnings

        warnings.warn(
            f"{n_thinned} cluster(s) exceeded max_sv_per_cluster="
            f"{max_sv_per_cluster}; their SV blocks were subsampled, so "
            "early/bcm serving approximates the training-side decision",
            stacklevel=2)
    msv = max(1, max(len(s) for s in sv_lists))
    Xsv = np.zeros((part.k, msv, d), X.dtype)
    Wsv = np.zeros((part.k, msv, n_cls), np.float32)
    svmask = np.zeros((part.k, msv), bool)
    for c, sv in enumerate(sv_lists):
        Xsv[c, : len(sv)] = X[sv]
        Wsv[c, : len(sv)] = W[sv]
        svmask[c, : len(sv)] = True

    union = np.nonzero(active)[0]
    if len(union) == 0:
        union = np.array([0])
    Xall = X[union]
    Wall = W[union].astype(np.float32)

    # BCM: masked per-cluster Gram + noise on the real block, identity on
    # padding (padding rows of Xsv are zeros; for RBF K(x, 0) != 0, so the
    # mask — not the zero rows — is what keeps padding out of the solve)
    Xsv_j = jnp.asarray(Xsv)
    if with_bcm:
        mm = svmask[:, :, None] & svmask[:, None, :]
        Kreg = jax.vmap(lambda Xc: kern.pairwise(Xc, Xc))(Xsv_j)
        Kreg = jnp.where(jnp.asarray(mm), Kreg, 0.0)
        eye = jnp.eye(msv, dtype=Kreg.dtype)
        Kreg = Kreg + jnp.where(jnp.asarray(svmask)[:, :, None], noise, 1.0) * eye
        Lchol = jnp.linalg.cholesky(Kreg)
    else:
        Lchol = jnp.zeros((part.k, 0, 0), jnp.float32)

    sm = ServingModel(
        Xm=jnp.asarray(np.asarray(part.model.Xm)),
        Wm=jnp.asarray(np.asarray(part.model.W)),
        sm=jnp.asarray(np.asarray(part.model.s)),
        Xsv=Xsv_j, Wsv=jnp.asarray(Wsv), svmask=jnp.asarray(svmask),
        Xall=jnp.asarray(Xall), Wall=jnp.asarray(Wall),
        Lchol=Lchol, classes=jnp.asarray(classes),
        rho=jnp.asarray(rho, jnp.float32), rho_c=jnp.asarray(rho_c),
    )
    return jax.device_put(sm)


# ---------------------------------------------------------------------------
# jitted request programs (scores (nq, n_classes); argmax happens on device)
# ---------------------------------------------------------------------------

def _cluster_offsets(sm: ServingModel) -> Array:
    """(k,) decision offsets, one per cluster: the per-cluster multipliers
    rho_c of an early-stopped one-class export when present, else the
    global rho broadcast (0 for every non-ocsvm model, so applying these
    unconditionally is a uniform no-op outside the equality family)."""
    if sm.rho_c.shape[0]:
        return sm.rho_c
    return jnp.broadcast_to(jnp.asarray(sm.rho, jnp.float32), (sm.k,))


@partial(jax.jit, static_argnames=("kern", "use_pallas"))
def serve_scores_exact(sm: ServingModel, Xq: Array, kern: Kernel,
                       use_pallas: bool = False) -> Array:
    # sm.rho == 0 for non-ocsvm models; every scorer applies its own offset
    # so serve_batch never has to know which strategy already subtracted it
    K = gram(kern, Xq, sm.Xall, use_pallas=use_pallas)
    return f32_matmul(K, sm.Wall) - sm.rho


def serve_scores_early(sm: ServingModel, Xq: Array, kern: Kernel, cap: int,
                       use_pallas: bool = False) -> Array:
    """Route + bucketed SV-block scoring — the same jitted program as
    training-side early prediction (``predict._early_program``), fed the
    packed serving blocks.  The routed cluster's offset (per-cluster rho_c
    of an early-stopped one-class export, global rho otherwise) is applied
    inside the fused program."""
    route = KKMeansModel(Xm=sm.Xm, W=sm.Wm, s=sm.sm)
    return _early_program(kern, Xq, route, sm.Xsv, sm.Wsv, cap,
                          use_pallas=use_pallas,
                          offsets=_cluster_offsets(sm)[:, None])


@partial(jax.jit, static_argnames=("kern",))
def serve_scores_bcm(sm: ServingModel, Xq: Array, kern: Kernel,
                     noise: float = 1e-2) -> Array:
    diag = kern.diag(Xq)

    def per_cluster(Xc, Wc, Lc, mc, off):
        Kqs = kern.pairwise(Xq, Xc) * mc[None, :]
        # committee member c votes with ITS local decision f_c - rho_c
        f = f32_matmul(Kqs, Wc) - off                                # (nq, C)
        # Lchol was factored at export: two triangular solves per request
        sol = jax.scipy.linalg.cho_solve((Lc, True), Kqs.T)  # (s, nq)
        var = jnp.maximum(
            diag - jnp.einsum("qs,sq->q", Kqs, sol, precision=HIGHEST), noise)
        prec = jnp.where(jnp.any(mc), 1.0 / var, 0.0)        # skip empty blocks
        return f * prec[:, None], prec

    fs, ps = jax.vmap(per_cluster)(sm.Xsv, sm.Wsv, sm.Lchol, sm.svmask,
                                   _cluster_offsets(sm))
    return jnp.sum(fs, 0) / (jnp.sum(ps, 0) + 1e-12)[:, None]


def serve_batch(sm: ServingModel, Xq: Array, kern: Kernel, strategy: str,
                use_pallas: Optional[bool] = None,
                bucket: Optional[int] = None) -> Tuple[Array, Array]:
    """One batched request: returns (predictions, scores).

    Predictions are class labels (argmax over score columns) for
    classification models, raw regression values for ``task == "svr"``
    models (the single beta-score column, no argmax), and +/-1
    inlier/outlier labels for ``task == "ocsvm"`` (sign of score - rho; the
    returned scores are the offset decision values) — every branch is on a
    static shape, so each path stays one compiled program per strategy.

    ``bucket``, when given, pads the batch with zero query rows to exactly
    ``bucket`` rows before scoring and slices the results back to the real
    rows.  Everything shape-derived — the jit signature AND the early
    strategy's static buffer capacity (``early_capacity``) — then depends
    only on the bucket, so ragged request sizes sharing a bucket share ONE
    compiled program (unbucketed, every distinct batch size recompiled the
    early program through its shape-derived ``cap``).  Per-row scores are
    independent of the padding rows, so bucketed results on the real rows
    match the unbucketed ones."""
    nq = Xq.shape[0]
    if bucket is not None:
        pad = int(bucket) - nq
        if pad < 0:
            raise ValueError(f"bucket={bucket} smaller than the batch ({nq})")
        if pad:
            Xq = jnp.concatenate(
                [Xq, jnp.zeros((pad, Xq.shape[1]), Xq.dtype)])
    up = resolve_use_pallas(use_pallas)
    if strategy == "exact":
        scores = serve_scores_exact(sm, Xq, kern, use_pallas=up)
    elif strategy == "early":
        # cap derives from the (possibly padded) batch shape: with a bucket
        # it is a pure function of the bucket, keeping the jit cache warm
        cap = early_capacity(Xq.shape[0], sm.k)
        scores = serve_scores_early(sm, Xq, kern, cap, use_pallas=up)
    elif strategy == "bcm":
        if sm.Lchol.shape[1] == 0:
            raise ValueError("model was exported with with_bcm=False; "
                             "re-export to serve the bcm strategy")
        scores = serve_scores_bcm(sm, Xq, kern)
    else:
        raise ValueError(f"unknown strategy: {strategy}")
    scores = scores[:nq]
    if sm.task == "svr":
        return scores[:, 0], scores
    if sm.task == "ocsvm":
        # every scorer already applied its offset (rho / per-cluster rho_c)
        raw = scores[:, 0]
        return jnp.where(raw >= 0, 1.0, -1.0).astype(raw.dtype), raw[:, None]
    return sm.classes[jnp.argmax(scores, axis=1)], scores


def serving_cache_size() -> int:
    """Total jit-cache entries across every serving program — the compile
    counter's raw signal.  Any growth between two reads means a serving
    call compiled a fresh executable (a new batch/bucket shape, strategy,
    model signature, or capacity); the engine and the request loop read it
    around their timed regions to pin "zero recompiles after warmup"."""
    from repro.core.predict import _decision_scan

    progs = (_early_program, _decision_scan, serve_scores_exact,
             serve_scores_bcm)
    return sum(p._cache_size() for p in progs)


def run_request_loop(sm: ServingModel, kern: Kernel, strategy: str,
                     batches, use_pallas: Optional[bool] = None,
                     warmup: int = 2,
                     metrics: Optional[MetricsRegistry] = None,
                     bucketed: bool = False) -> dict:
    """Drive the jitted request program over a query stream, sync per
    response (a real serving loop), and report latency/throughput.

    ``batches`` is either a stacked (num_batches, batch, d) array (one
    static shape — the historical fixed-batch loop) or a sequence of
    (nq_i, d) arrays with RAGGED sizes; ``bucketed=True`` pads each batch
    to its power-of-two bucket (``predict.bucket_size``) so ragged sizes
    share compiled programs.

    Warmup covers EVERY distinct compiled signature (batch shape x bucket)
    appearing in the stream, not just the first batch's: with ragged
    batches, a first-shape-only warmup leaves later shapes to compile
    inside the timed region, and those multi-hundred-ms outliers corrupt
    p95/p99.  The report's ``compiles_timed`` (jit-cache growth across the
    timed loop, ``serving_cache_size``) pins the invariant: after warmup
    the timed region must serve with ZERO recompiles.

    With ``metrics``, each response latency feeds a per-strategy streaming
    histogram (``serve_latency_seconds``) and the loop maintains
    request/query counters; ``early`` additionally records the per-cluster
    route distribution and how many extra on-device overflow rounds the
    bucketed program paid (queries past ``early_capacity`` slots per
    cluster).  Routing stats are computed OUTSIDE the timed loop — the
    measured latencies stay those of the serving program alone."""
    if isinstance(batches, (list, tuple)):
        blist = [jnp.asarray(b) for b in batches]
    else:
        blist = [batches[i] for i in range(batches.shape[0])]
    sizes = [int(b.shape[0]) for b in blist]
    buckets = [bucket_size(n) if bucketed else None for n in sizes]
    uniform = len(set(sizes)) == 1

    # warm every distinct (shape, bucket) signature before timing
    distinct = {}
    for b, bk in zip(blist, buckets):
        distinct.setdefault((b.shape, bk), (b, bk))
    for _ in range(max(1, warmup)):
        for b, bk in distinct.values():
            pred, _ = serve_batch(sm, b, kern, strategy, use_pallas,
                                  bucket=bk)
            pred.block_until_ready()

    hist = (metrics.histogram("serve_latency_seconds", strategy=strategy)
            if metrics is not None else None)
    lat = []
    cache0 = serving_cache_size()
    t_all = time.perf_counter()
    for b, bk in zip(blist, buckets):
        t0 = time.perf_counter()
        pred, _ = serve_batch(sm, b, kern, strategy, use_pallas, bucket=bk)
        pred.block_until_ready()
        lat.append(time.perf_counter() - t0)
        if hist is not None:
            hist.observe(lat[-1])
    wall = time.perf_counter() - t_all
    compiles_timed = serving_cache_size() - cache0
    if metrics is not None:
        metrics.counter("serve_requests_total", strategy=strategy).inc(
            len(blist))
        metrics.counter("serve_queries_total", strategy=strategy).inc(
            sum(sizes))
        if compiles_timed:
            metrics.counter("serve_compiles_total", strategy=strategy).inc(
                compiles_timed)
        if strategy == "early":
            _record_route_metrics(sm, kern, blist, buckets, metrics,
                                  resolve_use_pallas(use_pallas))
    lat_ms = np.sort(np.asarray(lat)) * 1e3
    return {
        "strategy": strategy,
        "batch": sizes[0] if uniform else 0,   # 0 = ragged stream
        "batches": len(blist),
        "queries": int(sum(sizes)),
        "compiles_timed": int(compiles_timed),
        "qps": sum(sizes) / max(wall, 1e-9),
        "lat_ms_mean": float(lat_ms.mean()),
        "lat_ms_p50": float(np.percentile(lat_ms, 50)),
        "lat_ms_p95": float(np.percentile(lat_ms, 95)),
        "lat_ms_p99": float(np.percentile(lat_ms, 99)),
    }


def _record_route_metrics(sm: ServingModel, kern: Kernel, blist, buckets,
                          metrics: MetricsRegistry, use_pallas: bool) -> None:
    """Early-strategy routing telemetry: per-cluster query distribution and
    the number of EXTRA bucketed scoring rounds caused by per-batch cluster
    loads above ``early_capacity`` (the fused program's per-round buffer)."""
    from repro.core.kkmeans import assign_points

    route_model = KKMeansModel(Xm=sm.Xm, W=sm.Wm, s=sm.sm)
    assign, _ = assign_points(kern, route_model, jnp.concatenate(blist),
                              use_pallas=use_pallas)
    assign = np.asarray(assign)
    total = np.bincount(assign, minlength=sm.k)
    for c in range(sm.k):
        if total[c]:
            metrics.counter("serve_route_total", cluster=str(c)).inc(
                int(total[c]))
    overflow = 0
    off = 0
    for b, bk in zip(blist, buckets):
        row = assign[off: off + b.shape[0]]
        off += b.shape[0]
        if row.size == 0:
            continue
        # the program's capacity is bucket-derived when serving bucketed
        cap = early_capacity(bk if bk is not None else b.shape[0], sm.k)
        overflow += max(
            0, -(-int(np.bincount(row, minlength=sm.k).max()) // cap) - 1)
    metrics.counter("serve_early_overflow_rounds_total").inc(overflow)


def _serve_async(args, model, Xpool: np.ndarray) -> None:
    """--serve-async: register the model, warm every bucket signature, and
    drive a Poisson trace of mixed-size requests through the continuous-
    batching engine (imports are local: registry/engine import this
    module)."""
    import asyncio

    from repro.launch.engine import (
        AsyncServingEngine, DeadlineExceeded, EngineConfig, EngineOverloaded,
    )
    from repro.launch.registry import ModelRegistry

    registry = ModelRegistry()
    man = registry.register("default", model,
                            with_bcm=(args.strategy == "bcm"))
    if args.registry:
        registry.save(args.registry)
        print(f"registry manifests -> {args.registry}")
    engine = AsyncServingEngine(registry, EngineConfig(
        max_batch=args.batch,
        max_queue_rows=args.max_queue if args.max_queue > 0 else None,
        timeout_s=args.timeout_s if args.timeout_s > 0 else None))
    warm = engine.warmup(strategies=[args.strategy])
    rng = np.random.default_rng(args.seed)
    n_req = args.batches
    sizes = rng.choice([1, 4, 16, 64], size=n_req, p=[0.35, 0.3, 0.25, 0.1])
    arrivals = np.cumsum(rng.exponential(1.0 / args.qps, size=n_req))
    lats: list = []
    outcomes = {"shed": 0, "expired": 0}

    async def one(delay: float, size: int) -> None:
        await asyncio.sleep(delay)
        Xq = Xpool[rng.integers(0, Xpool.shape[0], size=size)]
        t0 = time.perf_counter()
        try:
            await engine.submit(Xq, "default", strategy=args.strategy)
        except EngineOverloaded:
            outcomes["shed"] += 1           # the in-process 429
            return
        except DeadlineExceeded:
            outcomes["expired"] += 1
            return
        lats.append(time.perf_counter() - t0)

    async def drive() -> None:
        async with engine:
            await asyncio.gather(*[
                one(float(arrivals[i]), int(sizes[i])) for i in range(n_req)])

    asyncio.run(drive())
    stats = engine.stats()
    # tails over ADMITTED-and-delivered requests only: shed/expired
    # requests fail fast by design and must not pollute the latency report
    ms = (np.asarray(lats) * 1e3 if lats else np.asarray([float("nan")]))
    print(f"async {args.strategy} v{man.version}: {n_req} requests "
          f"({int(sizes.sum())} queries) at {args.qps:.0f} offered rps | "
          f"delivered {len(lats)} shed {outcomes['shed']} "
          f"expired {outcomes['expired']} | "
          f"admitted lat ms p50 {np.percentile(ms, 50):.2f} "
          f"p95 {np.percentile(ms, 95):.2f} p99 {np.percentile(ms, 99):.2f} "
          f"| warmup compiles {warm}, after warmup "
          f"{stats['compiles_after_warmup']}")
    if args.metrics_out:
        prom = engine.metrics.dump(args.metrics_out)
        print(f"metrics -> {args.metrics_out} and {prom}", flush=True)


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.core.dcsvm import fit
    from repro.core.predict import accuracy_multiclass, f1, mse, recall
    from repro.core.tasks import EpsilonSVR, OneClassSVM
    from repro.data import (
        friedman1, gaussian_mixture_multiclass, gaussian_with_outliers,
        train_test_split,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="svc", choices=["svc", "svr", "ocsvm"])
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--classes", type=int, default=3)
    ap.add_argument("--levels", type=int, default=2)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--strategy", default="early",
                    choices=["exact", "early", "bcm"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--batches", type=int, default=50)
    ap.add_argument("--gamma", type=float, default=8.0)
    ap.add_argument("--C", type=float, default=4.0)
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--nu", type=float, default=0.1,
                    help="one-class support/outlier mass bound")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="",
                    help="dump serving metrics (latency histograms, "
                         "request/route counters) as JSON at this path plus "
                         "Prometheus text exposition next to it (.prom)")
    ap.add_argument("--serve-async", action="store_true",
                    help="serve through the asyncio continuous-batching "
                         "engine (launch/engine.py): Poisson arrivals with "
                         "mixed request sizes against the versioned "
                         "registry, instead of the fixed-batch sync loop")
    ap.add_argument("--qps", type=float, default=500.0,
                    help="offered Poisson request rate for --serve-async")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="--serve-async admission bound on queued query "
                         "rows; submits past it shed with EngineOverloaded "
                         "(0 = unbounded)")
    ap.add_argument("--timeout-s", type=float, default=0.0,
                    help="--serve-async default per-request deadline; "
                         "requests expiring in queue resolve with "
                         "DeadlineExceeded before batch formation "
                         "(0 = none)")
    ap.add_argument("--registry", default="",
                    help="write the model registry's manifests JSON here "
                         "(--serve-async)")
    args = ap.parse_args(argv)

    kern = Kernel("rbf", gamma=args.gamma)
    t0 = time.perf_counter()
    if args.task == "svr":
        X, y = friedman1(jax.random.PRNGKey(args.seed), args.n)
    elif args.task == "ocsvm":
        X, y = gaussian_with_outliers(jax.random.PRNGKey(args.seed), args.n)
    else:
        X, y = gaussian_mixture_multiclass(jax.random.PRNGKey(args.seed),
                                           args.n, n_classes=args.classes)
    Xtr, ytr, Xte, yte = train_test_split(
        jax.random.PRNGKey(args.seed + 1), X, y)
    cfg = DCSVMConfig(kernel=kern, C=args.C, k=args.k, levels=args.levels,
                      m=min(1000, Xtr.shape[0]), tol=1e-3, seed=args.seed)
    if args.task == "svr":
        model = fit(cfg, Xtr, ytr, task=EpsilonSVR(eps=args.eps))
        print(f"fit svr: {time.perf_counter()-t0:.1f}s  "
              f"n_sv={len(model.sv_index)}/{Xtr.shape[0]}")
    elif args.task == "ocsvm":
        model = fit(cfg, Xtr, task=OneClassSVM(nu=args.nu))  # label-free
        print(f"fit ocsvm: {time.perf_counter()-t0:.1f}s  "
              f"n_sv={len(model.sv_index)}/{Xtr.shape[0]}  "
              f"rho={model.rho:.4f}")
    else:
        model = fit_ova(cfg, Xtr, ytr)
        print(f"fit_ova: {time.perf_counter()-t0:.1f}s  "
              f"n_sv={len(model.sv_union)}/{Xtr.shape[0]}")

    sm = export_serving_model(model)
    pred, _ = serve_batch(sm, Xte, kern, args.strategy)
    if sm.task == "svr":
        print(f"serving mse ({args.strategy}): {mse(yte, pred):.5f}")
    elif sm.task == "ocsvm":
        print(f"serving outlier recall ({args.strategy}): "
              f"{recall(yte, pred, -1.0):.4f}  f1: {f1(yte, pred, -1.0):.4f}")
    else:
        acc = accuracy_multiclass(yte, pred)
        print(f"serving accuracy ({args.strategy}): {acc:.4f}")

    if args.serve_async:
        _serve_async(args, model, np.asarray(Xte))
        return

    rng = np.random.default_rng(args.seed)
    idx = rng.integers(0, Xte.shape[0], size=(args.batches, args.batch))
    batches = jnp.asarray(np.asarray(Xte)[idx])
    registry = MetricsRegistry() if args.metrics_out else None
    if registry is not None:
        registry.counter("serve_strategy_selected_total",
                         strategy=args.strategy).inc()
    rep = run_request_loop(sm, kern, args.strategy, batches, metrics=registry)
    print(f"{rep['strategy']}: {rep['qps']:.0f} q/s | "
          f"lat ms mean {rep['lat_ms_mean']:.2f} "
          f"p50 {rep['lat_ms_p50']:.2f} p95 {rep['lat_ms_p95']:.2f} "
          f"p99 {rep['lat_ms_p99']:.2f}")
    if registry is not None:
        prom = registry.dump(args.metrics_out)
        print(f"metrics -> {args.metrics_out} and {prom}", flush=True)


if __name__ == "__main__":
    main()
