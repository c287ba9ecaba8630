"""Distributed DC-SVM: the paper's algorithm mapped onto a device mesh via
shard_map, with a communication-efficient parallel-block conquer.

Two SPMD programs over the generalized box dual
``min 1/2 u'Qu + p'u, 0 <= u <= c`` with ``Q = (s s') ∘ K`` (C-SVC,
weighted C-SVC, epsilon-SVR — everything ``repro.core.tasks`` reduces to
the box family):

1. ``divide_step`` — clusters sharded across devices; each device solves
   its local clusters with the vmapped CD solver against *locally resident*
   Gram blocks (built once per cluster on-device; a sequential ``lax.map``
   sweep caps peak memory at one cluster's Grams when the per-device batch
   exceeds ``gram_budget``).  ZERO collectives: DC-SVM's divide step is
   embarrassingly parallel *by construction* (Lemma 1 makes the subproblems
   exactly independent), which is why the algorithm maps so well onto a pod.

2. ``conquer_step`` — parallel block minimization (CE-PBM; Hsieh, Si &
   Dhillon 2016) on the full problem.  Rows of (X, s, alpha, g) are sharded
   over the mesh axis; per communication round:

     a. every device takes its LOCAL top-B coordinates by |projected
        gradient| and solves its OWN BxB sub-QP against on-the-fly kernel
        columns — P independent block solves per round;
     b. ONE all-gather ships the P rank-B updates (feature rows, signs,
        deltas, indices) — O(P * B * d) bytes, the only bulk communication;
     c. each device applies the rank-P*B gradient update as a single skinny
        matmul ``g_l += gamma * (s_l ∘ (K(X_l, X_sel) @ (s_sel ∘ delta)))``
        (fused Pallas ``cd_column_update`` on the Pallas path; the
        ``core.colcache`` LRU serves repeat blocks without recomputing);
     d. the combination step size ``gamma = clip(-g'Δ / Δ'QΔ, 0, 1)``
        (solver.combination_step_size) keeps the P simultaneous block
        updates convergent WITHOUT backtracking — ``Δ'QΔ`` from the
        replicated gathered-block Gram, ``g'Δ`` from one scalar psum, so
        the loop condition stays uniform across devices.  Scaled steps
        that a block solve aimed AT a box bound snap onto it once within
        an O(tol) band (a ``(1-gamma)``-contraction never lands exactly,
        and the projected gradient would report the gap forever);
     e. owners write the post-snap block values into their alpha shard
        (blocks live on disjoint shards, so there are no collisions), and
        the exactly-applied step — not the proposal — is what entered the
        gradient matmul in (c), keeping the maintained gradient drift-free.

   That is P× more coordinate updates per round at the same bytes on the
   wire as a single replicated global block step.  ``mode="replicated"``
   keeps the legacy scheme — exact global Gauss-Southwell-B where all
   devices deterministically solve the SAME global top-B block — as the
   communication-round baseline (benchmarks/bench_dist.py).

``fit_distributed`` runs the multilevel pipeline device-resident: SV
detection between levels is a scatter-add on device, adaptive kmeans
sampling draws on device (``_sv_sample``), and alpha never round-trips
through NumPy until the caller asks for it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import colcache, gramop
from repro.core.kernels import (HIGHEST, Kernel, f32_matmul, gram,
                                resolve_use_pallas)
from repro.core.solver import (_solve_small_qp, combination_step_size,
                               proj_grad)
from repro.core import solver as S
from repro.core.tasks import Task, TaskDual, resolve_task
from repro.obs.trace import (ConvTrace, trace_fetch, trace_init,
                             trace_record, trace_summary)
from repro.obs.spans import span

Array = jax.Array


def _varying(tree, axis: str):
    """Mark every leaf of ``tree`` as varying over the manual ``axis``.

    ``shard_map`` checks that a loop carry (and both branches of a ``cond``)
    keep one varying-axes type; state built from constants starts out
    device-invariant and turns varying once a per-device value is written
    into it, so carries are cast up front.  Leaves that already vary pass
    through (``pcast`` refuses varying -> varying)."""
    def one(x):
        if axis in jax.typeof(x).vma:
            return x
        return lax.pcast(x, (axis,), to="varying")
    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# divide step
# ---------------------------------------------------------------------------

def divide_step(
    mesh: Mesh,
    axis: str,
    cfg,
    Xc: Array,
    sc: Array,
    pc: Array,
    cc: Array,
    ac: Array,
    mask: Array,
) -> Array:
    """Solve one level's clusters of the generalized dual, sharded over
    ``axis``.

    ``Xc``: (k, nc, d) with k a multiple of the axis size; ``sc``/``pc``/
    ``cc``/``ac``/``mask``: (k, nc) per-cluster sign vectors, linear terms,
    boxes, warm starts and pad masks.  Each device's Gram blocks are built
    and consumed locally (per-device Gram residency: no cluster data or
    kernel block ever crosses the mesh); when the local stacked Grams
    ``(k/P) * nc^2`` exceed ``cfg.gram_budget`` the vmapped solve falls back
    to a sequential ``lax.map`` sweep — one cluster Gram live at a time.
    Returns the updated (k, nc) dual variables.
    """
    tol, max_iters = cfg.tol, cfg.max_iters
    kernel, block, sweeps = cfg.kernel, cfg.block, cfg.sweeps
    use_pallas = resolve_use_pallas(cfg.use_pallas)
    compute_dtype = getattr(cfg, "compute_dtype", None)
    P_ = mesh.shape[axis]
    k, nc, _ = Xc.shape
    if k % P_ != 0:
        raise ValueError(
            f"cluster count {k} must be a multiple of the mesh axis size "
            f"{P_} (fit_distributed rounds k up for you)")
    # per-device residency decided on the BYTE budget (f32 cluster Grams)
    resident = gramop.fits_budget((k // P_) * nc * nc, cfg.gram_budget)

    def local(Xl, sl, pl, cl, al, ml):
        def one(Xi, si, pi, ci, ai, mi):
            Ki = gram(kernel, Xi, Xi, use_pallas=use_pallas,
                      compute_dtype=compute_dtype)
            mm = mi[:, None] & mi[None, :]
            Qi = (si[:, None] * si[None, :]) * jnp.where(mm, Ki, 0.0)
            Qi = Qi + jnp.where(mi, 0.0, 1.0) * jnp.eye(nc, dtype=Qi.dtype)
            ai = jnp.where(mi, ai, 0.0)
            if block > 0 and block < nc:
                res = S.solve_box_qp_block(Qi, ci, alpha0=ai, tol=tol,
                                           max_iters=max_iters, block=block,
                                           sweeps=sweeps, active_mask=mi,
                                           p=pi)
            else:
                res = S.solve_box_qp(Qi, ci, alpha0=ai, tol=tol,
                                     max_iters=max_iters, active_mask=mi,
                                     p=pi, use_pallas=use_pallas)
            return res.alpha

        if resident:
            return jax.vmap(one)(Xl, sl, pl, cl, al, ml)
        return lax.map(lambda t: one(*t), (Xl, sl, pl, cl, al, ml))

    spec = P(axis)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=spec,
    )
    # the caller's arrays may be committed to one device: place them
    return fn(*jax.device_put((Xc, sc, pc, cc, ac, mask),
                              NamedSharding(mesh, spec)))


# ---------------------------------------------------------------------------
# conquer step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConquerConfig:
    kernel: Kernel
    C: float = 1.0           # scalar box; per-coordinate via conquer_step(c=...)
    tol: float = 1e-3
    max_iters: int = 2_000   # communication-round cap
    block: int = 64          # per-device block size B
    sweeps: int = 4
    mode: str = "parallel"   # "parallel" = CE-PBM (P local blocks/round);
                             # "replicated" = legacy global top-B baseline
    use_pallas: Optional[bool] = None  # None = auto (Pallas on TPU)
    cache_cap: int = 0       # LRU slots for (P*B, n_local) Q-row slices;
                             # 0 = fully fused recompute (parallel mode only)
    grad_chunks: int = 16    # row chunks for the XLA initial-gradient matvec
    compute_dtype: Optional[str] = None  # Gram operand precision (bf16 tiles,
                             # f32 accumulation); None = exact f32 default.
                             # Cached Q-row slices store in this dtype too,
                             # doubling the rows a byte budget holds
    trace_cap: int = 0       # convergence-trace ring capacity (obs.trace);
                             # 0 = off (no ring in the program); > 0
                             # records one sample per round and
                             # conquer_step returns a 4th ConvTrace
                             # element


def conquer_step(
    mesh: Mesh,
    axis: str,
    cfg: ConquerConfig,
    X: Array,
    s: Array,
    alpha0: Array,
    p=-1.0,
    c=None,
    valid: Optional[Array] = None,
) -> Tuple[Array, Array, Array]:
    """Distributed conquer on the full generalized dual, warm-started.

    ``X``: (n, d) dual points, ``s``/``alpha0``: (n,) sign vector and warm
    start — any n: rows are padded internally with masked c=0 coordinates
    up to a multiple of the axis size and sliced back on return.  ``p`` and
    ``c`` may be scalars or (n,) vectors (weighted boxes / the SVR linear
    term); ``valid`` masks coordinates out of selection (used for padding).
    Returns ``(alpha, rounds, pg_max)`` where ``rounds`` counts
    communication rounds and ``pg_max`` is the projected-gradient residual
    recomputed AT the returned alpha (the pre-fix code reported the
    stopping value of the previous iterate).

    With ``cfg.trace_cap > 0`` one convergence sample per communication
    round — post-update pg_max / objective / free-set size (psum-reduced,
    so the ring is replicated across devices) plus the CE-PBM combination
    step γ* — is recorded on device and a 4th ``ConvTrace`` element is
    returned; fetch it with ``obs.trace.trace_fetch`` AFTER the loop.  The
    trace adds two scalar psums per round and nothing else; with
    ``trace_cap=0`` (the default) the ring is an empty carry and nothing of
    it enters the program.
    """
    if cfg.mode not in ("parallel", "replicated"):
        raise ValueError(f"unknown conquer mode {cfg.mode!r} "
                         f"(expected 'parallel' or 'replicated')")
    kernel = cfg.kernel
    use_pallas = resolve_use_pallas(cfg.use_pallas)
    compute_dtype = getattr(cfg, "compute_dtype", None)
    if use_pallas:
        from repro.kernels import ops as kops

    def pairwise(A, Bm):
        return kernel.pairwise(A, Bm, compute_dtype=compute_dtype)

    P_ = mesh.shape[axis]
    n0, d = X.shape
    dtype = X.dtype
    acc = jnp.promote_types(dtype, jnp.float32)
    s = jnp.asarray(s, dtype)
    alpha0 = jnp.asarray(alpha0, dtype)
    cvec = jnp.broadcast_to(
        jnp.asarray(cfg.C if c is None else c, dtype), (n0,))
    pvec = jnp.broadcast_to(jnp.asarray(p, dtype), (n0,))
    vvec = (jnp.ones(n0, bool) if valid is None
            else jnp.asarray(valid).astype(bool))

    # ---- pad to a multiple of the device count with inert coordinates ----
    pad = (-n0) % P_
    if pad:
        X = jnp.concatenate([X, jnp.zeros((pad, d), dtype)])
        s = jnp.concatenate([s, jnp.ones(pad, dtype)])
        alpha0 = jnp.concatenate([alpha0, jnp.zeros(pad, dtype)])
        cvec = jnp.concatenate([cvec, jnp.zeros(pad, dtype)])
        pvec = jnp.concatenate([pvec, jnp.zeros(pad, dtype)])
        vvec = jnp.concatenate([vvec, jnp.zeros(pad, bool)])
    n = n0 + pad
    n_l = n // P_
    B = max(1, min(cfg.block, n_l))
    cache_cap = 0 if cfg.mode != "parallel" else cfg.cache_cap
    if cache_cap > 0:
        cache_cap = max(cache_cap, P_ * B)   # insert needs one full block

    def cross_matvec(Xl, Z, w):
        """K(X_l, Z) @ w without materializing the (n_l, n) block."""
        if use_pallas:
            return kops.kernel_matvec(Xl, Z, w, kernel,
                                      compute_dtype=compute_dtype)
        nl = Xl.shape[0]
        chunks = max(1, min(cfg.grad_chunks, nl))
        padl = (-nl) % chunks
        Xp = jnp.pad(Xl, ((0, padl), (0, 0))) if padl else Xl
        out = lax.map(lambda Xi: f32_matmul(pairwise(Xi, Z), w),
                      Xp.reshape(chunks, -1, d))
        return out.reshape(-1)[:nl]

    def local(Xl, sl, al, pl, cl, vl):
        me = lax.axis_index(axis)
        # ---- initial local gradient: g_l = Q[l, :] @ alpha + p ------------
        Xg = lax.all_gather(Xl, axis).reshape(n, d)
        wg = lax.all_gather(sl * al, axis).reshape(n)
        g_l = (sl * cross_matvec(Xl, Xg, wg)).astype(acc) + pl.astype(acc)

        def scores_of(al, g_l):
            # pads (and caller-invalidated rows) never enter selection;
            # proj_grad alone is not enough — a c=0 coordinate still
            # reports max(g, 0) as "violation" at its (degenerate) bound
            return jnp.abs(jnp.where(vl, proj_grad(al, g_l, cl), 0.0))

        def qdelta(Xsel, ssel, w):
            """(QΔ) restricted to local rows: s_l ∘ (K(X_l, X_sel) @ w),
            w = s_sel ∘ Δ_sel — the rank-P*B skinny matmul (fused Pallas
            cd_column_update on the Pallas path)."""
            if use_pallas:
                return kops.cd_column_update(
                    Xl, sl, Xsel, w, kernel,
                    compute_dtype=compute_dtype).astype(acc)
            return (sl * f32_matmul(pairwise(Xl, Xsel), w)).astype(acc)

        def propose(al, g_l):
            """One CE-PBM proposal: local GS-B block, local BxB solve, one
            all-gather of the P rank-B updates, combination step size.

            gamma is decided BEFORE the gradient update: ``dQd`` comes from
            the replicated (P*B, P*B) selected-block Gram (O((PB)^2 d)
            flops, zero communication) and ``gTd`` from a scalar psum.
            Coordinates whose block solve targeted a box bound are SNAPPED
            onto it when the gamma-scaled step lands within eps — without
            this, gamma < 1 makes bound-bound coordinates approach their
            bound geometrically but never reach it, so their projected
            gradient (which treats any interior point as free) stays O(1)
            forever and the stopping test cannot fire.  eps is tied to
            cfg.tol so a snapped coordinate's residual bound-distance can
            never re-trip selection.  The skinny gradient matmul then uses
            the exactly-APPLIED step (all-gathered, P*B floats), so the
            maintained gradient stays drift-free through snapping.
            """
            sc_ = scores_of(al, g_l)
            _, ib = lax.top_k(sc_, B)
            Xb, sb, ab, gb, cb = Xl[ib], sl[ib], al[ib], g_l[ib], cl[ib]
            Qbb = ((sb[:, None] * sb[None, :])
                   * pairwise(Xb, Xb)).astype(acc)
            target = _solve_small_qp(Qbb, gb, ab.astype(acc), cb, cfg.sweeps)
            delta = target - ab.astype(acc)
            gath = {k2: lax.all_gather(v, axis) for k2, v in
                    dict(x=Xb, s=sb, d=delta,
                         i=ib.astype(jnp.int32)).items()}
            Xsel = gath["x"].reshape(P_ * B, d)
            ssel = gath["s"].reshape(-1)
            dsel = gath["d"].reshape(-1)
            gidx = (jnp.arange(P_, dtype=jnp.int32)[:, None] * n_l
                    + gath["i"]).reshape(-1)
            Qsel = ((ssel[:, None] * ssel[None, :])
                    * pairwise(Xsel, Xsel)).astype(acc)
            dQd = jnp.vdot(dsel, f32_matmul(Qsel, dsel), precision=HIGHEST)
            gTd = lax.psum(jnp.vdot(gb.astype(acc), delta,
                                    precision=HIGHEST), axis)
            gamma = combination_step_size(gTd, dQd)
            a_new = (ab.astype(acc) + gamma * delta).astype(dtype)
            eps = (0.1 * cfg.tol * (1.0 + cb)).astype(dtype)
            a_new = jnp.where((target <= 0.0) & (a_new <= eps),
                              jnp.zeros((), dtype), a_new)
            a_new = jnp.where((target >= cb.astype(acc))
                              & (a_new >= cb - eps), cb, a_new)
            applied = a_new.astype(acc) - ab.astype(acc)
            asel = lax.all_gather(applied, axis).reshape(-1)
            pg = lax.pmax(jnp.max(sc_), axis)
            return ib, a_new, Xsel, ssel, asel, gidx, pg, gamma

        def q_rows_local(Xsel, ssel):
            """(P*B, n_l) Q-row slices of the selected block against the
            local shard — the cache-refill unit."""
            if use_pallas:
                return kops.q_rows(Xl, sl, Xsel, ssel, kernel,
                                   compute_dtype=compute_dtype).astype(acc)
            return ((ssel[:, None] * sl[None, :])
                    * pairwise(Xsel, Xl)).astype(acc)

        def record_round(tr, al, g_l, pg, gamma=None, cache_hits=None):
            """One post-update sample per round; the psum-reduced columns
            make every device's ring identical, so the caller reads shard 0."""
            alc = al.astype(acc)
            obj = lax.psum(0.5 * jnp.vdot(alc, g_l, precision=HIGHEST)
                           + 0.5 * jnp.vdot(pl.astype(acc), alc,
                                            precision=HIGHEST), axis)
            nfree = lax.psum(jnp.sum(((al > 0.0) & (al < cl) & vl)
                                     .astype(jnp.int32)), axis)
            return _varying(trace_record(tr, pg_max=pg, objective=obj,
                                         n_free=nfree, gamma=gamma,
                                         cache_hits=cache_hits), axis)

        pg0 = lax.pmax(jnp.max(scores_of(al, g_l)), axis)
        x = (al, g_l)

        if cfg.mode == "parallel" and cache_cap == 0:
            def step(al, g_l):
                ib, a_new, Xsel, ssel, asel, _, pg, gamma = propose(al, g_l)
                g_l = g_l + qdelta(Xsel, ssel, ssel * asel)
                al = al.at[ib].set(a_new)
                return al, g_l, pg, gamma

        elif cfg.mode == "parallel":
            def step(al, g_l, cache):
                ib, a_new, Xsel, ssel, asel, gidx, pg, gamma = \
                    propose(al, g_l)
                slots, hit = colcache.lookup(cache, gidx)
                served = jnp.all(hit)
                Qrows = lax.cond(
                    served,
                    lambda: cache.cols[jnp.where(hit, slots, 0)].astype(acc),
                    lambda: q_rows_local(Xsel, ssel),
                )
                cache = colcache.update(cache, gidx, Qrows, served, slots,
                                        hit)
                g_l = g_l + f32_matmul(asel, Qrows)
                al = al.at[ib].set(a_new)
                return al, g_l, cache, pg, gamma

            # cached Q-row slices store in the policy dtype: a bf16 policy
            # fits twice the rows of f32 under the same byte budget
            store = (jnp.dtype(compute_dtype) if compute_dtype is not None
                     else acc)
            x += (_varying(colcache.init(cache_cap, n, dtype=store,
                                         width=n_l), axis),)

        else:   # replicated: legacy exact global GS-B baseline
            def step(al, g_l):
                sc_ = scores_of(al, g_l)
                sb, ib = lax.top_k(sc_, B)              # local candidates
                cand = dict(sc=sb, x=Xl[ib], g=g_l[ib], a=al[ib], y=sl[ib],
                            c=cl[ib], i=ib.astype(jnp.int32))
                gath = {k2: lax.all_gather(v, axis) for k2, v in
                        cand.items()}
                flat = gath["sc"].reshape(-1)
                _, sel = lax.top_k(flat, B)             # same global top-B
                xb = gath["x"].reshape(P_ * B, d)[sel]
                gb = gath["g"].reshape(-1)[sel]
                ab = gath["a"].reshape(-1)[sel]
                yb = gath["y"].reshape(-1)[sel]
                cb = gath["c"].reshape(-1)[sel]
                owner = (sel // B).astype(jnp.int32)
                lidx = gath["i"].reshape(-1)[sel]
                Qbb = ((yb[:, None] * yb[None, :])
                       * kernel.pairwise(xb, xb)).astype(acc)
                new_ab = _solve_small_qp(Qbb, gb, ab.astype(acc), cb,
                                         cfg.sweeps)
                delta = (new_ab - ab).astype(acc)
                g_l = g_l + qdelta(xb, yb, yb * delta)
                own = owner == me
                safe_idx = jnp.where(own, lidx, 0)
                al = al.at[safe_idx].add(
                    jnp.where(own, delta, 0.0).astype(dtype))
                pg = lax.pmax(jnp.max(sc_), axis)
                # no combination step: the trace's gamma column stays NaN
                return al, g_l, pg, None

        def cond(state):
            _, it, pg, _ = state
            return (pg > cfg.tol) & (it < cfg.max_iters)

        def body(state):
            x, it, _, tr = state
            *x_new, pg, gamma = step(*x)
            x_new = tuple(x_new)
            if tr is not None:
                # the cache-hit delta is identical across devices: lookups
                # key on the replicated gidx
                hits = x_new[2].hits - x[2].hits if cache_cap > 0 else None
                tr = record_round(tr, *x_new[:2], pg, gamma, cache_hits=hits)
            return x_new, it + 1, pg, tr

        tr0 = (_varying(trace_init(cfg.trace_cap), axis)
               if cfg.trace_cap > 0 else None)
        (al, g_l, *_), rounds, _, tr = lax.while_loop(
            cond, body, (x, jnp.zeros((), jnp.int32), pg0, tr0))

        # residual at the RETURNED alpha, not the pre-update stopping value
        pg_exit = lax.pmax(jnp.max(scores_of(al, g_l)), axis)
        if tr is None:
            return al, rounds[None], pg_exit[None]
        # the ring is replicated (psum/pmax-reduced columns): ship every
        # device's copy out and let the caller read shard 0
        return al, rounds[None], pg_exit[None], tr.buf[None], tr.count[None]

    spec = P(axis)
    traced = cfg.trace_cap > 0
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=(spec, P(axis), P(axis)) + ((P(axis), P(axis)) if traced
                                              else ()),
    )
    out = fn(*jax.device_put((X, s, alpha0, pvec, cvec, vvec),
                             NamedSharding(mesh, spec)))
    alpha, rounds, pg = out[:3]
    if traced:
        return (alpha[:n0], rounds[0], jnp.max(pg),
                ConvTrace(buf=out[3][0], count=out[4][0]))
    return alpha[:n0], rounds[0], jnp.max(pg)


# ---------------------------------------------------------------------------
# full distributed DC-SVM driver
# ---------------------------------------------------------------------------

def _sv_sample(key: Array, sv_mask: Array, m: int) -> Array:
    """Device-side adaptive kmeans sample: m indices with every support
    vector first (random order) and random non-SV fill when fewer than m
    SVs exist — the static-shape, no-host-round-trip replacement for
    ``rng.choice(sv_idx)``."""
    u = jax.random.uniform(key, sv_mask.shape)
    _, idx = lax.top_k(jnp.where(sv_mask, 1.0 + u, u), m)
    return idx


def fit_distributed(
    cfg,
    mesh: Mesh,
    axis: str,
    X: Array,
    y: Optional[Array] = None,
    task: Optional[Task] = None,
    conquer_block: int = 64,
    conquer_iters: int = 5_000,
    mode: str = "parallel",
    cache_cap: int = 0,
):
    """Multilevel DC-SVM with every level's cluster solves sharded over
    ``axis`` and the final conquer running parallel block minimization.

    ``cfg`` is a core.dcsvm.DCSVMConfig; ``task`` selects the workload
    (C-SVC default, WeightedCSVC, EpsilonSVR — any single-row box-family
    task; the equality-constrained family is single-host for now).  Cluster
    counts are rounded up to a multiple of the axis size so every device
    gets equal work (balanced clusters double as straggler mitigation:
    lockstep SPMD with equal tiles); any dataset size works — the conquer
    pads internally.  The pipeline is device-resident between levels: SV
    detection is a scatter-add over ``base_index`` on device and the
    adaptive kmeans sample draws on device, so alpha never round-trips
    through NumPy.  Returns ``(alpha (n_dual,), stats list)``, alpha on the
    mesh's first device.
    """
    from repro.core.kkmeans import Partition, two_step_kernel_kmeans

    task = resolve_task(task)
    X = jnp.asarray(X)
    n = X.shape[0]
    if y is None:
        if not task.label_free:
            raise ValueError(f"task {task.name!r} requires labels y")
        y = jnp.zeros(n, X.dtype)
    y = jnp.asarray(y, X.dtype)
    td = task.build(X, y[None, :], cfg.C)
    if td.has_equality:
        raise NotImplementedError(
            f"distributed fit covers the box dual family (svc / "
            f"weighted-svc / svr); task {task.name!r} carries an equality "
            f"constraint — use core.dcsvm.fit")
    if td.n_rows != 1:
        raise ValueError("distributed fit is single-row (binary labels or "
                         f"regression); got n_rows={td.n_rows}")
    nd = td.n_dual
    base_index = np.asarray(td.base_index)
    bidx = jnp.asarray(base_index)
    s1, p1, c1 = td.S[0], td.P[0], td.Cvec[0]
    use_pallas = resolve_use_pallas(cfg.use_pallas)
    P_ = mesh.shape[axis]
    # the fit's own state (alpha, SV mass, kmeans samples) lives on ONE
    # device of the mesh and only the shard_mapped steps span it: a Pallas
    # kernel called on arrays sharded over several devices cannot be
    # partitioned, so a sharded step output must not leak into kmeans
    home = SingleDeviceSharding(mesh.devices.flat[0])
    key = jax.random.PRNGKey(cfg.seed)
    alpha = jnp.zeros(nd, X.dtype)
    sv_base = None            # (n,) on-device SV mass per base point
    stats = []

    for l in range(cfg.levels, 0, -1):
        kl = max(cfg.k ** l, P_)
        kl = -(-kl // P_) * P_          # multiple of device count
        if kl >= n // 2:
            continue
        key, sub, ksamp = jax.random.split(key, 3)
        sample_idx = None
        if cfg.adaptive and sv_base is not None:
            sample_idx = _sv_sample(ksamp, sv_base > 0, min(cfg.m, n))
        with span(f"divide/level{l}/cluster"):
            part = two_step_kernel_kmeans(cfg.kernel, X, kl, sub, m=cfg.m,
                                          iters=cfg.kmeans_iters,
                                          sample_idx=sample_idx,
                                          balanced=True,
                                          use_pallas=use_pallas,
                                          span_prefix=f"divide/level{l}")
        # expand the base partition to dual coordinates (SVR's mirrored
        # pair of a sample shares its cluster)
        dpart = part if nd == n else Partition.build(
            np.asarray(part.assign)[base_index].astype(np.int32), kl,
            part.model)
        mask = jnp.asarray(dpart.mask)
        ac = jnp.where(mask, dpart.gather(alpha), 0.0)
        with span(f"divide/level{l}/solve"):
            ac = divide_step(mesh, axis, cfg, dpart.gather(td.Xd),
                             dpart.gather(s1), dpart.gather(p1),
                             dpart.gather(c1), ac, mask)
            alpha = dpart.scatter(jax.device_put(ac, home), nd)
        # device-resident SV tracking: dual mass scatter-added per base
        # point (the box family keeps alpha >= 0, so mass > 0 <=> any SV)
        sv_base = jnp.zeros(n, X.dtype).at[bidx].add(alpha)
        stats.append(dict(level=l, clusters=kl,
                          n_sv=jnp.sum(sv_base > 0),
                          balance_redirected=part.redirected))

    trace_cap = getattr(cfg, "trace", None) or 0
    ccfg = ConquerConfig(kernel=cfg.kernel, C=cfg.C, tol=cfg.tol,
                         max_iters=conquer_iters, block=conquer_block,
                         sweeps=cfg.sweeps, mode=mode,
                         use_pallas=cfg.use_pallas, cache_cap=cache_cap,
                         compute_dtype=getattr(cfg, "compute_dtype", None),
                         trace_cap=trace_cap)
    with span("conquer/distributed"):
        out = conquer_step(mesh, axis, ccfg, td.Xd, s1, alpha, p=p1, c=c1)
        alpha, rounds, pg = jax.device_put(out[:3], home)
    sv_base = jnp.zeros(n, X.dtype).at[bidx].add(alpha)
    st0 = dict(level=0, rounds=rounds, pg_max=pg,
               n_sv=jnp.sum(sv_base > 0))
    if trace_cap > 0:
        # the single sanctioned device->host fetch of the round trace,
        # alongside the exit-time counter sync below
        st0["trace"] = trace_fetch(out[3])
        st0["trace_summary"] = trace_summary(st0["trace"])
    stats.append(st0)
    return alpha, _finalize_stats(stats)


def _finalize_stats(stats):
    """One host sync at exit: convert the accumulated device scalars."""
    out = []
    for st in stats:
        fin = {}
        for k2, v in st.items():
            if isinstance(v, jax.Array):
                v = v.item()
                v = int(v) if float(v).is_integer() else float(v)
            fin[k2] = v
        out.append(fin)
    return out


def fit_distributed_model(
    cfg,
    mesh: Mesh,
    axis: str,
    X: Array,
    y: Optional[Array] = None,
    task: Optional[Task] = None,
    **kw,
):
    """``fit_distributed`` wrapped into a ``DCSVMModel`` (collapsed beta
    over the base points), so distributed training feeds the same
    prediction / serving path as the single-host driver."""
    from repro.core.dcsvm import DCSVMModel

    task = resolve_task(task)
    X = jnp.asarray(X)
    if y is None:
        y = jnp.zeros(X.shape[0], X.dtype)
    y = jnp.asarray(y, X.dtype)
    alpha, stats = fit_distributed(cfg, mesh, axis, X, y, task=task, **kw)
    td = task.build(X, y[None, :], cfg.C)
    beta = td.collapse(alpha[None, :])[0]
    return DCSVMModel(cfg, X, y, alpha, None, False, stats, task=task,
                      beta=beta)
