"""DC-SVM: multilevel divide-and-conquer kernel machines (paper Algorithm 1).

The driver is parameterized by a ``repro.core.tasks.Task`` reducing the
workload (C-SVC, weighted C-SVC, epsilon-SVR) to one generalized dual
``min 1/2 u'Qu + p'u, 0 <= u <= c`` with ``Q = (s s') ∘ K`` — clustering
stays label-free on the base points and is expanded to the task's dual
coordinates, so one partition serves every task (DESIGN.md §7).

Level l (= levels .. 1): partition all n points into k^l balanced clusters by
two-step kernel kmeans (sampling from the lower level's support vectors when
``adaptive`` — Theorem 3), then solve the k^l independent sub-QPs warm-started
from the lower level's alpha.  All clusters of one level are solved in a
single vmapped CD call (or a lax.map sweep when the per-level Gram budget is
exceeded).

Level 0: optional refine pass on the level-1 support vectors, then the full
problem — warm-started greedy CD (Theorem 1 says the warm start is within
C^2 D(pi)/sigma_n of alpha*, so few iterations are needed; Theorem 2 says the
SV pattern is largely correct already, so the greedy selection rarely touches
non-SVs).

``early_stop_level = l`` stops after level l and returns an early-prediction
model (paper eq. 11): route a query to its nearest cluster, score with that
cluster's local model only.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.kernels import (DEFAULT_GRAM_BUDGET, HIGHEST, Kernel,
                                f32_matmul, gram, gram_matvec,
                                resolve_use_pallas)
from repro.core.kkmeans import Partition, two_step_kernel_kmeans
from repro.core import gramop
from repro.core import solver as S
from repro.core.tasks import CSVC, Task, TaskDual, resolve_task
from repro.obs.spans import span
from repro.obs.trace import trace_fetch, trace_init, trace_summary

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DCSVMConfig:
    kernel: Kernel = Kernel("rbf", gamma=1.0)
    C: float = 1.0
    k: int = 4                     # branching factor (paper: 4)
    levels: int = 4                # l_max (paper: 4 => 256 bottom clusters)
    m: int = 1000                  # kmeans sample size (paper: 1000)
    kmeans_iters: int = 20
    tol: float = 1e-3              # projected-gradient stopping tolerance
    max_iters: int = 30_000        # per-(sub)problem CD iteration cap
    block: int = 0                 # 0 = paper-faithful 1-coordinate CD; >0 = block CD
    sweeps: int = 4                # inner sweeps for block CD
    eq_block_size: int = 1         # equality-family rank-2B block: B maximal-
                                   # violating pairs per outer iteration
                                   # (solve_eq_qp_block / blocked matvec);
                                   # <= 1 falls back to the rank-2 pairwise
                                   # engine (solve_eq_qp)
    adaptive: bool = True          # sample kmeans points from lower-level SVs
    refine: bool = True            # refine pass on level-1 SVs before final solve
    balanced: bool = True
    use_pallas: Optional[bool] = None  # None = auto (Pallas on TPU, XLA elsewhere)
    early_stop_level: int = 0      # 0 = exact solve; l >= 1 = stop after level l
    gram_budget: int = DEFAULT_GRAM_BUDGET  # BYTE budget for a level's stacked
                                   # cluster Grams / caches / spill panels
                                   # (2**29 B == the historical 2**27 f32 slots,
                                   # so default residency decisions are
                                   # unchanged)
    compute_dtype: Optional[str] = None  # Gram matmul-operand precision, e.g.
                                   # "bfloat16" (f32 accumulation, flash-
                                   # attention idiom).  None = the f32 default:
                                   # bit-identical to the pre-policy paths
    host_spill: bool = False       # level 0 out-of-core: kernel-row panels
                                   # spilled to host RAM, device LRU +
                                   # double-buffered prefetch (core.gramop)
    gram_dedup: bool = True        # base-indexed Gram view for tasks with
                                   # duplicated dual rows (SVR): kernel rows
                                   # computed/cached on the n base points,
                                   # signs expanded exactly at read (~4x fewer
                                   # cluster kernel evals, 2x cache rows)
    full_gram_threshold: int = 16384   # above this, level 0 uses the matvec solver
    col_cache_cap: int = 0         # kernel-column LRU slots for the matvec solver.
                                   # 0 (default) = fully fused recompute path; opt
                                   # in by sizing it >= the expected active set
                                   # (~#SV) — block serving is all-or-nothing, so
                                   # an undersized cache pays its (cap, n) memory
                                   # for ~zero hits (DESIGN.md §2)
    shrink_rounds: int = 3
    seed: int = 0
    trace: Optional[int] = None    # convergence-trace ring capacity for the
                                   # level-0 solve: keep the LAST ``trace``
                                   # per-iteration samples (pg_max, objective,
                                   # n_free, cache hits) in a device-resident
                                   # ring, fetched ONCE at fit exit into
                                   # level_stats.  None = no trace state in
                                   # any solver loop; the jaxpr is
                                   # bit-identical to the untraced build
                                   # (same static-gate contract as
                                   # compute_dtype=None; DESIGN.md §13)


@dataclasses.dataclass
class DCSVMModel:
    config: DCSVMConfig
    X: Array                       # base training points (n, d)
    y: Array                       # labels in {-1, +1} (SVR: real targets)
    alpha: Array                   # dual solution over the task's dual
                                   # coordinates (n for SVC, 2n for SVR)
    partition: Optional[Partition] # base-point partition at the stopping
                                   # level (early prediction / serving)
    is_early: bool
    level_stats: List[Dict[str, Any]]
    task: Task = dataclasses.field(default_factory=CSVC)
    beta: Optional[Array] = None   # collapsed decision coefficients (n,):
                                   # f(x) = sum_i beta_i K(x_i, x)
    rho: Optional[float] = None    # decision offset (equality-constrained
                                   # tasks: f(x) = sum_i beta_i K(x_i,x) - rho)
    rho_clusters: Optional[Array] = None   # (k,) per-cluster offsets of an
                                   # early-stopped equality model: each local
                                   # sub-QP carries its own multiplier, so
                                   # eq.-11 routing subtracts the assigned
                                   # cluster's rho_c, not the global rho

    @property
    def weights(self) -> Array:
        """Decision coefficients beta over the base points; models built
        before the task refactor (beta=None) fall back to the hinge form
        ``y ∘ alpha`` (identical for classification)."""
        return self.beta if self.beta is not None else self.alpha * self.y

    @property
    def sv_index(self) -> np.ndarray:
        return np.nonzero(np.asarray(self.weights) != 0)[0]


# ---------------------------------------------------------------------------
# per-level solve: all clusters at once
# ---------------------------------------------------------------------------

def _map_classes(fn, args, fits_budget: bool):
    """Apply ``fn`` over the leading class axis of ``args``: vmapped when the
    batched per-class intermediates fit the Gram budget, otherwise a
    sequential ``lax.map`` sweep (one class's Q live at a time)."""
    if fits_budget:
        return jax.vmap(fn)(*args)
    return jax.lax.map(lambda t: fn(*t), args)


def _split_eq_targets(Ac: Array, Cc: Array, mask: Array, Gc: Array,
                      d_total: Array, n_groups: int) -> Array:
    """Proportional split of the global equality target(s) over clusters.

    ``Ac``/``Cc``/``Gc``: (k, n_rows, nc) gathered equality coefficients,
    boxes, and constraint-group ids, ``mask``: (k, nc), ``d_total``:
    (n_rows, n_groups).  Per group g, each cluster's sub-target ``d_c,g``
    sits at the same relative position inside the cluster's attainable
    interval [lo_c, hi_c] = [sum_{a<0} a c, sum_{a>0} a c] (over the
    cluster's group-g members) as ``d_g`` sits inside the global one — so
    every sub-QP is feasible and the sub-targets sum exactly to ``d_g``
    (the concatenated cluster solutions are a feasible global warm start);
    a cluster with no group-g members gets ``d_c,g = 0``.  For the
    all-positive ``a`` of the shipping tasks this is the
    capacity-proportional split d_c = d * cap_c/cap per group.  Returns
    (k, n_rows, n_groups).
    """
    m = mask[:, None, :]
    out = []
    for g in range(n_groups):
        contrib = jnp.where(m & (Gc == g), Ac * Cc, 0.0)
        hi_c = jnp.sum(jnp.maximum(contrib, 0.0), axis=-1)     # (k, n_rows)
        lo_c = jnp.sum(jnp.minimum(contrib, 0.0), axis=-1)
        lo = jnp.sum(lo_c, axis=0)                             # (n_rows,)
        hi = jnp.sum(hi_c, axis=0)
        span = jnp.maximum(hi - lo, 1e-12)
        frac = (jnp.clip(d_total[:, g], lo, hi) - lo) / span
        out.append(lo_c + frac[None, :] * (hi_c - lo_c))
    return jnp.stack(out, axis=-1)


def _solve_clusters(
    cfg: DCSVMConfig, Xc: Array, sc: Array, pc: Array, cc: Array, ac: Array,
    mask: Array, use_pallas: bool = False,
    aeq: Optional[Array] = None, geq: Optional[Array] = None,
    deq: Optional[Array] = None, n_groups: int = 1,
    Xcb: Optional[Array] = None, lbc: Optional[Array] = None,
) -> Array:
    """Solve the independent generalized sub-QPs of one level.
    Xc: (k, nc, d), mask: (k, nc); sc/pc/cc/ac are class-stacked
    (k, n_rows, nc) sign vectors, linear terms, per-coordinate boxes and
    warm-start duals — binary is one row.  The Gram is task- and
    label-independent, so one Gram per cluster serves every row and all
    k * n_rows sub-QPs run in a single vmapped CD call.

    ``aeq``/``geq``/``deq`` (equality family): (k, n_rows, nc) coefficients
    and group ids plus the (k, n_rows, n_groups) per-cluster targets from
    ``_split_eq_targets`` — each sub-QP keeps its own hyperplane(s)
    ``a'u_c = d_c,g`` via the pairwise (``eq_block_size <= 1``) or rank-2B
    blocked engine (warm starts are projected feasible inside the
    solver)."""
    k, nc, _ = Xc.shape
    n_cls = sc.shape[1]
    has_eq = aeq is not None
    dedup = Xcb is not None

    def one(Xi, Si, Pi, Ci, Ai, mi, *rest):
        if dedup:
            # base-indexed view: the cluster's kernel evaluations run on its
            # nb unique base points (nc = 2 nb for SVR's mirrored dual), and
            # the dual-coordinate Gram is a gather — the same dot products,
            # so bit-identical to the direct (nc, nc) Gram at 1/4 the evals
            Xbi, lbi = rest[0], rest[1]
            rest = rest[2:]
            Kb = gram(cfg.kernel, Xbi, Xbi, use_pallas=use_pallas,
                      compute_dtype=cfg.compute_dtype)
            Ki = Kb[lbi][:, lbi]
        else:
            Ki = gram(cfg.kernel, Xi, Xi, use_pallas=use_pallas,
                      compute_dtype=cfg.compute_dtype)
        eq = rest
        # zero pad rows/cols so pad slots cannot leak into real gradients
        mm = mi[:, None] & mi[None, :]
        Kz = jnp.where(mm, Ki, 0.0)
        eye_pad = jnp.where(mi, 0.0, 1.0) * jnp.eye(nc, dtype=Ki.dtype)

        def per_class(si, pi, ci, ai, *eqi):
            Qi = (si[:, None] * si[None, :]) * Kz + eye_pad
            ai = jnp.where(mi, ai, 0.0)
            if has_eq:
                aqi, gqi, dqi = eqi
                eq_kw = dict(alpha0=ai, tol=cfg.tol, max_iters=cfg.max_iters,
                             active_mask=mi, p=pi, gid=gqi,
                             n_groups=n_groups)
                cb = jnp.where(mi, ci, 0.0)
                ab = jnp.where(mi, aqi, 0.0)
                if cfg.eq_block_size > 1:
                    res = S.solve_eq_qp_block(
                        Qi, cb, ab, dqi, block=cfg.eq_block_size,
                        sweeps=cfg.sweeps, **eq_kw,
                    )
                else:
                    res = S.solve_eq_qp(Qi, cb, ab, dqi, **eq_kw)
            elif cfg.block > 0 and cfg.block < nc:
                res = S.solve_box_qp_block(
                    Qi, ci, alpha0=ai, tol=cfg.tol, max_iters=cfg.max_iters,
                    block=cfg.block, sweeps=cfg.sweeps, active_mask=mi, p=pi,
                )
            else:
                res = S.solve_box_qp(
                    Qi, ci, alpha0=ai, tol=cfg.tol, max_iters=cfg.max_iters,
                    active_mask=mi, p=pi,
                )
            return res.alpha

        return jax.vmap(per_class)(Si, Pi, Ci, Ai, *eq)      # (n_cls, nc)

    args = (Xc, sc, pc, cc, ac, mask) \
        + ((Xcb, lbc) if dedup else ()) \
        + ((aeq, geq, deq) if has_eq else ())
    # sequential sweep bounds peak memory at one cluster's Grams
    return _map_classes(one, args,
                        gramop.fits_budget(k * n_cls * nc * nc,
                                           cfg.gram_budget))


def _solve_subset(cfg: DCSVMConfig, td: TaskDual, alpha: Array, idx: Array,
                  use_pallas: bool = False) -> Array:
    """Refine pass: solve the sub-QP restricted to ``idx`` (level-1 SVs,
    dual coordinates).

    ``alpha`` is class-stacked (n_rows, n_dual); the subset Gram is shared
    across rows (per-row Q batches fall back to a sequential sweep when
    they would blow the Gram budget)."""
    Xs = td.Xd[idx]
    Ks = gram(cfg.kernel, Xs, Xs, use_pallas=use_pallas,
              compute_dtype=cfg.compute_dtype)
    ss, ps, cs, as_ = td.S[:, idx], td.P[:, idx], td.Cvec[:, idx], alpha[:, idx]
    fits = gramop.fits_budget(td.S.shape[0] * Xs.shape[0] ** 2,
                              cfg.gram_budget)

    if td.has_equality:
        # per-group sub-targets: the full targets minus the frozen
        # complement's a'u (the complement is the non-SV set, i.e. u = 0,
        # so d_sub == d — computed explicitly to stay correct for any idx)
        G = td.n_groups
        gids = td.group_ids
        oh = gids[..., None] == jnp.arange(G)            # (n_rows, nd, G)
        au = (td.A * alpha)[..., None] * oh
        ds = td.Deq - jnp.sum(au, axis=1) + jnp.sum(au[:, idx], axis=1)

        def per_class_eq(si, pi, ci, ai, aqi, gqi, dqi):
            Qs = (si[:, None] * si[None, :]) * Ks
            eq_kw = dict(alpha0=ai, tol=cfg.tol, max_iters=cfg.max_iters,
                         p=pi, gid=gqi, n_groups=G)
            if cfg.eq_block_size > 1:
                res = S.solve_eq_qp_block(Qs, ci, aqi, dqi,
                                          block=cfg.eq_block_size,
                                          sweeps=cfg.sweeps, **eq_kw)
            else:
                res = S.solve_eq_qp(Qs, ci, aqi, dqi, **eq_kw)
            return res.alpha

        new = _map_classes(per_class_eq,
                           (ss, ps, cs, as_, td.A[:, idx], gids[:, idx], ds),
                           fits)
        return alpha.at[:, idx].set(new)

    def per_class(si, pi, ci, ai):
        Qs = (si[:, None] * si[None, :]) * Ks
        if cfg.block > 0:
            res = S.solve_box_qp_block(
                Qs, ci, alpha0=ai, tol=cfg.tol, max_iters=cfg.max_iters,
                block=min(cfg.block, Qs.shape[0]), sweeps=cfg.sweeps, p=pi,
            )
        else:
            res = S.solve_box_qp(Qs, ci, alpha0=ai, tol=cfg.tol,
                                 max_iters=cfg.max_iters, p=pi)
        return res.alpha

    new = _map_classes(per_class, (ss, ps, cs, as_), fits)
    return alpha.at[:, idx].set(new)


def _stack_results(results: List[S.SolveResult]) -> S.SolveResult:
    """Stack per-class SolveResults along a new leading axis, field-wise.
    ``None`` fields (no cache, no trace) stay ``None``; pytree fields
    (ConvTrace) are stacked leaf-wise."""
    def stack_field(f):
        vals = [getattr(r, f) for r in results]
        if any(v is None for v in vals):
            return None
        return jax.tree.map(lambda *vs: jnp.stack(vs), *vals)
    return S.SolveResult(*(stack_field(f) for f in S.SolveResult._fields))


def _level0_dense(cfg: DCSVMConfig, td: TaskDual) -> bool:
    """Whether level 0 solves against a dense Gram; otherwise it runs the
    operator engines (matvec or, for the box family, host spill).
    ``host_spill`` routes the box family out-of-core even under the dense
    threshold (the flag's meaning is "never materialize the level-0 Gram");
    equality tasks stay on their dense/matvec engines."""
    spill = cfg.host_spill and not td.has_equality
    return td.n_dual <= cfg.full_gram_threshold and not spill


def _level0_subqp(cfg: DCSVMConfig, td: TaskDual, use_pallas: bool) -> str:
    """The B×B sub-QP sweep the level-0 solve runs: ``"pallas"`` where the
    box family's operator engines run on the Pallas backend (the
    ``small_qp_sweep`` kernel), ``"xla"`` elsewhere."""
    operator = not _level0_dense(cfg, td) and not td.has_equality
    return "pallas" if operator and use_pallas else "xla"


def _solve_full(cfg: DCSVMConfig, td: TaskDual, alpha: Array,
                use_pallas: bool = False):
    """Top-level (level 0) solve on the whole generalized dual, warm-started.

    ``alpha`` is class-stacked (n_rows, n_dual): the dense path shares one
    Gram across all rows and solves the row QPs in a single vmapped call —
    unless the n_rows (n, n) Q batch would blow the Gram budget, in which
    case rows run as a sequential sweep (one Q live at a time); the matvec
    path vmaps the matvec solver over the class axis (the per-row cache
    budget is split accordingly)."""
    n = td.n_dual
    n_cls = td.S.shape[0]

    def _tr():
        # fresh per-class ring; created INSIDE the per-class closures so the
        # class vmap stacks it to (n_cls, cap, NCOLS) / (n_cls,)
        return trace_init(cfg.trace) if cfg.trace else None

    dedup = cfg.gram_dedup and td.n_base != n and not td.has_equality
    spill = cfg.host_spill and not td.has_equality
    if _level0_dense(cfg, td):
        if dedup:
            # base-indexed dense Gram: n_base^2 kernel evals instead of
            # n_dual^2, gathered to dual coordinates (bit-identical values)
            Xb, bidx = td.base_view()
            K = gram(cfg.kernel, Xb, Xb, use_pallas=use_pallas,
                     compute_dtype=cfg.compute_dtype)[bidx][:, bidx]
        else:
            K = gram(cfg.kernel, td.Xd, td.Xd, use_pallas=use_pallas,
                     compute_dtype=cfg.compute_dtype)

        if td.has_equality:
            def per_class_eq(si, pi, ci, ai, aqi, gqi, dqi):
                Q = (si[:, None] * si[None, :]) * K
                return S.solve_eq_qp_shrink(
                    Q, ci, aqi, dqi, alpha0=ai, tol=cfg.tol,
                    max_iters=cfg.max_iters, rounds=cfg.shrink_rounds, p=pi,
                    block=cfg.eq_block_size, sweeps=cfg.sweeps, gid=gqi,
                    n_groups=td.n_groups, trace=_tr(),
                )

            return _map_classes(
                per_class_eq,
                (td.S, td.P, td.Cvec, alpha, td.A, td.group_ids, td.Deq),
                gramop.fits_budget(n_cls * n * n, cfg.gram_budget))

        def per_class(si, pi, ci, ai):
            Q = (si[:, None] * si[None, :]) * K
            return S.solve_with_shrinking(
                Q, ci, alpha0=ai, tol=cfg.tol, max_iters=cfg.max_iters,
                rounds=cfg.shrink_rounds, block=cfg.block, p=pi,
                trace=_tr(),
            )

        return _map_classes(per_class, (td.S, td.P, td.Cvec, alpha),
                            gramop.fits_budget(n_cls * n * n, cfg.gram_budget))

    if td.has_equality:
        def per_class_eq_mv(si, pi, ci, ai, aqi, gqi, dqi):
            return S.solve_eq_qp_matvec(
                td.Xd, si, cfg.kernel, ci, aqi, dqi, alpha0=ai, tol=cfg.tol,
                max_iters=cfg.max_iters, use_pallas=use_pallas, p=pi,
                block=cfg.eq_block_size, sweeps=cfg.sweeps, gid=gqi,
                n_groups=td.n_groups, compute_dtype=cfg.compute_dtype,
                trace=_tr(),
            )

        return jax.vmap(per_class_eq_mv)(td.S, td.P, td.Cvec, alpha,
                                         td.A, td.group_ids, td.Deq)

    Xb, bidx = td.base_view() if dedup else (None, None)

    if spill:
        # out-of-core level 0: per class, raw kernel-row panels spilled to
        # host RAM with a device panel LRU (core.gramop) — gram_budget is
        # the DEVICE byte budget; Gram size is bounded by host memory
        results = []
        for r in range(td.S.shape[0]):
            op = gramop.GramOperator(
                Xd=td.Xd, s=td.S[r], Xb=Xb, bidx=bidx, kernel=cfg.kernel,
                use_pallas=use_pallas, compute_dtype=cfg.compute_dtype,
                budget_bytes=cfg.gram_budget)
            results.append(gramop.solve_box_qp_spill(
                op, td.Cvec[r], alpha0=alpha[r], tol=cfg.tol,
                max_iters=cfg.max_iters, block=max(cfg.block, 64),
                sweeps=cfg.sweeps, p=td.P[r],
                device_budget_bytes=cfg.gram_budget // max(n_cls, 1),
                trace=_tr()))
        return _stack_results(results)

    # the (cap, kwidth) cache buffer(s) count against the same BYTE budget
    # as the stacked cluster Grams; bf16 storage fits twice the f32 rows
    store = jnp.dtype(cfg.compute_dtype or jnp.float32).itemsize
    kwidth = td.n_base if dedup else n
    cache_cap = min(cfg.col_cache_cap, n,
                    cfg.gram_budget // max(kwidth * n_cls * store, 1))

    def per_class_mv(si, pi, ci, ai):
        return S.solve_box_qp_matvec(
            td.Xd, si, cfg.kernel, ci, alpha0=ai, tol=cfg.tol,
            max_iters=cfg.max_iters, block=max(cfg.block, 64), sweeps=cfg.sweeps,
            use_pallas=use_pallas, cache_cap=cache_cap, p=pi,
            compute_dtype=cfg.compute_dtype, Xbase=Xb, base_index=bidx,
            trace=_tr(),
        )

    return jax.vmap(per_class_mv)(td.S, td.P, td.Cvec, alpha)


# ---------------------------------------------------------------------------
# Algorithm 1
# ---------------------------------------------------------------------------

def _fit_algorithm1(
    cfg: DCSVMConfig,
    X: Array,
    td: TaskDual,
    callback: Optional[Callable[[int, Array, Dict[str, Any]], None]] = None,
):
    """Shared Algorithm-1 driver for every task (binary / one-vs-all C-SVC,
    weighted C-SVC, epsilon-SVR).

    ``td`` is the task's generalized dual (``repro.core.tasks``): class-
    stacked (n_rows, n_dual) sign/linear/box vectors over the dual points
    ``td.Xd`` (binary = one row).  The divide step is task- and label-
    independent — kernel kmeans clusters the n *base* points, and the base
    partition is expanded to dual coordinates through ``td.base_index``, so
    one partition serves every task/row and SVR's two mirrored coordinates
    of a sample always share a cluster.  All n_rows * k^l sub-QPs of a
    level run in a single vmapped CD call (``_solve_clusters``).  Returns
    ``(alpha (n_rows, n_dual), base partition, stats, is_early)``; the
    callback receives the class-stacked dual alpha.
    """
    n = X.shape[0]
    nd = td.n_dual
    base_index = np.asarray(td.base_index)
    use_pallas = resolve_use_pallas(cfg.use_pallas)
    key = jax.random.PRNGKey(cfg.seed)
    alpha = jnp.zeros(td.S.shape, X.dtype)
    sv_idx: Optional[np.ndarray] = None     # dual coordinates with alpha > 0
    sv_base: Optional[np.ndarray] = None    # their (unique) base points
    stats: List[Dict[str, Any]] = []
    partition: Optional[Partition] = None
    rng = np.random.default_rng(cfg.seed)

    for l in range(cfg.levels, 0, -1):
        kl = cfg.k ** l
        if kl >= n // 2:   # degenerate level (clusters of ~1 point): skip
            continue
        t0 = time.perf_counter()
        key, sub = jax.random.split(key)
        sample_idx = None
        if cfg.adaptive and sv_base is not None and len(sv_base) > kl:
            take = min(cfg.m, len(sv_base))
            sample_idx = rng.choice(sv_base, size=take, replace=False)
        with span(f"divide/level{l}/cluster"):
            partition = two_step_kernel_kmeans(
                cfg.kernel, X, kl, sub, m=cfg.m, iters=cfg.kmeans_iters,
                sample_idx=sample_idx, balanced=cfg.balanced,
                use_pallas=use_pallas, span_prefix=f"divide/level{l}",
            )
        t_cluster = time.perf_counter() - t0

        t0 = time.perf_counter()
        with span(f"interlevel/level{l}/gather"):
            # expand the base partition to dual coordinates: SVR's mirrored
            # (alpha_i, alpha*_i) pair inherits sample i's cluster
            dpart = partition if nd == n else Partition.build(
                np.asarray(partition.assign)[base_index].astype(np.int32),
                kl, partition.model)
            Xcb = lbc = None
            if cfg.gram_dedup and nd != n:
                # base-indexed cluster Grams: map each dual slot to its base
                # point's local slot inside the BASE partition's cluster
                # (the mirrored pair shares a cluster by construction), so
                # each cluster computes an (nb, nb) Gram instead of (2nb, 2nb)
                pidx, pmask = (np.asarray(partition.idx),
                               np.asarray(partition.mask))
                pos = np.zeros(n, np.int64)
                ci_, si_ = np.nonzero(pmask)
                pos[pidx[ci_, si_]] = si_
                didx = np.asarray(dpart.idx)
                lbc = jnp.asarray(
                    np.where(np.asarray(dpart.mask),
                             pos[base_index[np.maximum(didx, 0)]], 0),
                    jnp.int32)
                Xcb = partition.gather(X)
            Xc = dpart.gather(td.Xd)
            mask = jnp.asarray(dpart.mask)
            # (k, nc, n_rows) gathers -> (k, n_rows, nc) class-stacked batch
            sc = jnp.moveaxis(dpart.gather(td.S.T), -1, 1)
            pc = jnp.moveaxis(dpart.gather(td.P.T), -1, 1)
            cc = jnp.moveaxis(dpart.gather(td.Cvec.T), -1, 1)
            ac = jnp.moveaxis(dpart.gather(alpha.T), -1, 1)
            ac = jnp.where(mask[:, None, :], ac, 0.0)
            aeqc = geqc = deqc = None
            if td.has_equality:
                # split the global target(s) a'u = d_g proportionally over
                # clusters per constraint group; the pairwise/blocked
                # sub-solver projects each gathered warm start onto its own
                # hyperplane(s)
                aeqc = jnp.moveaxis(dpart.gather(td.A.T), -1, 1)
                geqc = jnp.moveaxis(dpart.gather(td.group_ids.T), -1, 1)
                deqc = _split_eq_targets(aeqc, cc, mask, geqc,
                                         jnp.asarray(td.Deq), td.n_groups)
        with span(f"divide/level{l}/solve"):
            ac = _solve_clusters(cfg, Xc, sc, pc, cc, ac, mask,
                                 use_pallas=use_pallas, aeq=aeqc, geq=geqc,
                                 deq=deqc, n_groups=max(td.n_groups, 1),
                                 Xcb=Xcb, lbc=lbc)
            alpha = dpart.scatter(jnp.moveaxis(ac, 1, -1), nd).T
            alpha.block_until_ready()
        t_train = time.perf_counter() - t0

        with span(f"interlevel/level{l}/select"):
            sv_idx = np.nonzero(np.any(np.asarray(alpha) > 0, axis=0))[0]
            sv_base = np.unique(base_index[sv_idx])
        st = dict(level=l, clusters=kl, cluster_time=t_cluster, train_time=t_train,
                  n_sv=int(len(sv_base)),
                  balance_redirected=partition.redirected)
        stats.append(st)
        if callback is not None:
            callback(l, alpha, st)
        if cfg.early_stop_level == l:
            return alpha, partition, stats, True

    # ---- level 0: refine + full solve -----------------------------------
    t0 = time.perf_counter()
    if cfg.refine and sv_idx is not None and 0 < len(sv_idx) < nd:
        with span("conquer/refine"):
            alpha = _solve_subset(cfg, td, alpha, jnp.asarray(sv_idx),
                                  use_pallas=use_pallas)
    subqp = _level0_subqp(cfg, td, use_pallas)
    with span("conquer/solve", subqp=subqp):
        res = _solve_full(cfg, td, alpha, use_pallas=use_pallas)
        alpha = res.alpha
        alpha.block_until_ready()
    with span("interlevel/level0/select"):
        sv_base0 = np.unique(
            base_index[np.any(np.asarray(alpha) > 0, axis=0)])
        st = dict(level=0, clusters=1, cluster_time=0.0,
                  train_time=time.perf_counter() - t0,
                  n_sv=int(len(sv_base0)),
                  iters=int(np.sum(np.asarray(res.iters))),
                  pg_max=float(np.max(np.asarray(res.pg_max))), subqp=subqp)
        if res.cache_hits is not None:
            hits = int(np.sum(np.asarray(res.cache_hits)))
            misses = int(np.sum(np.asarray(res.cache_misses)))
            st["cache_hits"] = hits
            st["cache_misses"] = misses
            st["cache_hit_rate"] = hits / max(hits + misses, 1)
        for name in ("cache_evictions", "spills", "spill_hits"):
            v = getattr(res, name, None)
            if v is not None:
                st[name] = int(np.sum(np.asarray(v)))
    if getattr(res, "trace", None) is not None:
        # the ONLY device->host trace transfer of the whole fit
        fetched = trace_fetch(res.trace)
        st["trace"] = fetched
        st["trace_summary"] = trace_summary(fetched)
    stats.append(st)
    if callback is not None:
        callback(0, alpha, st)
    return alpha, partition, stats, False


def _recover_rho_clusters(cfg: DCSVMConfig, td: TaskDual, task: Task,
                          alpha: Array, partition: Partition) -> Array:
    """Per-cluster decision offsets of an early-stopped model: cluster c's
    local sub-QP was solved with its own constraint(s) a'u_c = d_c,g, so
    its offset is the LOCAL multiplier combination rho_c (the global
    interval of a concatenated early solution is meaningless — the local
    levels differ by O(1)).  The offset recovery is delegated to
    ``task.recover_offset`` (single-constraint bracket midpoint for
    one-class SVM; the per-group r_+/r_- bias combination for two-
    constraint nu-SVC).  One per-cluster Gram matvec, same memory shape as
    a level solve — including the level solve's budget fallback (a
    sequential sweep when the stacked cluster Grams exceed
    ``gram_budget``).  Equality tasks keep n_dual == n_base, so the base
    partition indexes the dual coordinates directly."""
    use_pallas = resolve_use_pallas(cfg.use_pallas)
    Xc = partition.gather(td.Xd)
    mask = jnp.asarray(partition.mask)
    sc = partition.gather(td.S[0])
    pc = partition.gather(td.P[0])
    cc = partition.gather(td.Cvec[0])
    aq = partition.gather(td.A[0])
    gq = partition.gather(td.group_ids[0])
    uc = partition.gather(alpha[0])

    def one(Xi, si, pi, ci, ai, gi_, ui, mi):
        Ki = gram(cfg.kernel, Xi, Xi, use_pallas=use_pallas,
                  compute_dtype=cfg.compute_dtype)
        mm = mi[:, None] & mi[None, :]
        Kz = jnp.where(mm, Ki, 0.0)
        ui = jnp.where(mi, ui, 0.0)
        gi = si * f32_matmul(Kz, si * ui) + pi
        return task.recover_offset(ui, gi, jnp.where(mi, ci, 0.0),
                                   jnp.where(mi, ai, 0.0), gi_,
                                   active_mask=mi)

    return _map_classes(one, (Xc, sc, pc, cc, aq, gq, uc, mask),
                        gramop.fits_budget(partition.k * partition.nc ** 2,
                                           cfg.gram_budget))


def _recover_rho(cfg: DCSVMConfig, td: TaskDual, task: Task,
                 alpha: Array) -> float:
    """Decision offset rho at the returned dual (one-class SVM's equality
    multiplier; minus the bias for two-constraint nu-SVC): recomputes the
    full gradient with one kernel matvec and reads the task's combination
    of the KKT multiplier bracket(s)."""
    up = resolve_use_pallas(cfg.use_pallas)
    s = td.S[0]
    g = s * gram_matvec(cfg.kernel, td.Xd, s * alpha[0], use_pallas=up,
                        compute_dtype=cfg.compute_dtype) \
        + td.P[0]
    return float(task.recover_offset(alpha[0], g, td.Cvec[0], td.A[0],
                                     td.group_ids[0]))


def fit(
    cfg: DCSVMConfig,
    X: Array,
    y: Optional[Array] = None,
    callback: Optional[Callable[[int, Array, Dict[str, Any]], None]] = None,
    task: Optional[Task] = None,
) -> DCSVMModel:
    """Train DC-SVM on any supported task (default: C-SVC on +/-1 labels).

    ``task`` selects the workload (``tasks.CSVC`` / ``tasks.WeightedCSVC`` /
    ``tasks.EpsilonSVR`` / ``tasks.NuSVC`` / ``tasks.OneClassSVM``); for
    regression ``y`` holds real targets; for label-free tasks (one-class
    SVM) ``y`` may be omitted.  ``callback(level, alpha, stats)`` fires
    after each level (level 0 = final solve) — benchmarks use it for
    time/objective curves; ``alpha`` is the task's dual vector (2n
    coordinates for SVR).  The whole fit is the span ``fit``.
    """
    with span("fit"):
        X = jnp.asarray(X)
        task = resolve_task(task)
        if y is None:
            if not task.label_free:
                raise ValueError(f"task {task.name!r} requires labels y")
            y = jnp.zeros(X.shape[0], X.dtype)
        y = jnp.asarray(y, X.dtype)
        td = task.build(X, y[None, :], cfg.C)
        cb = None if callback is None else (
            lambda l, a, st: callback(l, a[0], st))
        alpha, partition, stats, is_early = _fit_algorithm1(cfg, X, td, cb)
        beta = td.collapse(alpha)[0]
        rho = rho_clusters = None
        if task.has_rho_offset:
            rho = _recover_rho(cfg, td, task, alpha)
            if is_early and partition is not None:
                rho_clusters = _recover_rho_clusters(cfg, td, task, alpha,
                                                     partition)
        return DCSVMModel(cfg, X, y, alpha[0], partition, is_early, stats,
                          task=task, beta=beta, rho=rho,
                          rho_clusters=rho_clusters)


def objective_value(cfg: DCSVMConfig, X: Array, y: Array, alpha: Array,
                    num_chunks: Optional[int] = None, p=-1.0) -> Array:
    """f(alpha) = 1/2 alpha' Q alpha + p' alpha on the FULL generalized dual
    (Q = (s s') ∘ K), computed without materializing Q.  ``y`` is the task's
    sign vector ``s`` over the dual points ``X``; the default ``p = -1``
    is the hinge objective.  On the Pallas path the Q @ alpha matvec streams
    through the fused ``kernel_matvec`` kernel instead of the chunked
    ``lax.map``; ``num_chunks=None`` sizes the chunking to the config's
    byte budget (chunking is bit-identical — it only partitions rows)."""
    Kv = gram_matvec(cfg.kernel, X, y * alpha, num_chunks=num_chunks,
                     use_pallas=resolve_use_pallas(cfg.use_pallas),
                     compute_dtype=cfg.compute_dtype,
                     budget_bytes=cfg.gram_budget)
    pvec = jnp.broadcast_to(jnp.asarray(p, alpha.dtype), alpha.shape)
    return (0.5 * jnp.vdot(alpha, y * Kv, precision=HIGHEST)
            + jnp.vdot(pvec, alpha, precision=HIGHEST))
