"""QP solvers for the two generalized kernel-machine dual families.

Box family (the paper's bias-free hinge dual and its task generalizations):

    min_u  f(u) = 1/2 u' Q u + p' u     s.t.  0 <= u <= c

with per-coordinate linear term ``p`` and per-coordinate upper bound ``c``
(both broadcast from scalars).  The classic C-SVC hinge dual is the default
instantiation ``p = -1, c = C`` — C-SVC, weighted C-SVC and epsilon-SVR in
``repro.core.tasks`` reduce to this one problem with ``Q = (s s') ∘ K`` for
a task-specific sign vector ``s``.  Because the paper drops the bias term
there is no equality constraint, so single-coordinate updates are exactly
solvable in closed form:

    u_i <- clip(u_i - g_i / Q_ii, 0, c_i),      g = Q u + p.

Equality-constrained family (one-class SVM, nu-SVC — DESIGN.md §9):

    min_u  f(u) = 1/2 u' Q u + p' u     s.t.  0 <= u <= c,  a' u = d

with a nonzero coefficient vector ``a`` (possibly mixed-sign).  Single
coordinates can no longer move alone; the solver takes SMO-style *pairwise*
steps along the constraint-neutral direction ``e_i/a_i - e_j/a_j`` chosen by
the maximal-violating-pair rule, so every iterate stays on the hyperplane.

Solvers (all pure JAX, `lax` control flow, vmap-able over a leading batch of
independent subproblems — the divide step solves all clusters of one level in
a single vmapped call):

* ``solve_box_qp``        — greedy (Gauss-Southwell) CD, the paper-faithful
                            solver (LIBSVM's selection rule without bias).
* ``solve_box_qp_block``  — beyond-paper batched variant: select top-B
                            coordinates by projected gradient, solve the BxB
                            sub-QP, rank-B gradient update (MXU-friendly).
* ``solve_box_qp_matvec`` — block CD with on-the-fly kernel columns; never
                            materializes Q (top-level conquer at large n).
* ``solve_eq_qp``         — pairwise maximal-violating-pair CD on a dense Q
                            for the equality-constrained family.
* ``solve_eq_qp_block``   — rank-2B blocked variant: B maximal-violating
                            pairs per outer iteration, solved as a coupled
                            2Bx2B sub-QP with one coupling row per group
                            (MXU-shaped like ``solve_box_qp_block``).
* ``solve_eq_qp_shrink``  — LIBSVM-style outer shrinking rounds around the
                            pairwise / blocked engines.
* ``solve_eq_qp_matvec``  — the same pairwise engine with on-the-fly kernel
                            columns (fused Pallas path available); with
                            ``block > 1`` the gradient update is the fused
                            rank-2B ``cd_column_update``.

Group decomposition (``gid``/``n_groups``): the equality solvers accept a
partition of the coordinates into ``n_groups`` disjoint groups, each with
its OWN single constraint ``sum_{i in g} a_i u_i = d_g``.  Pairs are always
drawn within one group, so every constraint is preserved exactly.  This is
how the two-constraint nu-SVC dual (``e'u = nu n`` and ``y'u = 0``) is
solved: with +/-1 labels the pair decomposes into one mass constraint per
class group (DESIGN.md §10).  ``n_groups = 1`` (the default) is the plain
one-constraint family.

Stopping criterion: max |projected gradient| < tol for the box family;
``max_g (rho_lo_g - rho_hi_g) < tol`` (the maximal-violating-pair gap of
the per-group equality multiplier brackets, LIBSVM's working-set
criterion) for the equality family.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import colcache, gramop
from repro.core.kernels import (HIGHEST, Kernel, f32_matmul,
                                resolve_use_pallas)
from repro.obs.trace import ConvTrace, trace_record

Array = jax.Array


def _broadcast(v, n: int, dtype) -> Array:
    """Scalar-or-vector parameter -> (n,) vector (p and c are per-coordinate
    in the generalized dual; the scalar hinge defaults broadcast)."""
    return jnp.broadcast_to(jnp.asarray(v, dtype), (n,))


class SolveResult(NamedTuple):
    alpha: Array
    grad: Array          # g = Q a + p at the returned alpha
    iters: Array         # number of outer iterations executed
    pg_max: Array        # final max |projected gradient|
    cache_hits: Optional[Array] = None    # column-cache rows served (matvec solver)
    cache_misses: Optional[Array] = None  # column-cache rows recomputed
    cache_evictions: Optional[Array] = None  # live rows/panels displaced (LRU)
    spills: Optional[Array] = None        # panels written to the host tier
    spill_hits: Optional[Array] = None    # panels re-loaded from the host tier
    trace: Optional[ConvTrace] = None     # convergence ring buffer (obs.trace)


def objective(alpha: Array, grad: Array, p=-1.0) -> Array:
    """f(u) = 1/2 u'Qu + p'u evaluated from the maintained gradient.

    With g = Qu + p we have u'g = u'Qu + p'u, hence

        f(u) = 1/2 (u'g - p'u) + p'u = 1/2 u'g + 1/2 p'u.

    The default ``p = -1`` recovers the hinge form 1/2 a'g - 1/2 e'a.
    """
    pu = jnp.sum(jnp.asarray(p, alpha.dtype) * alpha)
    return 0.5 * jnp.vdot(alpha, grad, precision=HIGHEST) + 0.5 * pu


def _n_free(alpha: Array, cvec: Array, mask: Optional[Array] = None) -> Array:
    """Free-set size (strictly interior coordinates) for trace recording."""
    free = (alpha > 0.0) & (alpha < cvec)
    if mask is not None:
        free &= mask
    return jnp.sum(free.astype(jnp.int32))


def proj_grad(alpha: Array, grad: Array, C) -> Array:
    """Projected gradient of the box QP (the KKT residual).  ``C`` is the
    upper bound, scalar or per-coordinate."""
    at_lo = alpha <= 0.0
    at_hi = alpha >= C
    pg = jnp.where(at_lo, jnp.minimum(grad, 0.0), grad)
    pg = jnp.where(at_hi, jnp.maximum(grad, 0.0), pg)
    return pg


def kkt_residual(Q: Array, alpha: Array, C, p=-1.0) -> Array:
    g = f32_matmul(Q, alpha) + jnp.asarray(p, alpha.dtype)
    return jnp.max(jnp.abs(proj_grad(alpha, g, C)))


def combination_step_size(gTd: Array, dQd: Array) -> Array:
    """CE-PBM combined step size: backtracking-free exact line search on the
    dual quadratic (Hsieh, Si & Dhillon 2016, the distributed conquer).

    P devices simultaneously minimize their own block sub-QPs and propose
    the combined direction ``Δ = Σ_p Δ_p`` (disjoint coordinate support).
    Applying every block at full length can overshoot — each local solve
    ignores the cross-block curvature — so the combined update is
    ``α + γ Δ`` with

        γ* = argmin_γ f(α + γΔ) = -g'Δ / Δ'QΔ,   clipped to [0, 1].

    Both α and α + Δ are box-feasible and the blocks touch disjoint
    coordinates, so every γ in [0, 1] stays feasible.  Descent needs no
    backtracking loop: at the interior minimizer the decrease is
    ``-(g'Δ)² / (2 Δ'QΔ) <= 0``, and when γ* clips at 1 it is still
    ``<= -Δ'QΔ / 2``.  Each block solve only ever decreases its own
    sub-model, so ``g'Δ <= -½ Σ_p Δ_p' Q_pp Δ_p <= 0`` and the unclipped
    γ* is nonnegative; ``Δ'QΔ <= 0`` (PSD Q) only when Δ vanishes, where
    γ = 1 is a no-op.  Takes the two already-reduced scalars so the
    distributed caller can psum them instead of gathering gradients.
    """
    gamma = jnp.where(dQd > 0.0, -gTd / jnp.where(dQd > 0.0, dQd, 1.0), 1.0)
    return jnp.clip(gamma, 0.0, 1.0)


# ---------------------------------------------------------------------------
# The box family's loop: one skeleton drives every box CD engine
# ---------------------------------------------------------------------------

def _cd_loop(step, record, x, pg0, tol, max_iters, trace):
    """Run ``step`` until the stopping value is at most ``tol`` or
    ``max_iters`` iterations have run.

    ``x`` is the engine's carry, ``(alpha, g)`` plus the column cache on the
    cached matvec path; ``step(*x)`` returns the new carry followed by the
    stopping value of the iterate it improved.  ``trace`` is an optional
    carry: ``None`` has no leaves, so with tracing off the loop state
    flattens to the leaves of a loop without it and nothing of
    ``record(tr, x, x_new, pg_max)`` enters the program.  Returns
    ``(x, iters, pg_max, trace)``.
    """
    def cond(state):
        _, it, pg_max, _ = state
        return (pg_max > tol) & (it < max_iters)

    def body(state):
        x, it, _, tr = state
        *x_new, pg_max = step(*x)
        x_new = tuple(x_new)
        tr = tr if tr is None else record(tr, x, x_new, pg_max)
        return x_new, it + 1, pg_max, tr

    return lax.while_loop(cond, body, (x, 0, pg0, trace))


def _masked_record(mask, cvec, pvec):
    """Recorder of the dense box engines: (pg_max, objective, n_free) of
    the pre-update iterate, over the active coordinates."""
    def record(tr, x, _x_new, _pg_max):
        alpha, g = x
        return trace_record(tr, pg_max=jnp.max(jnp.abs(jnp.where(
                                mask, proj_grad(alpha, g, cvec), 0.0))),
                            objective=objective(alpha, g, pvec),
                            n_free=_n_free(alpha, cvec, mask))
    return record


# ---------------------------------------------------------------------------
# Greedy single-coordinate CD (paper-faithful conquer/sub-solver)
# ---------------------------------------------------------------------------

def _greedy_step(Q, cvec, diag, mask):
    """One greedy CD step on a dense ``Q``: ``step(alpha, g)`` returns the
    new ``(alpha, g)`` and max |pg| of the iterate it improved."""
    def step(alpha, g):
        pg = jnp.where(mask, proj_grad(alpha, g, cvec), 0.0)
        i = jnp.argmax(jnp.abs(pg))
        new_ai = jnp.clip(alpha[i] - g[i] / diag[i], 0.0, cvec[i])
        delta = new_ai - alpha[i]
        # stopping value computed from the *pre-update* pg (cheap, standard)
        return alpha.at[i].set(new_ai), g + delta * Q[:, i], jnp.max(jnp.abs(pg))
    return step


def _masked_pg_max(alpha, g, cvec, mask):
    return jnp.max(jnp.abs(jnp.where(mask, proj_grad(alpha, g, cvec), 0.0)))


def _greedy_cd_xla(Q, alpha, g, cvec, diag, mask, tol, max_iters):
    """The XLA loop of ``solve_box_qp`` without a trace:
    ``(alpha, g, iters, pg_max)``."""
    (alpha, g), iters, pg_max, _ = _cd_loop(
        _greedy_step(Q, cvec, diag, mask), None, (alpha, g),
        _masked_pg_max(alpha, g, cvec, mask), tol, max_iters, None)
    return alpha, g, iters, pg_max


# The largest batch the kernel solves.  It takes the problems of a batch one
# after another, so its time grows with the batch; the vmapped XLA loop
# steps them in lockstep, at a trip cost that grows more slowly.  On one
# v5e the kernel won at a batch of 16 (1,563 points a problem) and lost at
# 64 and 256 (98-782 points); the problem size did not move the crossover.
KERNEL_MAX_BATCH = 16


def greedy_cd_kernel_wins(batch: int) -> bool:
    """Whether ``batch`` greedy solves run together (vmapped) are faster as
    the ``greedy_cd`` kernel than as the lockstep XLA loop."""
    return batch <= KERNEL_MAX_BATCH


def greedy_cd_engine(batch: int, use_pallas) -> str:
    """The engine an untraced f32 ``solve_box_qp`` runs for ``batch``
    problems solved together (vmapped): ``"pallas"`` (the ``greedy_cd``
    kernel) or ``"xla"`` (the while-loop)."""
    kernel = resolve_use_pallas(use_pallas) and greedy_cd_kernel_wins(batch)
    return "pallas" if kernel else "xla"


@lru_cache(maxsize=None)
def _greedy_cd_batched(max_iters: int):
    """Greedy CD over a leading batch axis of (b, n, n) ``Q`` and (b, n)
    vectors, with ``tol`` (b,).  Under ``jax.vmap`` the mapped axes merge
    into that one batch, so the engine is chosen from the whole batch that
    runs together."""
    @jax.custom_batching.custom_vmap
    def run(Q, alpha, g, cvec, diag, mask, tol):
        if not greedy_cd_kernel_wins(alpha.shape[0]):
            return jax.vmap(partial(_greedy_cd_xla, max_iters=max_iters))(
                Q, alpha, g, cvec, diag, mask, tol)
        from repro.kernels import ops as kops
        # the kernel reads Q's column i as row i of Q.T (Q need not be
        # bitwise symmetric)
        return kops.greedy_cd(jnp.swapaxes(Q, 1, 2), alpha, g, cvec, diag,
                              mask, tol, max_iters=max_iters)

    @run.def_vmap
    def _(axis_size, in_batched, *args):
        args = [x if bat else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, bat in zip(args, in_batched)]
        out = run(*(x.reshape((-1,) + x.shape[2:]) for x in args))
        return (tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in out),
                (True,) * len(out))

    return run


@partial(jax.jit, static_argnames=("max_iters", "use_pallas"))
def solve_box_qp(
    Q: Array,
    C,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 10_000,
    active_mask: Optional[Array] = None,
    p=-1.0,
    trace: Optional[ConvTrace] = None,
    use_pallas: Optional[bool] = None,
) -> SolveResult:
    """Greedy coordinate descent on a dense Q. vmap over leading dims is fine.

    ``C`` (upper bound) and ``p`` (linear term) are scalar or per-coordinate
    vectors; the defaults ``C`` scalar, ``p = -1`` are the C-SVC hinge dual.
    ``active_mask`` freezes coordinates (shrinking): masked-out coordinates
    are never selected (their pg is treated as 0 for selection AND stopping,
    matching LIBSVM's shrunk working set).

    ``trace`` (static gate, ``None`` records nothing) records one
    (pg_max, objective, n_free) sample per iteration into the ring buffer,
    evaluated at the pre-update iterate like the stopping value.

    Untraced f32 solves on the Pallas backend (``use_pallas``, ``None`` =
    auto) run the whole loop as the ``greedy_cd`` kernel, with the same
    steps in the same order, wherever ``greedy_cd_kernel_wins`` says so for
    the batch that runs together; otherwise each step is a trip of an XLA
    ``while_loop``.
    """
    n = Q.shape[0]
    diag = jnp.maximum(jnp.diagonal(Q), 1e-12)
    alpha = jnp.zeros(n, Q.dtype) if alpha0 is None else alpha0
    cvec = _broadcast(C, n, Q.dtype)
    pvec = _broadcast(p, n, Q.dtype)
    g = f32_matmul(Q, alpha) + pvec
    mask = jnp.ones(n, bool) if active_mask is None else active_mask

    if (trace is None and resolve_use_pallas(use_pallas)
            and all(a.dtype == jnp.float32 for a in (Q, alpha, g))):
        out = _greedy_cd_batched(max_iters)(
            Q[None], alpha[None], g[None], cvec[None], diag[None], mask[None],
            jnp.full((1,), tol, Q.dtype))
        return SolveResult(*(o[0] for o in out))
    # one priming evaluation so the loop can exit immediately at the optimum
    pg0 = _masked_pg_max(alpha, g, cvec, mask)
    (alpha, g), iters, pg_max, tr = _cd_loop(
        _greedy_step(Q, cvec, diag, mask), _masked_record(mask, cvec, pvec),
        (alpha, g), pg0, tol, max_iters, trace)
    return SolveResult(alpha, g, iters, pg_max, trace=tr)


# ---------------------------------------------------------------------------
# Block greedy CD (beyond-paper batched variant)
# ---------------------------------------------------------------------------

def _solve_small_qp(Qbb: Array, gb: Array, ab: Array, cb, sweeps: int,
                    use_pallas: bool = False) -> Array:
    """Cyclic CD on the BxB subproblem. g_b is the gradient at entry; we
    maintain it locally.  ``cb`` is the upper bound, scalar or the (B,)
    slice of the per-coordinate box.  Returns the new a_b.

    ``use_pallas`` runs the whole sweep as one Pallas kernel
    (``kernels.small_qp``) with the same steps in the same order; otherwise
    each step is a trip of the ``fori_loop`` below."""
    B = Qbb.shape[0]
    cb = _broadcast(cb, B, Qbb.dtype)
    diag = jnp.maximum(jnp.diagonal(Qbb), 1e-12)
    if use_pallas:
        from repro.kernels import ops as kops
        # the kernel reads Qbb's column j as row j of Qbb.T (Qbb, a matmul's
        # output, need not be bitwise symmetric)
        return kops.small_qp_sweep(Qbb.T, gb, ab, cb, diag, sweeps=sweeps)

    def body(t, carry):
        a, g = carry
        j = t % B
        new_aj = jnp.clip(a[j] - g[j] / diag[j], 0.0, cb[j])
        delta = new_aj - a[j]
        a = a.at[j].set(new_aj)
        g = g + delta * Qbb[:, j]
        return a, g

    a, _ = lax.fori_loop(0, sweeps * B, body, (ab, gb))
    return a


@partial(jax.jit, static_argnames=("block", "sweeps", "max_iters"))
def solve_box_qp_block(
    Q: Array,
    C,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 2_000,
    block: int = 32,
    sweeps: int = 4,
    active_mask: Optional[Array] = None,
    p=-1.0,
    trace: Optional[ConvTrace] = None,
) -> SolveResult:
    """Top-B greedy block CD: each outer iteration moves B coordinates.

    Selection by |projected gradient| (Gauss-Southwell-B). The rank-B gradient
    update `g += Q[:, idx] @ delta` is a skinny matmul — the MXU-friendly
    reshaping of the paper's one-at-a-time CD.  ``C``/``p`` may be
    per-coordinate vectors (generalized dual).  ``trace`` records one sample
    per outer (rank-B) iteration, as in ``solve_box_qp``.
    """
    n = Q.shape[0]
    alpha = jnp.zeros(n, Q.dtype) if alpha0 is None else alpha0
    cvec = _broadcast(C, n, Q.dtype)
    pvec = _broadcast(p, n, Q.dtype)
    g = f32_matmul(Q, alpha) + pvec
    mask = jnp.ones(n, bool) if active_mask is None else active_mask

    def step(alpha, g):
        pg = jnp.where(mask, proj_grad(alpha, g, cvec), 0.0)
        scores = jnp.abs(pg)
        _, idx = lax.top_k(scores, block)
        Qbb = Q[idx][:, idx]
        ab, gb = alpha[idx], g[idx]
        new_ab = _solve_small_qp(Qbb, gb, ab, cvec[idx], sweeps)
        delta = new_ab - ab
        return (alpha.at[idx].set(new_ab), g + f32_matmul(Q[:, idx], delta),
                jnp.max(scores))

    pg0 = jnp.max(jnp.abs(jnp.where(mask, proj_grad(alpha, g, cvec), 0.0)))
    (alpha, g), iters, pg_max, tr = _cd_loop(
        step, _masked_record(mask, cvec, pvec), (alpha, g), pg0, tol,
        max_iters, trace)
    return SolveResult(alpha, g, iters, pg_max, trace=tr)


# ---------------------------------------------------------------------------
# Matvec-free block CD: kernel columns computed on the fly (large n)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("kernel", "block", "sweeps", "max_iters",
                                   "grad_chunks", "use_pallas", "cache_cap",
                                   "compute_dtype"))
def solve_box_qp_matvec(
    X: Array,
    y: Array,
    kernel: Kernel,
    C,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 500,
    block: int = 64,
    sweeps: int = 4,
    grad_chunks: int = 16,
    use_pallas: bool = False,
    cache_cap: int = 0,
    p=-1.0,
    compute_dtype: Optional[str] = None,
    Xbase: Optional[Array] = None,
    base_index: Optional[Array] = None,
    trace: Optional[ConvTrace] = None,
) -> SolveResult:
    """Block greedy CD where Q columns are recomputed from (X, y) per step.

    ``y`` is the generalized sign vector ``s`` of Q = (s s') ∘ K — class
    labels for C-SVC, the (+1, -1) mirror signs for epsilon-SVR's stacked
    (alpha, alpha*) coordinates.  ``C`` and ``p`` may be per-coordinate
    (weighted classes / the SVR linear term eps -/+ y).

    Kernel access goes through one ``core.gramop.GramOperator`` carrying the
    precision policy (``compute_dtype`` — ``None`` keeps the pre-policy
    bit-identical path) and the optional base-indexed dedup view
    (``Xbase``/``base_index`` with ``X == Xbase[base_index]`` row-for-row:
    SVR's 2n mirrored dual rows cache/store against the n base rows, signs
    expanded exactly at read).  Never materializes Q.  Three paths:

    * ``use_pallas=False, cache_cap=0`` — XLA reference: the (n, B) column
      block via ``kernel.pairwise`` each outer iteration.
    * ``use_pallas=True, cache_cap=0`` — fully fused: rank-B update through
      ``repro.kernels.ops.cd_column_update`` (the (n, B) kernel block lives
      only in VMEM, per tile) and gradient init through the streaming
      ``kernel_matvec`` kernel.
    * ``cache_cap>0`` — device-resident LRU cache of *raw* kernel rows
      (``core.colcache``, stored in the operator's storage dtype): a block
      whose B rows are all cached is served from HBM with no kernel compute
      at all (``lax.cond`` skips it); otherwise the B rows are recomputed
      (Pallas ``kermat`` on the fused path) and refilled into the cache.
      Hit/miss/eviction row counts are returned on ``SolveResult``.
    """
    op = gramop.GramOperator(Xd=X, s=y, Xb=Xbase, bidx=base_index,
                             kernel=kernel, use_pallas=use_pallas,
                             compute_dtype=compute_dtype)
    return solve_box_qp_op(op, C, alpha0=alpha0, tol=tol, max_iters=max_iters,
                           block=block, sweeps=sweeps, grad_chunks=grad_chunks,
                           cache_cap=cache_cap, p=p, trace=trace)


def solve_box_qp_op(
    op: "gramop.GramOperator",
    C,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 500,
    block: int = 64,
    sweeps: int = 4,
    grad_chunks: int = 16,
    cache_cap: int = 0,
    p=-1.0,
    trace: Optional[ConvTrace] = None,
) -> SolveResult:
    """The engine behind ``solve_box_qp_matvec``: block greedy CD against a
    ``GramOperator``.  Call inside jit (the operator's kernel / backend /
    precision fields are pytree aux data, hence trace-static).

    ``trace`` (static ``None`` gate) records one sample per outer iteration
    — on the cached path additionally the per-iteration cache-hit delta —
    entirely on device; nothing is fetched until the caller reads the
    returned ``SolveResult.trace``.
    """
    X = op.Xd
    n = op.n_dual
    alpha = jnp.zeros(n, X.dtype) if alpha0 is None else alpha0
    cvec = _broadcast(C, n, X.dtype)
    pvec = _broadcast(p, n, X.dtype)

    # accumulation dtype: at least f32 (Pallas kernels accumulate in f32),
    # f64 preserved when x64 is enabled
    acc = jnp.promote_types(X.dtype, jnp.float32)

    # initial gradient g = Q @ alpha + p: streaming Pallas matvec on the
    # fused path, chunked lax.map otherwise
    g = (op.matvec(alpha, num_chunks=grad_chunks) + pvec).astype(acc)

    def select(alpha, g):
        pg = proj_grad(alpha, g, cvec)
        scores = jnp.abs(pg)
        _, idx = lax.top_k(scores, block)
        return idx, jnp.max(scores)

    def solve_block(Qbb, alpha, g, idx):
        ab, gb = alpha[idx], g[idx]
        new_ab = _solve_small_qp(Qbb, gb, ab, cvec[idx], sweeps,
                                 use_pallas=op.use_pallas)
        return new_ab, new_ab - ab

    if cache_cap > 0:
        cap = max(cache_cap, block)  # must hold at least one full block

        def step(alpha, g, cache):
            idx, pg_max = select(alpha, g)
            keys = op.cache_keys(idx)
            slots, hit = colcache.lookup(cache, keys)
            served = jnp.all(hit)
            kr = lax.cond(
                served,
                lambda: cache.cols[jnp.where(hit, slots, 0)].astype(acc),
                lambda: op.kernel_rows(idx).astype(acc),
            )
            cache = colcache.update(cache, keys, kr, served, slots, hit)
            Qrows = op.expand_rows(kr, idx)
            new_ab, delta = solve_block(Qrows[:, idx], alpha, g, idx)
            return (alpha.at[idx].set(new_ab), g + f32_matmul(delta, Qrows),
                    cache, pg_max)
    elif op.use_pallas:
        def step(alpha, g):
            idx, pg_max = select(alpha, g)
            # fused: dg = s * (K(X, Xb) @ (sb * delta)); the (n, B) block
            # never leaves VMEM — only the (B, B) working-set block is formed
            Qbb = op.qbb(idx).astype(acc)
            new_ab, delta = solve_block(Qbb, alpha, g, idx)
            return alpha.at[idx].set(new_ab), op.col_update(g, idx, delta), \
                pg_max
    else:
        def step(alpha, g):
            idx, pg_max = select(alpha, g)
            Qb = op.q_block(idx).astype(acc)         # (n, B) on the fly
            Qbb = Qb[idx]                            # slice, don't recompute
            new_ab, delta = solve_block(Qbb, alpha, g, idx)
            return alpha.at[idx].set(new_ab), g + f32_matmul(Qb, delta), pg_max

    def record(tr, x, x_new, pg_max):
        # pre-update sample, matching the stopping value's iterate, with
        # the step's cache-hit delta when a cache exists
        alpha, g = x[:2]
        hits = x_new[2].hits - x[2].hits if cache_cap > 0 else None
        return trace_record(tr, pg_max=pg_max,
                            objective=objective(alpha, g, pvec),
                            n_free=_n_free(alpha, cvec), cache_hits=hits)

    pg0 = jnp.max(jnp.abs(proj_grad(alpha, g, cvec)))
    x = (alpha, g)
    if cache_cap > 0:
        x += (colcache.init(cap, op.kwidth, dtype=op.storage_dtype(acc),
                            width=op.kwidth),)
    (alpha, g, *cache), iters, pg_max, tr = _cd_loop(
        step, record, x, pg0, tol, max_iters, trace)
    if not cache:
        return SolveResult(alpha, g, iters, pg_max, trace=tr)
    cache, = cache
    return SolveResult(alpha, g, iters, pg_max, cache.hits, cache.misses,
                       cache_evictions=cache.evictions, trace=tr)


# ---------------------------------------------------------------------------
# Shrinking wrapper (LIBSVM-style outer rounds)
# ---------------------------------------------------------------------------

def solve_with_shrinking(
    Q: Array,
    C,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 10_000,
    rounds: int = 3,
    shrink_margin: float = 10.0,
    block: int = 0,
    p=-1.0,
    trace: Optional[ConvTrace] = None,
    use_pallas: Optional[bool] = None,
) -> SolveResult:
    """Outer shrinking rounds around the CD solver.

    Each round: solve on the active set to ``tol``; variables pinned at a
    bound with |g| > shrink_margin * tol are removed from the active set for
    the next round; the final round always re-activates everything so the
    returned KKT residual is on the FULL problem (LIBSVM's un-shrink check).
    ``C``/``p`` may be per-coordinate vectors (generalized dual);
    ``use_pallas`` is ``solve_box_qp``'s backend.

    ``pg_max`` is recomputed at the returned alpha (one Q @ alpha matvec):
    the inner solvers report the stopping value from the last *pre-update*
    iterate, which is not the residual of the solution they return.
    """
    if rounds < 1:
        raise ValueError(f"shrinking needs rounds >= 1, got {rounds}")
    n = Q.shape[0]
    alpha = jnp.zeros(n, Q.dtype) if alpha0 is None else alpha0
    cvec = _broadcast(C, n, Q.dtype)
    mask = jnp.ones(n, bool)
    solver = (partial(solve_box_qp, use_pallas=use_pallas) if block <= 0
              else partial(solve_box_qp_block, block=block))
    res = None
    # iteration counts accumulate on device; converting per round would force
    # a host sync between rounds and serialize dispatch
    total_iters = jnp.zeros((), jnp.int32)
    tr = trace  # one ring threaded through every round (None stays None)
    for r in range(rounds):
        final = r == rounds - 1
        m = jnp.ones(n, bool) if final else mask
        res = solver(Q, C, alpha0=alpha, tol=tol, max_iters=max_iters,
                     active_mask=m, p=p, trace=tr)
        tr = res.trace
        alpha, g = res.alpha, res.grad
        total_iters = total_iters + res.iters
        strongly_lo = (alpha <= 0.0) & (g > shrink_margin * tol)
        strongly_hi = (alpha >= cvec) & (g < -shrink_margin * tol)
        mask = ~(strongly_lo | strongly_hi)
    pg_full = kkt_residual(Q, res.alpha, cvec, p=p)
    return SolveResult(res.alpha, res.grad, total_iters, pg_full, trace=tr)


# ---------------------------------------------------------------------------
# Equality-constrained dual: pairwise (SMO-style) maximal-violating-pair CD
#
#     min 1/2 u'Qu + p'u   s.t.  0 <= u <= c,  a'u = d      (a_i != 0)
#
# KKT: there exists a multiplier rho with, per coordinate, h_i = g_i / a_i
# (g = Qu + p) satisfying  h_i = rho on free coordinates and one-sided
# inequalities at the bounds.  Every coordinate therefore contributes a
# one-sided bound on rho; optimality <=> the bracket [rho_lo, rho_hi] is
# non-empty.  The solver repeatedly picks the maximal violating pair
# (j = argmax of the lower bounds, i = argmin of the upper bounds) and takes
# the exact minimizer along u + t (e_i/a_i - e_j/a_j), which preserves a'u
# for every t.  See DESIGN.md §9 for the derivation.
# ---------------------------------------------------------------------------

def _safe_a(avec: Array) -> Array:
    return jnp.where(avec == 0.0, 1.0, avec)


def _eq_direction_sets(alpha: Array, cvec: Array, avec: Array, mask: Array):
    """Slot membership for the pairwise step u += t (e_i/a_i - e_j/a_j), t>0.

    ``i_plus``: coordinates that can occupy the i slot (their u moves by
    +t/a_i, so they need room upward when a_i > 0, downward when a_i < 0);
    ``i_minus``: the j slot (u moves by -t/a_j).  Coordinates with a == 0
    never couple to the constraint and are excluded — they belong to the box
    family and must be handled by the box solvers.
    """
    ok = mask & (avec != 0.0)
    up = alpha < cvec
    dn = alpha > 0.0
    i_plus = ok & jnp.where(avec > 0, up, dn)
    i_minus = ok & jnp.where(avec > 0, dn, up)
    return i_plus, i_minus


def _as_gid(gid, n: int) -> Array:
    """``None``-or-array group ids -> (n,) int32 (single group by default)."""
    if gid is None:
        return jnp.zeros(n, jnp.int32)
    return jnp.asarray(gid, jnp.int32)


def _broadcast_d(d, n_groups: int, dtype) -> Array:
    """Scalar-or-vector equality target(s) -> (n_groups,) vector."""
    return jnp.broadcast_to(jnp.asarray(d, dtype).reshape(-1), (n_groups,))


def equality_interval_grouped(alpha: Array, grad: Array, C, a, gid,
                              n_groups: int,
                              active_mask: Optional[Array] = None):
    """Per-group brackets [rho_lo_g, rho_hi_g] of the equality multipliers
    at ``alpha`` — (n_groups,) arrays; empty sides return -inf/+inf."""
    n = alpha.shape[0]
    cvec = _broadcast(C, n, alpha.dtype)
    avec = _broadcast(a, n, alpha.dtype)
    mask = jnp.ones(n, bool) if active_mask is None else active_mask
    ingrp = _as_gid(gid, n)[None, :] == jnp.arange(n_groups)[:, None]
    i_plus, i_minus = _eq_direction_sets(alpha, cvec, avec, mask)
    h = grad / _safe_a(avec)
    rho_lo = jnp.max(jnp.where(ingrp & i_minus, h, -jnp.inf), axis=1)
    rho_hi = jnp.min(jnp.where(ingrp & i_plus, h, jnp.inf), axis=1)
    return rho_lo, rho_hi


def equality_interval(alpha: Array, grad: Array, C, a,
                      active_mask: Optional[Array] = None):
    """Bracket [rho_lo, rho_hi] of the equality multiplier at ``alpha``.

    KKT holds iff rho_lo <= rho_hi; the gap ``rho_lo - rho_hi`` is the
    maximal-violating-pair violation (LIBSVM's working-set criterion,
    generalized to arbitrary nonzero ``a``).  Empty sides return -inf/+inf.
    """
    rho_lo, rho_hi = equality_interval_grouped(alpha, grad, C, a, None, 1,
                                               active_mask=active_mask)
    return rho_lo[0], rho_hi[0]


def kkt_residual_eq(Q: Array, alpha: Array, C, a, p=0.0, gid=None,
                    n_groups: int = 1) -> Array:
    """Maximal-violating-pair gap at ``alpha`` on the FULL problem (the
    equality-family analogue of ``kkt_residual``), maximized over the
    constraint groups; 0 at any KKT point."""
    g = f32_matmul(Q, alpha) + jnp.asarray(p, alpha.dtype)
    rho_lo, rho_hi = equality_interval_grouped(alpha, g, C, a, gid, n_groups)
    return jnp.maximum(jnp.max(rho_lo - rho_hi), 0.0)


def equality_rho_grouped(alpha: Array, grad: Array, C, a, gid, n_groups: int,
                         active_mask: Optional[Array] = None) -> Array:
    """Per-group equality multipliers (n_groups,) from the bracket
    midpoints, with the same finite-side fallback as ``equality_rho``."""
    rho_lo, rho_hi = equality_interval_grouped(alpha, grad, C, a, gid,
                                               n_groups,
                                               active_mask=active_mask)
    mid = 0.5 * (rho_lo + rho_hi)
    return jnp.where(jnp.isfinite(mid), mid,
                     jnp.where(jnp.isfinite(rho_lo), rho_lo,
                               jnp.where(jnp.isfinite(rho_hi), rho_hi, 0.0)))


def equality_rho(alpha: Array, grad: Array, C, a,
                 active_mask: Optional[Array] = None) -> Array:
    """Recover the equality multiplier rho (one-class SVM's decision offset)
    from the bracket midpoint; falls back to the finite side when a bound
    set is empty (all coordinates pinned at one bound)."""
    rho_lo, rho_hi = equality_interval(alpha, grad, C, a,
                                       active_mask=active_mask)
    mid = 0.5 * (rho_lo + rho_hi)
    rho = jnp.where(jnp.isfinite(mid), mid,
                    jnp.where(jnp.isfinite(rho_lo), rho_lo,
                              jnp.where(jnp.isfinite(rho_hi), rho_hi, 0.0)))
    return rho


def project_box_equality(alpha: Array, C, a, d,
                         active_mask: Optional[Array] = None,
                         iters: int = 64) -> Array:
    """Project onto {0 <= u <= c} ∩ {a'u = d} by moving along ``a``.

    phi(t) = a' clip(u - t a, 0, c) is monotone non-increasing in t, so the
    feasible point is found by bisection — exact whenever d lies in the
    attainable interval [sum_{a<0} a c, sum_{a>0} a c] (clamped otherwise).
    Coordinates outside ``active_mask`` (and a == 0 coordinates) are frozen
    at their clipped values but still counted toward a'u, so shrunk /
    padded coordinates keep their contribution.  Pure lax control flow:
    jit- and vmap-safe, used for feasible warm starts in the divide step.

    Already-feasible starts (to the rounding noise of measuring a'u) are
    returned bit-exact: the bisection's residual-noise-sized t would
    otherwise displace every bound coordinate by O(eps) off its bound,
    re-entering them into the pairwise solver's violating sets for nothing.
    """
    n = alpha.shape[0]
    dtype = alpha.dtype
    cvec = _broadcast(C, n, dtype)
    avec = _broadcast(a, n, dtype)
    mask = jnp.ones(n, bool) if active_mask is None else active_mask
    amove = jnp.where(mask, avec, 0.0)
    base = jnp.clip(alpha, 0.0, cvec)
    d = jnp.asarray(d, dtype)

    def at_t(t):
        return jnp.clip(base - t * amove, 0.0, cvec)

    def resid(t):
        return jnp.vdot(avec, at_t(t), precision=HIGHEST) - d

    # |t| >= c_i / |a_i| saturates every moving coordinate
    T = jnp.max(jnp.where(amove != 0.0,
                          cvec / jnp.maximum(jnp.abs(amove), 1e-12), 0.0)) + 1.0

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        go_right = resid(mid) > 0.0
        return jnp.where(go_right, mid, lo), jnp.where(go_right, hi, mid)

    lo, hi = lax.fori_loop(0, iters, body, (-T, T))
    noise = 8.0 * jnp.finfo(dtype).eps \
        * (jnp.sum(jnp.abs(avec * base)) + jnp.abs(d) + 1.0)
    return jnp.where(jnp.abs(resid(0.0)) <= noise, base, at_t(0.5 * (lo + hi)))


def _pair_step(alpha: Array, cvec: Array, avec: Array, i, j, t):
    """Apply the pairwise step of length ``t >= 0`` along e_i/a_i - e_j/a_j,
    clipped to both coordinates' boxes.  Returns (new_ai, di, new_aj, dj)
    with the realized deltas for the rank-2 gradient update.

    The coordinate whose box cap binds becomes the PRIMARY and lands
    EXACTLY on its bound (so it leaves the violating index sets); the other
    coordinate is slaved to the primary's realized delta, which preserves
    a'u to one rounding.  Driving the step from one fixed side instead
    stalls: when t is below the f32 ulp of the other coordinate its delta
    underflows to zero, the slaved bound coordinate never reaches its
    bound, and the same maximal-violating pair is selected forever.
    """
    ai, aj = avec[i], avec[j]
    t_hi_i = jnp.where(ai > 0, ai * (cvec[i] - alpha[i]), -ai * alpha[i])
    t_hi_j = jnp.where(aj > 0, aj * alpha[j], aj * (alpha[j] - cvec[j]))
    t = jnp.clip(t, 0.0, jnp.minimum(t_hi_i, t_hi_j))
    hit_i = t >= t_hi_i
    hit_j = t >= t_hi_j
    bound_i = jnp.where(ai > 0, cvec[i], 0.0)     # i slot moves toward here
    bound_j = jnp.where(aj > 0, 0.0, cvec[j])     # j slot moves toward here
    # j primary: j lands exactly on its bound, i is slaved
    dj_p = bound_j - alpha[j]
    ai_from_j = jnp.clip(alpha[i] - (aj * dj_p) / ai, 0.0, cvec[i])
    # i primary: exact bound when its cap binds, else the clipped t-step
    ai_from_t = jnp.where(hit_i, bound_i,
                          jnp.clip(alpha[i] + t / ai, 0.0, cvec[i]))
    new_ai = jnp.where(hit_j, ai_from_j, ai_from_t)
    di = new_ai - alpha[i]
    new_aj = jnp.where(hit_j, bound_j,
                       jnp.clip(alpha[j] - (ai * di) / aj, 0.0, cvec[j]))
    dj = new_aj - alpha[j]
    return new_ai, di, new_aj, dj


def _restore_equality(alpha: Array, grad: Array, Q_col, cvec: Array,
                      avec: Array, d, mask: Array):
    """One exact feasibility-restoration step: absorb the accumulated f32
    rounding drift of a'u - d into a single coordinate.

    The correction coordinate must stay STRICTLY interior before and after
    the move: nudging a bound coordinate off its bound re-enters it into the
    KKT index sets with its full multiplier discrepancy, turning an O(eps)
    feasibility fix into an O(1) jump of the maximal-violating-pair gap.  An
    interior coordinate moved by O(drift) changes the gap only by
    O(||Q|| drift).  Falls back to any maskable coordinate when the iterate
    is a vertex.  ``Q_col(k)`` returns column k of Q for the gradient fix-up.
    """
    r = (jnp.vdot(avec, alpha, precision=HIGHEST)
         - jnp.asarray(d, alpha.dtype))
    cand = jnp.clip(alpha - r / _safe_a(avec), 0.0, cvec)
    resid = r + avec * (cand - alpha)
    ok = mask & (avec != 0.0)
    interior = ok & (alpha > 0.0) & (alpha < cvec) \
        & (cand > 0.0) & (cand < cvec)
    score_int = jnp.where(interior, jnp.abs(resid), jnp.inf)
    k_int = jnp.argmin(score_int)
    k_any = jnp.argmin(jnp.where(ok, jnp.abs(resid), jnp.inf))
    k = jnp.where(jnp.isfinite(score_int[k_int]), k_int, k_any)
    delta = cand[k] - alpha[k]
    alpha = alpha.at[k].set(cand[k])
    grad = grad + delta * Q_col(k)
    return alpha, grad


def _project_box_equality_grouped(alpha, cvec, avec, dvec, gid, n_groups,
                                  mask, iters: int = 64):
    """Project onto the box intersected with EVERY group's hyperplane.

    Groups are disjoint, so the per-group projections commute: each moves
    only its own coordinates along its own (group-masked) ``a``.  The
    static-group Python loop unrolls under jit/vmap."""
    for g in range(n_groups):
        sel = gid == g
        alpha = project_box_equality(alpha, cvec, jnp.where(sel, avec, 0.0),
                                     dvec[g], active_mask=mask & sel,
                                     iters=iters)
    return alpha


def _restore_equality_grouped(alpha, grad, Q_col, cvec, avec, dvec, gid,
                              n_groups, mask):
    """Per-group feasibility restoration: absorb each group's accumulated
    a'u - d_g rounding drift into one strictly interior coordinate OF THAT
    GROUP (see ``_restore_equality``)."""
    for g in range(n_groups):
        sel = gid == g
        alpha, grad = _restore_equality(alpha, grad, Q_col, cvec,
                                        jnp.where(sel, avec, 0.0), dvec[g],
                                        mask & sel)
    return alpha, grad


def _refresh_loop(step, violation, full_grad, alpha, cvec, mask, pvec, tol,
                  max_iters, refresh_every, trace):
    """The equality engines' loop: an outer loop of refresh blocks, each an
    inner loop of up to ``refresh_every`` ``step``s on the maintained
    gradient, followed by an UNCONDITIONAL from-scratch gradient
    ``full_grad(alpha)`` and a stopping test ``violation(alpha, g)`` on the
    fresh gradient.

    Two reasons over a single loop with a conditional refresh: (1) under
    vmap (every divide-step caller) a batched-predicate ``lax.cond``
    executes both branches, which would silently run the full recompute
    every iteration; (2) the convergence test at a block boundary sees the
    TRUE gradient, so f32 drift accumulated across the block's updates
    cannot make the stopping test lie at tight tolerances.

    ``step(alpha, g)`` returns the new iterate, its gradient and the
    clamped violation of the iterate it improved.  ``trace`` is an optional
    carry (``None`` has no leaves, so the untraced loop state is that of a
    loop without it); when present it records one (pg_max=violation,
    objective, n_free) sample per step at the pre-update iterate, with
    ``pvec`` the linear term of the objective.  Returns
    ``(alpha, grad, iters, pg_max, trace)`` with ``iters`` counting steps
    and ``pg_max`` the last fresh-gradient violation.
    """
    def record(tr, alpha, g, viol):
        return trace_record(tr, pg_max=viol,
                            objective=objective(alpha, g, pvec),
                            n_free=_n_free(alpha, cvec, mask))

    def outer_cond(state):
        _, _, it, viol, _ = state
        return (viol > tol) & (it < max_iters)

    def outer_body(state):
        alpha, g, it, viol, tr = state
        block = jnp.minimum(refresh_every, max_iters - it)

        def inner_cond(st):
            _, _, _, k, viol, _ = st
            return (viol > tol) & (k < refresh_every) & (k < block)

        def inner_body(st):
            alpha, g, it, k, _, tr = st
            alpha2, g2, viol = step(alpha, g)
            tr = tr if tr is None else record(tr, alpha, g, viol)
            return alpha2, g2, it + 1, k + 1, viol, tr

        alpha, g, it, _, _, tr = lax.while_loop(
            inner_cond, inner_body, (alpha, g, it, 0, viol, tr))
        g = full_grad(alpha)
        return alpha, g, it, jnp.maximum(violation(alpha, g), 0.0), tr

    g = full_grad(alpha)
    viol0 = jnp.maximum(violation(alpha, g), 0.0)
    return lax.while_loop(outer_cond, outer_body, (alpha, g, 0, viol0, trace))


def _pairwise_mvp_loop(alpha, cvec, avec, mask, gid, n_groups, qdiag, qij_fn,
                       rank2_fn, full_grad, tol, max_iters, refresh_every,
                       trace=None, pvec=None):
    """Shared pairwise maximal-violating-pair engine (dense and matvec
    front-ends differ only in how Q entries and the rank-2 gradient update
    are produced), run by ``_refresh_loop`` with one rank-2 step per
    iteration.  ``iters`` counts pair steps.  Pairs are drawn within one
    group (``gid``/``n_groups``): the selected pair belongs to the group
    with the widest multiplier-bracket violation, so every group's
    constraint is preserved exactly and the stopping test is the max gap
    over groups.  Returns ``(alpha, grad, iters, pg_max, trace)``;
    ``trace``/``pvec`` as in ``_refresh_loop``.
    """
    safe = _safe_a(avec)
    ingrp = gid[None, :] == jnp.arange(n_groups)[:, None]      # (G, n)

    def select(alpha, g):
        i_plus, i_minus = _eq_direction_sets(alpha, cvec, avec, mask)
        h = g / safe
        hi_side = jnp.where(ingrp & i_plus, h, jnp.inf)        # (G, n)
        lo_side = jnp.where(ingrp & i_minus, h, -jnp.inf)
        ig = jnp.argmin(hi_side, axis=1)
        jg = jnp.argmax(lo_side, axis=1)
        gr = jnp.arange(n_groups)
        gaps = lo_side[gr, jg] - hi_side[gr, ig]
        gs = jnp.argmax(gaps)
        return ig[gs], jg[gs], gaps[gs]

    def pair_step(alpha, g):
        i, j, viol = select(alpha, g)
        # ``safe`` (a with 0 -> 1), not raw a: if the violating sets collapse
        # to one side mid-block, argmin/argmax over an all-inf side return an
        # arbitrary index whose a may be 0 (padding) — the step length is 0
        # there (viol <= 0), but raw-a division would still produce
        # inf - inf = NaN in curv and poison the iterate.  Real pairs always
        # have a != 0, so safe == a on every selected coordinate that moves.
        ai, aj = safe[i], safe[j]
        # exact minimizer along v = e_i/a_i - e_j/a_j: phi'(0) = h_i - h_j,
        # phi'' = Q_ii/a_i^2 + Q_jj/a_j^2 - 2 Q_ij/(a_i a_j) >= 0 (Q PSD)
        curv = qdiag[i] / (ai * ai) + qdiag[j] / (aj * aj) \
            - 2.0 * qij_fn(i, j) / (ai * aj)
        t = jnp.maximum(viol, 0.0) / jnp.maximum(curv, 1e-12)
        new_ai, di, new_aj, dj = _pair_step(alpha, cvec, safe, i, j, t)
        alpha = alpha.at[i].set(new_ai).at[j].set(new_aj)
        g = rank2_fn(g, i, j, di, dj)
        return alpha, g, jnp.maximum(viol, 0.0)

    return _refresh_loop(pair_step, lambda al, g: select(al, g)[2],
                         full_grad, alpha, cvec, mask, pvec, tol, max_iters,
                         refresh_every, trace)


@partial(jax.jit, static_argnames=("max_iters", "refresh_every", "n_groups"))
def solve_eq_qp(
    Q: Array,
    C,
    a,
    d,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 10_000,
    active_mask: Optional[Array] = None,
    p=0.0,
    refresh_every: int = 256,
    gid=None,
    n_groups: int = 1,
    trace: Optional[ConvTrace] = None,
) -> SolveResult:
    """Pairwise maximal-violating-pair CD on a dense Q; every iterate stays
    on the hyperplane(s) a'u = d.  vmap over leading dims is fine.

    The (possibly infeasible) warm start is first projected onto the
    feasible set along ``a`` (``project_box_equality``), so cluster
    sub-solutions gathered by the divide step are always valid starts.
    ``C``/``a``/``p`` broadcast from scalars; ``active_mask`` freezes
    coordinates (shrinking / padding) — frozen coordinates keep their value
    and their a'u contribution.  ``gid``/``n_groups`` decompose the
    coordinates into disjoint groups with one constraint each (``d`` is
    then the (n_groups,) target vector; a scalar broadcasts); pairs are
    drawn within one group.  Stops when the multiplier bracket gap
    max_g (rho_lo_g - rho_hi_g), measured on a freshly recomputed gradient
    every ``refresh_every`` pair steps (one Q @ u matvec, amortized
    O(n/refresh_every) per step — see ``_pairwise_mvp_loop``), drops below
    ``tol``.
    """
    n = Q.shape[0]
    dtype = Q.dtype
    cvec = _broadcast(C, n, dtype)
    avec = _broadcast(a, n, dtype)
    pvec = _broadcast(p, n, dtype)
    mask = jnp.ones(n, bool) if active_mask is None else active_mask
    gidv = _as_gid(gid, n)
    dvec = _broadcast_d(d, n_groups, dtype)
    alpha = jnp.zeros(n, dtype) if alpha0 is None else alpha0
    alpha = _project_box_equality_grouped(alpha, cvec, avec, dvec, gidv,
                                          n_groups, mask)

    alpha, g, iters, pg_max, tr = _pairwise_mvp_loop(
        alpha, cvec, avec, mask, gidv, n_groups,
        qdiag=jnp.diagonal(Q),
        qij_fn=lambda i, j: Q[i, j],
        rank2_fn=lambda g, i, j, di, dj: g + di * Q[:, i] + dj * Q[:, j],
        full_grad=lambda al: f32_matmul(Q, al) + pvec,
        tol=tol, max_iters=max_iters, refresh_every=refresh_every,
        trace=trace, pvec=pvec)
    alpha, g = _restore_equality_grouped(alpha, g, lambda k: Q[:, k], cvec,
                                         avec, dvec, gidv, n_groups, mask)
    return SolveResult(alpha, g, iters, pg_max, trace=tr)


# ---------------------------------------------------------------------------
# Rank-2B blocked pairwise CD: B maximal-violating pairs per outer iteration,
# solved as a coupled 2Bx2B sub-QP that carries one coupling row per group
# (a_b'u_b = const) — the equality-family analogue of solve_box_qp_block.
# Derivation and the B=1 reduction to the pairwise step: DESIGN.md §10.
# ---------------------------------------------------------------------------

_SELECT_BIG = 1e30   # finite tier-2 selection score: "no violation, but a
                     # real in-group coordinate" — sorts strictly above the
                     # -inf non-candidates, strictly below any real h score


def _solve_small_eq_qp(Qbb: Array, gb: Array, ub: Array, ab: Array, cb: Array,
                       gidb: Array, n_groups: int, active: Array,
                       steps: int) -> Array:
    """Grouped MVP pair-sweeps on the (m, m) sub-QP around the entry point.

    Each inner step selects the block-local maximal violating pair (within
    one group) and takes the exact clipped minimizer along
    ``e_i/a_i - e_j/a_j`` — the same rank-2 step as the pairwise engine, so
    EVERY inner iterate stays on each group's hyperplane
    ``a_b'u_b = const``.  ``active`` freezes slots (padding from a
    short-sided selection; possibly duplicate indices — frozen slots never
    move, so duplicates stay inert).  The local gradient ``gb`` is
    maintained by rank-2 updates on the (m,) slice; at block optimality the
    selected step length underflows to an exact no-op, so running all
    ``steps`` iterations is safe.  This is ``_solve_small_qp`` generalized
    to carry the coupling rows.
    """
    diag = jnp.diagonal(Qbb)
    safe = _safe_a(ab)
    ingrp = gidb[None, :] == jnp.arange(n_groups)[:, None]

    def body(_, carry):
        u, g = carry
        i_plus, i_minus = _eq_direction_sets(u, cb, ab, active)
        h = g / safe
        hi_side = jnp.where(ingrp & i_plus, h, jnp.inf)
        lo_side = jnp.where(ingrp & i_minus, h, -jnp.inf)
        ig = jnp.argmin(hi_side, axis=1)
        jg = jnp.argmax(lo_side, axis=1)
        gr = jnp.arange(n_groups)
        gaps = lo_side[gr, jg] - hi_side[gr, ig]
        gs = jnp.argmax(gaps)
        i, j = ig[gs], jg[gs]
        viol = gaps[gs]
        # safe (0 -> 1), not raw ab: a one-sided block returns arbitrary
        # indices with possibly-zero a (frozen padding slots) — the step is
        # 0 there, but raw-a division would turn it into NaN
        ai, aj = safe[i], safe[j]
        curv = diag[i] / (ai * ai) + diag[j] / (aj * aj) \
            - 2.0 * Qbb[i, j] / (ai * aj)
        t = jnp.maximum(viol, 0.0) / jnp.maximum(curv, 1e-12)
        new_ui, di, new_uj, dj = _pair_step(u, cb, safe, i, j, t)
        u = u.at[i].set(new_ui).at[j].set(new_uj)
        g = g + di * Qbb[:, i] + dj * Qbb[:, j]
        return u, g

    u, _ = lax.fori_loop(0, steps, body, (ub, gb))
    return u


def _blocked_mvp_loop(alpha, cvec, avec, mask, gid, n_groups, block, sweeps,
                      qbb_fn, rank2b_fn, full_grad, tol, max_iters,
                      refresh_every, trace=None, pvec=None):
    """Shared rank-2B blocked engine (dense and matvec front-ends differ
    only in how the sub-block of Q and the rank-2B gradient update are
    produced).

    Selection per outer iteration and group: the top-``block`` i-slot
    candidates (smallest multiplier bounds h among the upward-movable set)
    and, disjointly, the top-``block`` j-slot candidates (largest h among
    the downward-movable set) — so the global maximal violating pair is
    always inside the block and one blocked iteration makes at least as
    much progress as one exact pairwise step.  Tier-2 fallback: when a side
    has fewer than ``block`` violating candidates, remaining slots are
    filled with arbitrary distinct in-group coordinates (still useful: the
    sub-QP may move them); slots that cannot be filled at all (group
    smaller than 2*block) come back non-finite and are frozen in the
    sub-QP, their writes routed onto a valid slot so duplicate scatter
    writes are identical and therefore deterministic.

    Run by ``_refresh_loop`` like ``_pairwise_mvp_loop``, with one rank-2B
    iteration per step; ``iters`` counts outer blocked iterations and the
    return value is the same 5-tuple.
    """
    n = alpha.shape[0]
    safe = _safe_a(avec)
    ingrp = gid[None, :] == jnp.arange(n_groups)[:, None]      # (G, n)
    okg = ingrp & (mask & (avec != 0.0))[None, :]
    steps = 2 * sweeps * block

    def sides(alpha, g):
        i_plus, i_minus = _eq_direction_sets(alpha, cvec, avec, mask)
        h = g / safe
        return i_plus, i_minus, h

    def gap(i_plus, i_minus, h):
        hi = jnp.min(jnp.where(ingrp & i_plus, h, jnp.inf), axis=1)
        lo = jnp.max(jnp.where(ingrp & i_minus, h, -jnp.inf), axis=1)
        return jnp.max(lo - hi)

    def select(alpha, g):
        i_plus, i_minus, h = sides(alpha, g)
        viol = gap(i_plus, i_minus, h)
        big = jnp.asarray(_SELECT_BIG, h.dtype)
        sc_i = jnp.where(ingrp & i_plus, -h, jnp.where(okg, -big, -jnp.inf))
        iv, ii = lax.top_k(sc_i, block)                        # (G, B)
        taken = jnp.zeros(n, jnp.int32).at[ii.reshape(-1)].max(
            jnp.isfinite(iv).reshape(-1).astype(jnp.int32)).astype(bool)
        open_j = ~taken[None, :]
        sc_j = jnp.where(ingrp & i_minus & open_j, h,
                         jnp.where(okg & open_j, -big, -jnp.inf))
        jv, jj = lax.top_k(sc_j, block)
        idx = jnp.concatenate([ii, jj], axis=1).reshape(-1)    # (G * 2B,)
        valid = jnp.concatenate([jnp.isfinite(iv), jnp.isfinite(jv)],
                                axis=1).reshape(-1)
        return idx, valid, viol

    def block_step(alpha, g):
        idx, valid, viol = select(alpha, g)
        ub, gb = alpha[idx], g[idx]
        new_ub = _solve_small_eq_qp(qbb_fn(idx), gb, ub, avec[idx], cvec[idx],
                                    gid[idx], n_groups, valid, steps)
        # invalid slots may duplicate a valid slot's index: route their
        # writes onto one valid slot so duplicate writes carry identical
        # values (deterministic under scatter), and zero their deltas
        s0 = jnp.argmax(valid)
        alpha = alpha.at[jnp.where(valid, idx, idx[s0])].set(
            jnp.where(valid, new_ub, new_ub[s0]))
        delta = jnp.where(valid, new_ub - ub, 0.0)
        g = rank2b_fn(g, idx, delta)
        return alpha, g, jnp.maximum(viol, 0.0)

    return _refresh_loop(block_step, lambda al, g: gap(*sides(al, g)),
                         full_grad, alpha, cvec, mask, pvec, tol, max_iters,
                         refresh_every, trace)


@partial(jax.jit, static_argnames=("block", "sweeps", "max_iters",
                                   "refresh_every", "n_groups"))
def solve_eq_qp_block(
    Q: Array,
    C,
    a,
    d,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 5_000,
    block: int = 8,
    sweeps: int = 4,
    active_mask: Optional[Array] = None,
    p=0.0,
    refresh_every: int = 32,
    gid=None,
    n_groups: int = 1,
    trace: Optional[ConvTrace] = None,
) -> SolveResult:
    """Rank-2B blocked pairwise CD on a dense Q: each outer iteration
    selects the ``block`` maximal-violating pairs per group from the KKT
    multiplier bracket and solves the coupled 2Bx2B sub-QP (one coupling
    row per group) with grouped MVP pair-sweeps, then applies the rank-2B
    gradient update ``g += Q[:, idx] @ delta`` — a skinny matmul, the
    MXU-friendly reshaping of the pairwise engine exactly as
    ``solve_box_qp_block`` is of ``solve_box_qp``.

    Every iterate stays on every group's hyperplane (the sub-QP moves only
    along within-group pair directions), and the feasibility-restore and
    rho-bracket machinery of the rank-2 engine is reused unchanged.  At
    ``block = 1`` this is the pairwise step with ``sweeps`` extra polishing
    steps on the selected pair; ``DCSVMConfig.eq_block_size = 1`` routes to
    ``solve_eq_qp`` instead.  vmap over leading dims is fine.
    """
    n = Q.shape[0]
    dtype = Q.dtype
    cvec = _broadcast(C, n, dtype)
    avec = _broadcast(a, n, dtype)
    pvec = _broadcast(p, n, dtype)
    mask = jnp.ones(n, bool) if active_mask is None else active_mask
    gidv = _as_gid(gid, n)
    dvec = _broadcast_d(d, n_groups, dtype)
    B = max(1, min(block, n // (2 * n_groups)))
    alpha = jnp.zeros(n, dtype) if alpha0 is None else alpha0
    alpha = _project_box_equality_grouped(alpha, cvec, avec, dvec, gidv,
                                          n_groups, mask)

    alpha, g, iters, pg_max, tr = _blocked_mvp_loop(
        alpha, cvec, avec, mask, gidv, n_groups, B, sweeps,
        qbb_fn=lambda idx: Q[idx][:, idx],
        rank2b_fn=lambda g, idx, delta: g + f32_matmul(Q[:, idx], delta),
        full_grad=lambda al: f32_matmul(Q, al) + pvec,
        tol=tol, max_iters=max_iters, refresh_every=refresh_every,
        trace=trace, pvec=pvec)
    alpha, g = _restore_equality_grouped(alpha, g, lambda k: Q[:, k], cvec,
                                         avec, dvec, gidv, n_groups, mask)
    return SolveResult(alpha, g, iters, pg_max, trace=tr)


def solve_eq_qp_shrink(
    Q: Array,
    C,
    a,
    d,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 10_000,
    rounds: int = 3,
    shrink_margin: float = 10.0,
    p=0.0,
    block: int = 0,
    sweeps: int = 4,
    gid=None,
    n_groups: int = 1,
    trace: Optional[ConvTrace] = None,
) -> SolveResult:
    """Outer shrinking rounds around the pairwise engine (the equality-family
    ``solve_with_shrinking``): coordinates pinned at a bound whose multiplier
    bound h_i sits beyond THEIR GROUP's current rho estimate by more than
    ``shrink_margin * tol`` are frozen for the next round; the final round
    re-activates everything and the returned residual is the full-problem
    maximal-violating-pair gap.  Frozen coordinates keep their a'u
    contribution, so every round solves the SAME constrained problem.
    ``block > 1`` runs the rank-2B blocked engine (``solve_eq_qp_block``)
    inside each round instead of the rank-2 pairwise engine.
    """
    if rounds < 1:
        raise ValueError(f"shrinking needs rounds >= 1, got {rounds}")
    n = Q.shape[0]
    dtype = Q.dtype
    cvec = _broadcast(C, n, dtype)
    avec = _broadcast(a, n, dtype)
    gidv = _as_gid(gid, n)
    alpha = jnp.zeros(n, dtype) if alpha0 is None else alpha0
    mask = jnp.ones(n, bool)
    res = None
    total_iters = jnp.zeros((), jnp.int32)
    tr = trace  # one ring threaded through every round (None stays None)
    for r in range(rounds):
        final = r == rounds - 1
        m = jnp.ones(n, bool) if final else mask
        if block > 1:
            res = solve_eq_qp_block(Q, C, a, d, alpha0=alpha, tol=tol,
                                    max_iters=max_iters, block=block,
                                    sweeps=sweeps, active_mask=m, p=p,
                                    gid=gidv, n_groups=n_groups, trace=tr)
        else:
            res = solve_eq_qp(Q, C, a, d, alpha0=alpha, tol=tol,
                              max_iters=max_iters, active_mask=m, p=p,
                              gid=gidv, n_groups=n_groups, trace=tr)
        tr = res.trace
        alpha, g = res.alpha, res.grad
        total_iters = total_iters + res.iters
        rho = equality_rho_grouped(alpha, g, cvec, avec, gidv,
                                   n_groups)[gidv]
        h = g / _safe_a(avec)
        mtol = shrink_margin * tol
        at_lo = alpha <= 0.0
        at_hi = alpha >= cvec
        lock_lo = at_lo & jnp.where(avec > 0, h > rho + mtol, h < rho - mtol)
        lock_hi = at_hi & jnp.where(avec > 0, h < rho - mtol, h > rho + mtol)
        mask = ~(lock_lo | lock_hi)
    pg_full = kkt_residual_eq(Q, res.alpha, cvec, avec, p=p, gid=gidv,
                              n_groups=n_groups)
    return SolveResult(res.alpha, res.grad, total_iters, pg_full, trace=tr)


@partial(jax.jit, static_argnames=("kernel", "max_iters", "grad_chunks",
                                   "use_pallas", "refresh_every", "block",
                                   "sweeps", "n_groups", "compute_dtype"))
def solve_eq_qp_matvec(
    X: Array,
    y: Array,
    kernel: Kernel,
    C,
    a,
    d,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 5_000,
    grad_chunks: int = 16,
    use_pallas: bool = False,
    p=0.0,
    refresh_every: int = 512,
    block: int = 1,
    sweeps: int = 4,
    gid=None,
    n_groups: int = 1,
    compute_dtype: Optional[str] = None,
    trace: Optional[ConvTrace] = None,
) -> SolveResult:
    """Pairwise / blocked maximal-violating-pair CD with on-the-fly kernel
    columns: Q = (y y') ∘ K(X, X) is never materialized.  ``y`` is the task
    sign vector ``s`` (all ones for one-class SVM, labels for nu-SVC);
    ``a`` may be mixed-sign.  On the fused path (``use_pallas=True``) the
    rank-2 (``block <= 1``) or rank-2B (``block > 1``) gradient update
    streams through ``repro.kernels.ops.cd_column_update`` — the (n, 2B)
    kernel block lives only in VMEM — and the gradient init through the
    streaming ``kernel_matvec``: the whole solve is ONE jitted program with
    no host transfer.  ``refresh_every`` counts pair steps on the rank-2
    path and is rescaled by 2B on the blocked path, so the gradient-drift
    budget between from-scratch refreshes is comparable.
    """
    n = X.shape[0]
    dtype = X.dtype
    cvec = _broadcast(C, n, dtype)
    avec = _broadcast(a, n, dtype)
    pvec = _broadcast(p, n, dtype)
    mask = jnp.ones(n, bool)
    gidv = _as_gid(gid, n)
    dvec = _broadcast_d(d, n_groups, dtype)
    alpha = jnp.zeros(n, dtype) if alpha0 is None else alpha0
    alpha = _project_box_equality_grouped(alpha, cvec, avec, dvec, gidv,
                                          n_groups, mask)

    op = gramop.GramOperator(Xd=X, s=y, kernel=kernel, use_pallas=use_pallas,
                             compute_dtype=compute_dtype)

    acc = jnp.promote_types(dtype, jnp.float32)

    def full_grad(al):
        return (op.matvec(al, num_chunks=grad_chunks) + pvec).astype(acc)

    def rank2b_fn(g, idx, delta):
        """Rank-|idx| gradient update, shared by the rank-2 and rank-2B
        paths: fused cd_column_update on the Pallas path (the (n, |idx|)
        kernel block stays in VMEM), an on-the-fly column matmul on XLA."""
        return op.col_update(g, idx, delta)

    if block > 1:
        B = max(1, min(block, n // (2 * n_groups)))

        def qbb_fn(idx):
            return op.qbb(idx).astype(acc)

        alpha, g, iters, pg_max, tr = _blocked_mvp_loop(
            alpha, cvec, avec, mask, gidv, n_groups, B, sweeps,
            qbb_fn=qbb_fn, rank2b_fn=rank2b_fn, full_grad=full_grad,
            tol=tol, max_iters=max_iters,
            refresh_every=max(1, refresh_every // (2 * B)),
            trace=trace, pvec=pvec)
    else:
        def qij_fn(i, j):
            return op.qbb(jnp.stack([i, j]))[0, 1].astype(acc)

        def rank2_fn(g, i, j, di, dj):
            return rank2b_fn(g, jnp.stack([i, j]), jnp.stack([di, dj]))

        alpha, g, iters, pg_max, tr = _pairwise_mvp_loop(
            alpha, cvec, avec, mask, gidv, n_groups,
            qdiag=op.qdiag().astype(acc),
            qij_fn=qij_fn, rank2_fn=rank2_fn, full_grad=full_grad,
            tol=tol, max_iters=max_iters, refresh_every=refresh_every,
            trace=trace, pvec=pvec)

    def q_col(k):
        # XLA pairwise regardless of backend (one skinny column), under the
        # operator's precision policy
        Kk = kernel.pairwise(X, X[k][None, :],
                             compute_dtype=op._cd())[:, 0]
        return (y * y[k] * Kk).astype(acc)

    alpha, g = _restore_equality_grouped(alpha, g, q_col, cvec, avec, dvec,
                                         gidv, n_groups, mask)
    return SolveResult(alpha, g, iters, pg_max, trace=tr)
