"""Unified kernel-operator layer: one Gram abstraction for every consumer.

Every layer that touches kernel elements — the divide step's cluster Grams,
the conquer solvers' row blocks and matvecs, the distributed per-device
residency, the column cache, and serving's bucketed scores — routes through
a ``GramOperator``: kernel + data + precision policy + memory tiers in one
pytree, so precision, chunking, and caching are decided in exactly one place
(DESIGN.md §12).

Three concerns live here:

1. **Precision policy** (``compute_dtype``).  ``None`` (the default) keeps
   every computation bit-identical to the pre-policy code path.  A low
   precision like ``"bfloat16"`` casts the *matmul operand tiles* only —
   accumulation stays f32 via ``preferred_element_type`` and the kernel
   transform (exp / polynomial) runs in f32, exactly the
   ``kernels/flash_attention.py`` idiom.  The relative tile error is then
   bounded by the bf16 mantissa (2^-8) on the Gram inner products, not
   amplified by the length-d reduction.

2. **Memory hierarchy** (``solve_box_qp_spill``).  Kernel rows are panelized
   into device-budget-sized tiles: device panel LRU (tier 1) over pinned
   host-RAM numpy buffers (tier 2, written through on first compute), with a
   double-buffered async ``jax.device_put`` so the copy of the next panel
   overlaps the current panel's jitted block-CD sub-solve.  Gram size is
   therefore bounded by *host* RAM, not device memory — the out-of-core
   regime the ROADMAP item calls for.

3. **Base-indexed Gram view** (``Xb``/``bidx``).  Tasks with duplicated dual
   rows (epsilon-SVR's stacked (alpha, alpha*) mirror) dedup kernel storage
   to the n base rows: cached/spilled rows are *raw* kernel rows of width
   ``n_base`` and the task signs expand at read time via
   ``Q[i, j] = s_i * K[i, bidx_j] * s_j`` (multiplication by +/-1 is exact,
   so the expansion is bit-transparent).  That is a 4x cluster-level Gram
   saving and a 2x row-cache saving for SVR.

All budgets are denominated in BYTES (``DEFAULT_GRAM_BUDGET``), so bf16
storage really does fit twice the rows of f32.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.kernels import (DEFAULT_GRAM_BUDGET, Kernel, auto_num_chunks,
                                f32_matmul, gram_matvec)

Array = jax.Array


def fits_budget(n_elems: int, budget_bytes: int, dtype=jnp.float32) -> bool:
    """Does an ``n_elems``-element buffer of ``dtype`` fit ``budget_bytes``?
    The one predicate behind every Gram-residency decision (dense cluster
    batches, per-device shard residency, cache sizing)."""
    return int(n_elems) * jnp.dtype(dtype).itemsize <= int(budget_bytes)


def resolve_compute_dtype(compute_dtype, ref_dtype) -> Optional[str]:
    """Normalize the precision policy: ``None`` — or a dtype equal to the
    data's own — means "no cast", keeping the exact pre-policy jaxpr."""
    if compute_dtype is None:
        return None
    cd = jnp.dtype(compute_dtype)
    if cd == jnp.dtype(ref_dtype):
        return None
    return str(cd)


@dataclasses.dataclass(frozen=True)
class GramOperator:
    """Kernel + dual data + precision policy + base-index dedup, as a pytree.

    ``Xd`` (n_dual, d) are the task's dual points and ``s`` (n_dual,) its
    sign vector, defining ``Q = (s s') ∘ K(Xd, Xd)``.  When ``Xb``/``bidx``
    are set (``Xd == Xb[bidx]`` row-for-row), kernel rows are computed and
    stored against the ``n_base`` base rows only and sign-expanded at read.
    ``kernel``/``use_pallas``/``compute_dtype``/``budget_bytes`` are static
    (pytree aux data), so the operator can cross ``jax.jit`` boundaries and
    be ``dataclasses.replace``d per class row inside a ``vmap``.
    """

    Xd: Array
    s: Array
    Xb: Optional[Array] = None
    bidx: Optional[Array] = None
    kernel: Kernel = Kernel("rbf", gamma=1.0)
    use_pallas: bool = False
    compute_dtype: Optional[str] = None
    budget_bytes: int = DEFAULT_GRAM_BUDGET

    # -- structure --------------------------------------------------------
    @property
    def n_dual(self) -> int:
        return self.Xd.shape[0]

    @property
    def dedup(self) -> bool:
        return self.bidx is not None

    @property
    def kwidth(self) -> int:
        """Width of a raw kernel row — the cache/spill storage unit."""
        return self.Xb.shape[0] if self.dedup else self.n_dual

    def storage_dtype(self, acc):
        """Row-storage dtype for the cache/spill tiers: the compute dtype
        when a low-precision policy is active, else the accumulator's."""
        if self.compute_dtype is not None:
            return jnp.dtype(self.compute_dtype)
        return jnp.dtype(acc)

    def cache_keys(self, idx: Array) -> Array:
        """Cache key per selected dual coordinate: the base id under dedup
        (mirrored SVR coordinates share one cached row), else the
        coordinate itself."""
        return self.bidx[idx] if self.dedup else idx

    # -- kernel access ----------------------------------------------------
    def _cd(self) -> Optional[str]:
        return resolve_compute_dtype(self.compute_dtype, self.Xd.dtype)

    def kmat(self, A: Array, B: Array) -> Array:
        """Policy-tiled K(A, B) — Pallas kermat tiles or the XLA pairwise."""
        if self.use_pallas:
            from repro.kernels import ops as kops

            return kops.kernel_matrix(A, B, self.kernel,
                                      compute_dtype=self.compute_dtype)
        return self.kernel.pairwise(A, B, compute_dtype=self._cd())

    def kernel_rows(self, idx: Array) -> Array:
        """Raw (B, kwidth) kernel rows ``K(Xd[idx], base points)`` — the
        sign-free unit the column cache and the host-spill panels store."""
        pts = self.Xb if self.dedup else self.Xd
        return self.kmat(self.Xd[idx], pts)

    def expand_rows(self, kr: Array, idx: Array) -> Array:
        """Raw rows (B, kwidth) -> signed Q rows (B, n_dual): gather the
        base columns out to dual coordinates, then apply the task signs
        (exact: ``s`` is +/-1)."""
        cols = kr[:, self.bidx] if self.dedup else kr
        return self.s[idx][:, None] * (cols * self.s[None, :])

    def q_rows(self, idx: Array) -> Array:
        """Signed (B, n_dual) rows of Q for a selected block."""
        return self.expand_rows(self.kernel_rows(idx), idx)

    def q_block(self, idx: Array) -> Array:
        """Signed (n_dual, B) columns of Q (the XLA no-cache orientation)."""
        Xsel = self.Xd[idx]
        if self.dedup:
            Kb = self.kmat(self.Xb, Xsel)[self.bidx]
        else:
            Kb = self.kmat(self.Xd, Xsel)
        return (self.s[:, None] * self.s[idx][None, :]) * Kb

    def qbb(self, idx: Array) -> Array:
        """The (B, B) working-set block of Q."""
        Xsel, ssel = self.Xd[idx], self.s[idx]
        Kbb = self.kernel.pairwise(Xsel, Xsel, compute_dtype=self._cd())
        return (ssel[:, None] * ssel[None, :]) * Kbb

    def qdiag(self) -> Array:
        return self.s * self.s * self.kernel.diag(self.Xd)

    def matvec(self, v: Array, num_chunks: Optional[int] = None,
               via_base: bool = False) -> Array:
        """Q @ v without materializing Q.  ``via_base=True`` collapses the
        weights onto the base rows first (an n_base-sized matvec — 4x fewer
        kernel evaluations for SVR, at the cost of a re-associated sum), and
        is opt-in so the default path stays bit-identical to the historical
        full-width matvec."""
        if via_base and self.dedup:
            w = jnp.zeros(self.Xb.shape[0], v.dtype).at[self.bidx].add(
                self.s * v)
            kv = gram_matvec(self.kernel, self.Xb, w, num_chunks=num_chunks,
                             use_pallas=self.use_pallas,
                             compute_dtype=self.compute_dtype,
                             budget_bytes=self.budget_bytes)
            return self.s * kv[self.bidx]
        return self.s * gram_matvec(self.kernel, self.Xd, self.s * v,
                                    num_chunks=num_chunks,
                                    use_pallas=self.use_pallas,
                                    compute_dtype=self.compute_dtype,
                                    budget_bytes=self.budget_bytes)

    def col_update(self, g: Array, idx: Array, delta: Array) -> Array:
        """g += Q[:, idx] @ delta — the rank-B gradient update.  Fused
        Pallas ``cd_column_update`` (the (n, B) block never leaves VMEM) on
        the Pallas path, on-the-fly column matmul on XLA."""
        Xsel, ssel = self.Xd[idx], self.s[idx]
        if self.use_pallas:
            from repro.kernels import ops as kops

            if self.dedup:
                base = kops.cd_column_update(
                    self.Xb, jnp.ones(self.Xb.shape[0], self.Xd.dtype),
                    Xsel, ssel * delta, self.kernel,
                    compute_dtype=self.compute_dtype)
                return g + (self.s * base[self.bidx]).astype(g.dtype)
            return g + kops.cd_column_update(
                self.Xd, self.s, Xsel, ssel * delta, self.kernel,
                compute_dtype=self.compute_dtype).astype(g.dtype)
        Qb = self.q_block(idx).astype(g.dtype)
        return g + f32_matmul(Qb, delta)


jax.tree_util.register_pytree_node(
    GramOperator,
    lambda op: ((op.Xd, op.s, op.Xb, op.bidx),
                (op.kernel, op.use_pallas, op.compute_dtype, op.budget_bytes)),
    lambda aux, kids: GramOperator(kids[0], kids[1], kids[2], kids[3],
                                   kernel=aux[0], use_pallas=aux[1],
                                   compute_dtype=aux[2], budget_bytes=aux[3]),
)


# ---------------------------------------------------------------------------
# Host-RAM spill tier: out-of-core block CD over kernel-row panels
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block", "sweeps", "inner", "rows_p"))
def _panel_block_cd(op: GramOperator, tile: Array, pstart, alpha: Array,
                    g: Array, cvec: Array, tol, *, block: int, sweeps: int,
                    inner: int, rows_p: int):
    """Greedy block CD restricted to one device-resident panel of raw kernel
    rows.  ``tile`` is (rows_p, kwidth) in storage dtype; selection is
    Gauss-Southwell within the panel, the rank-B gradient update runs over
    ALL coordinates (sign expansion of the B selected raw rows), so the
    maintained global gradient stays exact across panel visits.

    Panels live in BASE-row space: under dedup a dual coordinate is
    in-panel when its *base id* is — so SVR's mirrored pair (i, i+n)
    always co-resides and the working set can move the strongly coupled
    pair jointly (panel-restricted CD would zigzag if the mirrors were
    split across panels)."""
    from repro.core.solver import _solve_small_qp, proj_grad

    n = alpha.shape[0]
    acc = g.dtype
    key = op.bidx if op.dedup else jnp.arange(n)
    in_panel = (key >= pstart) & (key < pstart + rows_p)

    def panel_pg(alpha, g):
        return jnp.max(jnp.where(in_panel,
                                 jnp.abs(proj_grad(alpha, g, cvec)), 0.0))

    def body(state):
        alpha, g, it, _ = state
        sc = jnp.where(in_panel, jnp.abs(proj_grad(alpha, g, cvec)),
                       -jnp.inf)
        _, sel = lax.top_k(sc, block)
        # the last panel may hold fewer than ``block`` coordinates: freeze
        # out-of-panel picks (box [0, 0]) so junk tile rows cannot move them
        valid = in_panel[sel]
        local = jnp.clip(key[sel] - pstart, 0, rows_p - 1)
        kr = tile[local].astype(acc)
        Qrows = op.expand_rows(kr, sel)                     # (B, n) signed
        ab = jnp.where(valid, alpha[sel], 0.0).astype(acc)
        cb = jnp.where(valid, cvec[sel], 0.0)
        new_ab = _solve_small_qp(Qrows[:, sel], g[sel], ab, cb, sweeps,
                                 use_pallas=op.use_pallas)
        delta = jnp.where(valid, new_ab - ab, 0.0)
        alpha = alpha.at[sel].add(delta.astype(alpha.dtype))
        g = g + f32_matmul(delta, Qrows)
        return alpha, g, it + 1, panel_pg(alpha, g)

    def cond(state):
        _, _, it, pg = state
        return (pg > tol) & (it < inner)

    state0 = (alpha, g, jnp.zeros((), jnp.int32), panel_pg(alpha, g))
    alpha, g, it, _ = lax.while_loop(cond, body, state0)
    return alpha, g, it


def solve_box_qp_spill(
    op: GramOperator,
    C,
    alpha0: Optional[Array] = None,
    tol: float = 1e-3,
    max_iters: int = 500,
    block: int = 64,
    sweeps: int = 4,
    p=-1.0,
    device_budget_bytes: Optional[int] = None,
    max_rounds: int = 512,
    trace=None,
):
    """Out-of-core block CD for the box dual: Gram bounded by HOST memory.

    Raw kernel rows are computed once per panel (``rows_p`` rows sized to
    ``device_budget_bytes``), written through to a host-RAM numpy buffer
    (the spill tier) and served from a device panel LRU.  Each outer round
    is a Gauss-Seidel sweep over panels — a jitted within-panel block-CD
    sub-solve per panel, monotone in the global objective because the
    maintained gradient is exact — with the NEXT panel's host->device copy
    dispatched (async ``jax.device_put``) before the current sub-solve, so
    transfer overlaps compute.  After every sweep the gradient is recomputed
    from scratch (one streaming matvec) and convergence is judged on the
    full projected gradient, identical to the in-memory solver's criterion.

    Counter semantics on the returned ``SolveResult`` (panel units):
    ``cache_hits``/``cache_misses`` = device-tier panel hits / panels
    computed, ``cache_evictions`` = device panels dropped, ``spills`` =
    panels written to the host tier, ``spill_hits`` = panels re-loaded from
    it.

    ``trace`` (an ``obs.trace.ConvTrace``) records one sample per OUTER
    round at the fresh-gradient refresh — pg_max, objective, free-set size
    and the round's device-panel-hit delta.  Unlike the in-memory solvers
    this loop already host-syncs each round on ``pg`` by design, so the
    samples are recorded host-side at the same sync point; ``None`` is a
    strict no-op.
    """
    from repro.core.solver import (SolveResult, _broadcast, _n_free,
                                   objective, proj_grad)
    from repro.obs.spans import span
    from repro.obs.trace import trace_record

    X = op.Xd
    n = op.n_dual
    acc = jnp.promote_types(X.dtype, jnp.float32)
    budget = (op.budget_bytes if device_budget_bytes is None
              else int(device_budget_bytes))
    store = op.storage_dtype(acc)
    nb = op.kwidth                  # panel row space: base ids under dedup
    row_bytes = nb * jnp.dtype(store).itemsize
    block = max(1, min(block, n))
    rows_p = int(max(block, min(nb, budget // max(row_bytes, 1))))
    starts = list(range(0, nb, rows_p))
    cap_panels = max(1, budget // max(rows_p * row_bytes, 1))
    inner = max(4, rows_p // block)

    alpha = (jnp.zeros(n, X.dtype) if alpha0 is None
             else jnp.asarray(alpha0, X.dtype))
    cvec = _broadcast(C, n, X.dtype)
    pvec = _broadcast(p, n, X.dtype)

    def fresh_grad(alpha):
        return (op.matvec(alpha, via_base=op.dedup) + pvec).astype(acc)

    g = fresh_grad(alpha)
    host: dict = {}
    dev: OrderedDict = OrderedDict()
    hits = misses = evictions = spills = spill_hits = 0

    def evict_to(cap):
        nonlocal evictions
        while len(dev) > cap:
            dev.popitem(last=False)
            evictions += 1

    def fetch(pid):
        nonlocal hits, misses, spills, spill_hits
        if pid in dev:
            dev.move_to_end(pid)
            hits += 1
            return dev[pid]
        with span("spill/fetch_panel"):
            if pid in host:
                tile = jax.device_put(host[pid])
                spill_hits += 1
            else:
                idxp = jnp.clip(starts[pid] + jnp.arange(rows_p), 0, nb - 1)
                pts = op.Xb if op.dedup else op.Xd
                tile = op.kmat(pts[idxp], pts).astype(store)
                host[pid] = np.asarray(tile)      # write-through host spill
                spills += 1
                misses += 1
        dev[pid] = tile
        evict_to(cap_panels)
        return tile

    it_total = 0
    pg = float(jnp.max(jnp.abs(proj_grad(alpha, g, cvec))))
    rounds = 0
    hits_mark = 0
    while pg > tol and it_total < max_iters and rounds < max_rounds:
        for pid in range(len(starts)):
            tile = fetch(pid)
            nxt = (pid + 1) % len(starts)
            if len(starts) > 1 and nxt not in dev and nxt in host:
                # double buffer: device_put dispatches without blocking, so
                # the next panel's copy overlaps this panel's sub-solve
                dev[nxt] = jax.device_put(host[nxt])
                spill_hits += 1
                evict_to(cap_panels + 1)
            with span("spill/panel_solve"):
                alpha, g, its = _panel_block_cd(
                    op, tile, jnp.int32(starts[pid]), alpha, g, cvec, tol,
                    block=block, sweeps=sweeps, inner=inner, rows_p=rows_p)
                it_total += int(its)
            if it_total >= max_iters:
                break
        # refresh from scratch: panel sweeps keep the gradient exact in
        # infinite precision, but rounding drift accumulates over rounds
        g = fresh_grad(alpha)
        pg = float(jnp.max(jnp.abs(proj_grad(alpha, g, cvec))))
        rounds += 1
        if trace is not None:
            # this loop host-syncs on pg every round anyway; the sample
            # rides the same sync point (panel units for the hit delta)
            trace = trace_record(trace, pg_max=pg,
                                 objective=objective(alpha, g, pvec),
                                 n_free=_n_free(alpha, cvec),
                                 cache_hits=hits - hits_mark)
            hits_mark = hits

    i32 = lambda v: jnp.asarray(v, jnp.int32)
    return SolveResult(alpha, g, i32(it_total), jnp.asarray(pg, acc),
                       cache_hits=i32(hits), cache_misses=i32(misses),
                       cache_evictions=i32(evictions), spills=i32(spills),
                       spill_hits=i32(spill_hits), trace=trace)
