"""End-to-end Pallas/XLA parity for the streaming conquer engine.

Covers the ISSUE-1 acceptance criteria: ``solve_box_qp_matvec`` with
``use_pallas=True`` (fused cd_column_update + kernel_matvec) and with the
device-resident column cache must match the XLA reference path to 1e-5,
and the serving paths (decision_exact / decision_early) must agree across
backends.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    DCSVMConfig,
    Kernel,
    colcache,
    fit,
    gram_matvec,
    objective_value,
    solve_box_qp_matvec,
    solve_with_shrinking,
)
from repro.core.predict import decision_early, decision_exact
from repro.data import gaussian_mixture, train_test_split

KERNELS = [
    Kernel("rbf", gamma=4.0),
    Kernel("poly", gamma=1.0, degree=3, coef0=1.0),
    Kernel("linear"),
]


def _problem(n=160, d=7, key=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    # centered data keeps the poly/linear Grams well-conditioned
    X = (jax.random.uniform(k1, (n, d)) - 0.5) * 2.0
    y = jnp.sign(jax.random.normal(k2, (n,)))
    return X, y


# (n, d) per kernel sized so the Gram is generically full-rank and the dual
# optimum unique — otherwise both backends converge to *different* optima of
# a singular QP and alpha-level parity is meaningless (poly rank is
# C(d+deg, deg), linear rank is d)
PARITY_SHAPES = {"rbf": (160, 7), "poly": (64, 7), "linear": (32, 40)}


@pytest.mark.parametrize("kern", KERNELS, ids=[k.kind for k in KERNELS])
def test_matvec_solver_pallas_parity(kern):
    """use_pallas=True vs False: alphas within 1e-5 (acceptance criterion)."""
    n, d = PARITY_SHAPES[kern.kind]
    X, y = _problem(n=n, d=d)
    C = 2.0
    r_x = solve_box_qp_matvec(X, y, kern, C, tol=1e-6, max_iters=4000, block=16)
    r_p = solve_box_qp_matvec(X, y, kern, C, tol=1e-6, max_iters=4000, block=16,
                              use_pallas=True)
    np.testing.assert_allclose(np.asarray(r_p.alpha), np.asarray(r_x.alpha),
                               atol=1e-5)
    assert float(r_p.pg_max) <= 1e-6 * 1.5


@pytest.mark.parametrize("use_pallas", [False, True])
def test_matvec_solver_cache_parity(use_pallas):
    """Column cache on/off must not change the solution; counters must add up."""
    X, y = _problem(key=3)
    C = 2.0
    base = solve_box_qp_matvec(X, y, kern := Kernel("rbf", gamma=4.0), C,
                               tol=1e-6, max_iters=4000, block=16)
    res = solve_box_qp_matvec(X, y, kern, C, tol=1e-6, max_iters=4000, block=16,
                              use_pallas=use_pallas, cache_cap=X.shape[0])
    np.testing.assert_allclose(np.asarray(res.alpha), np.asarray(base.alpha),
                               atol=1e-5)
    hits, misses = int(res.cache_hits), int(res.cache_misses)
    assert hits + misses == int(res.iters) * 16
    # cap = n: once the active set is resident the solver must start hitting
    assert hits > 0


def test_matvec_solver_warm_start_pallas():
    """Warm-started fused path converges immediately at the optimum."""
    X, y = _problem(key=5)
    kern = Kernel("rbf", gamma=4.0)
    C = 1.0
    ref = solve_box_qp_matvec(X, y, kern, C, tol=1e-6, max_iters=4000, block=16)
    warm = solve_box_qp_matvec(X, y, kern, C, alpha0=ref.alpha, tol=1e-5,
                               max_iters=4000, block=16, use_pallas=True)
    assert int(warm.iters) == 0
    np.testing.assert_allclose(np.asarray(warm.alpha), np.asarray(ref.alpha))


def test_gram_matvec_pallas_parity():
    X, _ = _problem(n=130, d=9, key=7)
    v = jax.random.normal(jax.random.PRNGKey(8), (130,))
    for kern in KERNELS:
        a = gram_matvec(kern, X, v, num_chunks=4)
        b = gram_matvec(kern, X, v, use_pallas=True)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-5)


def test_objective_value_pallas_parity():
    X, y = _problem(n=120, key=9)
    a = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (120,))) * 0.1
    cfg_x = DCSVMConfig(kernel=Kernel("rbf", gamma=4.0), C=2.0, use_pallas=False)
    cfg_p = dataclasses.replace(cfg_x, use_pallas=True)
    fx = float(objective_value(cfg_x, X, y, a))
    fp = float(objective_value(cfg_p, X, y, a))
    assert abs(fx - fp) < 1e-4 * (1 + abs(fx))


def test_colcache_lru_semantics():
    """Unit-level: insert fills LRU slots, touch refreshes, eviction unmaps."""
    cache = colcache.init(cap=4, n=10)
    idx = jnp.array([1, 2])
    slots, hit = colcache.lookup(cache, idx)
    assert not bool(jnp.any(hit))
    rows = jnp.arange(20, dtype=jnp.float32).reshape(2, 10)
    cache = colcache.update(cache, idx, rows, jnp.asarray(False), slots, hit)
    assert int(cache.misses) == 2 and int(cache.hits) == 0

    # both rows now resident, served block counts as hits and touches stamps
    slots, hit = colcache.lookup(cache, idx)
    assert bool(jnp.all(hit))
    served_rows = cache.cols[slots]
    np.testing.assert_array_equal(np.asarray(served_rows), np.asarray(rows))
    cache = colcache.update(cache, idx, served_rows, jnp.asarray(True), slots, hit)
    assert int(cache.hits) == 2

    # insert 2+2 more rows: cap=4 forces eviction of the original two
    for a, b in ((3, 4), (5, 6)):
        idx2 = jnp.array([a, b])
        slots2, hit2 = colcache.lookup(cache, idx2)
        cache = colcache.update(cache, idx2, rows, jnp.asarray(False), slots2, hit2)
    _, hit = colcache.lookup(cache, jnp.array([1, 2]))
    assert not bool(jnp.any(hit)), "LRU rows must be evicted and unmapped"
    _, hit2 = colcache.lookup(cache, jnp.array([3, 4, 5, 6]))
    assert bool(jnp.all(hit2))


def test_fit_backend_parity_and_cache_stats():
    """fit() through the matvec conquer path: XLA vs Pallas backends agree and
    the level-0 stats surface cache hit counters."""
    X, y = gaussian_mixture(jax.random.PRNGKey(0), 700, d=8, modes_per_class=4,
                            spread=0.15)
    Xtr, ytr, Xte, yte = train_test_split(jax.random.PRNGKey(1), X, y)
    kern = Kernel("rbf", gamma=8.0)
    cfg_x = DCSVMConfig(kernel=kern, C=4.0, k=4, levels=1, m=200, tol=1e-4,
                        use_pallas=False, full_gram_threshold=64, block=32,
                        col_cache_cap=512)
    cfg_p = dataclasses.replace(cfg_x, use_pallas=True)
    m_x = fit(cfg_x, Xtr, ytr)
    m_p = fit(cfg_p, Xtr, ytr)
    st = m_x.level_stats[-1]
    assert {"cache_hits", "cache_misses", "cache_hit_rate"} <= set(st)
    assert 0.0 <= st["cache_hit_rate"] <= 1.0
    # same conquer trajectory to CD tolerance on both backends
    assert float(jnp.max(jnp.abs(m_x.alpha - m_p.alpha))) < 5e-4

    d_x = decision_exact(m_x, Xte, use_pallas=False)
    d_p = decision_exact(m_x, Xte, use_pallas=True)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_x),
                               rtol=1e-4, atol=1e-4)


def test_decision_early_pallas_parity():
    X, y = gaussian_mixture(jax.random.PRNGKey(2), 600, d=8, modes_per_class=4,
                            spread=0.15)
    Xtr, ytr, Xte, _ = train_test_split(jax.random.PRNGKey(3), X, y)
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=8.0), C=4.0, k=4, levels=1,
                      m=200, tol=1e-3, early_stop_level=1, use_pallas=False)
    model = fit(cfg, Xtr, ytr)
    d_x = decision_early(model, Xte, use_pallas=False)
    d_p = decision_early(model, Xte, use_pallas=True)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_x),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ISSUE-3: signed-weight parity on the generalized (task) dual.  The SVR dual
# runs the SAME fused kernels with a mixed-sign s vector (+1/-1 mirrored
# coordinate pairs) over duplicated, non-tile-aligned rows — pin Pallas/XLA
# parity there too.
# ---------------------------------------------------------------------------

def _svr_problem(n=75, d=5, key=21, eps=0.05, C=2.0):
    from repro.core.tasks import EpsilonSVR

    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    X = (jax.random.uniform(k1, (n, d)) - 0.5) * 2.0
    y = jnp.sum(jnp.sin(2.0 * X), axis=-1) / d \
        + 0.02 * jax.random.normal(k2, (n,))
    task = EpsilonSVR(eps=eps)
    td = task.build(X, y[None, :], C)
    return td


@pytest.mark.parametrize("kern", KERNELS, ids=[k.kind for k in KERNELS])
def test_cd_column_update_signed_weights_parity(kern):
    """Fused cd_column_update with a mixed-sign s vector (the SVR case) on
    non-tile-aligned shapes: Pallas == XLA reference to 1e-5."""
    from repro.kernels import ops as kops

    td = _svr_problem(n=83, d=7)           # nd = 166: not a multiple of 8/128
    s = td.S[0]
    idx = jnp.asarray([3, 82, 83, 165, 40, 123, 7])   # mirrored pairs included
    Xb, sb = td.Xd[idx], s[idx]
    delta = jax.random.normal(jax.random.PRNGKey(5), (idx.shape[0],)) * 0.1
    got = kops.cd_column_update(td.Xd, s, Xb, sb * delta, kern)
    Kb = kern.pairwise(td.Xd, Xb)
    want = s * (Kb @ (sb * delta))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kern", KERNELS, ids=[k.kind for k in KERNELS])
def test_matvec_solver_svr_pallas_parity(kern):
    """solve_box_qp_matvec on the 2n SVR dual (signed weights through the
    fused kernels, per-coordinate p): XLA and Pallas agree on the collapsed
    beta to 1e-5 (beta — not the raw 2n dual — is the well-posed quantity:
    Q is rank-deficient by construction on duplicated rows)."""
    td = _svr_problem(n=60, d=5)
    s, p, cvec = td.S[0], td.P[0], td.Cvec[0]
    r_x = solve_box_qp_matvec(td.Xd, s, kern, cvec, tol=1e-6, max_iters=4000,
                              block=16, p=p)
    r_p = solve_box_qp_matvec(td.Xd, s, kern, cvec, tol=1e-6, max_iters=4000,
                              block=16, p=p, use_pallas=True)
    assert float(r_p.pg_max) <= 1e-6 * 1.5
    beta_x = np.asarray(td.collapse(r_x.alpha[None, :])[0])
    beta_p = np.asarray(td.collapse(r_p.alpha[None, :])[0])
    np.testing.assert_allclose(beta_p, beta_x, atol=1e-5)


def test_svr_fit_backend_parity():
    """End-to-end epsilon-SVR fit through the divide/conquer driver: XLA and
    Pallas backends produce the same decision function."""
    from repro.core.tasks import EpsilonSVR
    from repro.data import friedman1

    X, y = friedman1(jax.random.PRNGKey(4), 300)
    kern = Kernel("rbf", gamma=1.0)
    cfg_x = DCSVMConfig(kernel=kern, C=4.0, k=3, levels=1, m=150, tol=1e-4,
                        kmeans_iters=10, use_pallas=False,
                        full_gram_threshold=64, block=32)
    cfg_p = dataclasses.replace(cfg_x, use_pallas=True)
    task = EpsilonSVR(eps=0.1)
    m_x = fit(cfg_x, X, y, task=task)
    m_p = fit(cfg_p, X, y, task=task)
    d_x = decision_exact(m_x, X[:64], use_pallas=False)
    d_p = decision_exact(m_p, X[:64], use_pallas=True)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_x),
                               rtol=1e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# ISSUE-4: pairwise (equality-constrained) CD parity.  The pairwise engine
# runs the SAME fused kernels (cd_column_update rank-2 updates, streaming
# kernel_matvec gradient init) with mixed-sign equality coefficients a over
# non-tile-aligned shapes — pin Pallas/XLA parity and the on-device property.
# ---------------------------------------------------------------------------

def _eq_problem(kern, key=31):
    """Non-tile-aligned n per kernel kind (full-rank Grams => the strictly
    convex equality QP has a unique optimum, so alpha parity is well
    posed), mixed-sign a bounded away from zero, interior target d."""
    shapes = {"rbf": (83, 7), "poly": (61, 7), "linear": (37, 40)}
    n, d_feat = shapes[kern.kind]
    rng = np.random.default_rng(key)
    X = jnp.asarray(((rng.uniform(size=(n, d_feat)) - 0.5) * 2.0)
                    .astype(np.float32))
    y = jnp.asarray(np.where(rng.uniform(size=n) > 0.5, 1.0, -1.0)
                    .astype(np.float32))
    a = jnp.asarray((np.where(rng.uniform(size=n) > 0.5, 1.0, -1.0)
                     * rng.uniform(0.5, 1.5, size=n)).astype(np.float32))
    ac = np.asarray(a, np.float64)
    lo, hi = np.minimum(ac, 0).sum(), np.maximum(ac, 0).sum()
    d = float(lo + 0.4 * (hi - lo))
    return X, y, a, d


@pytest.mark.parametrize("kern", KERNELS, ids=[k.kind for k in KERNELS])
def test_eq_pairwise_cd_pallas_parity(kern):
    """solve_eq_qp_matvec with mixed-sign a on non-tile-aligned shapes:
    use_pallas=True (fused rank-2 cd_column_update + streaming matvec init)
    must match the XLA reference path to 1e-5, stay box- and equality-
    feasible, and reach the same stopping residual.  tol is scale-aware:
    the poly kernel's values reach (1 + d)^3 here, so 1e-6 sits below the
    f32 resolution of the multiplier bracket."""
    from repro.core import solve_eq_qp_matvec

    tol = {"rbf": 1e-6, "poly": 1e-5, "linear": 1e-6}[kern.kind]
    X, y, a, d = _eq_problem(kern)
    r_x = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=tol,
                             max_iters=100_000)
    r_p = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=tol,
                             max_iters=100_000, use_pallas=True)
    np.testing.assert_allclose(np.asarray(r_p.alpha), np.asarray(r_x.alpha),
                               atol=1e-5)
    for res in (r_x, r_p):
        u = np.asarray(res.alpha, np.float64)
        an = np.asarray(a, np.float64)
        assert int(res.iters) < 100_000
        assert u.min() >= -1e-7 and u.max() <= 1.0 + 1e-6
        scale = np.abs(an * u).sum() + abs(d)
        assert abs(an @ u - d) <= 4e-6 * max(scale, 1.0)
        assert float(res.pg_max) <= tol * 1.5


def test_eq_pairwise_warm_start_pallas():
    """Warm-started fused pairwise path converges immediately at the
    optimum (the feasible-projection entry step must not perturb it)."""
    from repro.core import solve_eq_qp_matvec

    kern = Kernel("rbf", gamma=2.0)
    X, y, a, d = _eq_problem(kern, key=33)
    ref = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=1e-5,
                             max_iters=200_000)
    warm = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, alpha0=ref.alpha,
                              tol=1e-4, max_iters=200_000, use_pallas=True)
    assert int(warm.iters) <= 2
    np.testing.assert_allclose(np.asarray(warm.alpha), np.asarray(ref.alpha),
                               atol=1e-5)


def test_eq_solve_loop_stays_on_device():
    """Satellite: the whole pairwise solve (projection, selection, rank-2
    updates, feasibility restore) is ONE jitted program — no device-to-host
    transfer once compiled."""
    from repro.core import solve_eq_qp_matvec

    kern = Kernel("rbf", gamma=2.0)
    X, y, a, d = _eq_problem(kern, key=35)
    args = (X, y, kern, 1.0, a, d)
    kw = dict(tol=1e-5, max_iters=50_000, use_pallas=True)
    warm = solve_eq_qp_matvec(*args, **kw)       # compile outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        res = solve_eq_qp_matvec(*args, **kw)
    np.testing.assert_allclose(np.asarray(res.alpha), np.asarray(warm.alpha))


def test_oneclass_fit_backend_parity():
    """End-to-end one-class fit through the divide/conquer driver: XLA and
    Pallas backends produce the same decision function and offset."""
    from repro.core import OneClassSVM
    from repro.core.predict import decision_exact
    from repro.data import gaussian_with_outliers

    X, _ = gaussian_with_outliers(jax.random.PRNGKey(6), 700)
    kern = Kernel("rbf", gamma=4.0)
    cfg_x = DCSVMConfig(kernel=kern, k=3, levels=1, m=250, tol=1e-4,
                        kmeans_iters=8, use_pallas=False,
                        full_gram_threshold=64)
    cfg_p = dataclasses.replace(cfg_x, use_pallas=True)
    task = OneClassSVM(nu=0.1)
    m_x = fit(cfg_x, X, task=task)
    m_p = fit(cfg_p, X, task=task)
    assert abs(m_x.rho - m_p.rho) < 1e-3 * (1 + abs(m_x.rho))
    d_x = decision_exact(m_x, X[:64], use_pallas=False)
    d_p = decision_exact(m_p, X[:64], use_pallas=True)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_x),
                               rtol=1e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# ISSUE-5: rank-2B blocked pairwise CD parity.  The blocked engine routes its
# gradient update through the SAME fused cd_column_update kernel with a
# (2B,) delta instead of a rank-2 one — pin Pallas/XLA parity on mixed-sign
# non-tile-aligned shapes, warm starts, and the on-device property.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kern", KERNELS, ids=[k.kind for k in KERNELS])
def test_eq_block_cd_pallas_parity(kern):
    """solve_eq_qp_matvec with block=8 (fused rank-2B cd_column_update +
    streaming matvec init) must match the XLA reference blocked path to
    1e-5 on mixed-sign non-tile-aligned shapes, stay box- and equality-
    feasible, and reach the same stopping residual.  tol is scale-aware:
    poly/linear kernel values reach ~(1+d)^3 / ~d here, so the f32 noise
    of measuring the multiplier gap itself sits above 1e-6."""
    from repro.core import solve_eq_qp_matvec

    tol = {"rbf": 1e-6, "poly": 1e-5, "linear": 1e-5}[kern.kind]
    X, y, a, d = _eq_problem(kern)
    r_x = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=tol,
                             max_iters=50_000, block=8)
    r_p = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=tol,
                             max_iters=50_000, block=8, use_pallas=True)
    np.testing.assert_allclose(np.asarray(r_p.alpha), np.asarray(r_x.alpha),
                               atol=1e-5)
    an = np.asarray(a, np.float64)
    for res in (r_x, r_p):
        u = np.asarray(res.alpha, np.float64)
        assert int(res.iters) < 50_000
        assert u.min() >= -1e-7 and u.max() <= 1.0 + 1e-6
        scale = np.abs(an * u).sum() + abs(d)
        assert abs(an @ u - d) <= 4e-6 * max(scale, 1.0)
        assert float(res.pg_max) <= tol * 1.5


def test_eq_block_matches_rank2_across_backends():
    """The blocked engine and the rank-2 engine land on the same optimum of
    the strictly convex equality QP, on both backends."""
    from repro.core import solve_eq_qp_matvec

    kern = Kernel("rbf", gamma=2.0)
    X, y, a, d = _eq_problem(kern, key=37)
    ref = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=1e-6,
                             max_iters=200_000)
    for up in (False, True):
        blk = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=1e-6,
                                 max_iters=50_000, block=8, use_pallas=up)
        np.testing.assert_allclose(np.asarray(blk.alpha),
                                   np.asarray(ref.alpha), atol=2e-5)


def test_eq_block_warm_start_pallas():
    """Warm-started fused rank-2B path converges immediately at the optimum
    (the grouped feasible-projection entry step must not perturb it)."""
    from repro.core import solve_eq_qp_matvec

    kern = Kernel("rbf", gamma=2.0)
    X, y, a, d = _eq_problem(kern, key=39)
    ref = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, tol=1e-5,
                             max_iters=50_000, block=8)
    warm = solve_eq_qp_matvec(X, y, kern, 1.0, a, d, alpha0=ref.alpha,
                              tol=1e-4, max_iters=50_000, block=8,
                              use_pallas=True)
    assert int(warm.iters) <= 2
    np.testing.assert_allclose(np.asarray(warm.alpha), np.asarray(ref.alpha),
                               atol=1e-5)


def test_eq_block_solve_loop_stays_on_device():
    """The whole blocked solve (grouped projection, top-k pair selection,
    2Bx2B sub-QP, rank-2B updates, feasibility restore) is ONE jitted
    program — no device-to-host transfer once compiled."""
    from repro.core import solve_eq_qp_matvec

    kern = Kernel("rbf", gamma=2.0)
    X, y, a, d = _eq_problem(kern, key=41)
    args = (X, y, kern, 1.0, a, d)
    kw = dict(tol=1e-5, max_iters=50_000, block=8, use_pallas=True)
    warm = solve_eq_qp_matvec(*args, **kw)       # compile outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        res = solve_eq_qp_matvec(*args, **kw)
    np.testing.assert_allclose(np.asarray(res.alpha), np.asarray(warm.alpha))


def test_oneclass_blocked_fit_backend_parity():
    """End-to-end one-class fit with eq_block_size=8 through the divide/
    conquer driver: XLA and Pallas backends agree, and the blocked fit
    matches the rank-2 fit's decision function."""
    from repro.core import OneClassSVM
    from repro.data import gaussian_with_outliers

    X, _ = gaussian_with_outliers(jax.random.PRNGKey(8), 700)
    kern = Kernel("rbf", gamma=4.0)
    cfg_x = DCSVMConfig(kernel=kern, k=3, levels=1, m=250, tol=1e-4,
                        kmeans_iters=8, use_pallas=False,
                        full_gram_threshold=64, eq_block_size=8)
    cfg_p = dataclasses.replace(cfg_x, use_pallas=True)
    cfg_r2 = dataclasses.replace(cfg_x, eq_block_size=1)
    task = OneClassSVM(nu=0.1)
    m_x = fit(cfg_x, X, task=task)
    m_p = fit(cfg_p, X, task=task)
    m_r2 = fit(cfg_r2, X, task=task)
    assert abs(m_x.rho - m_p.rho) < 1e-3 * (1 + abs(m_x.rho))
    assert abs(m_x.rho - m_r2.rho) < 1e-3 * (1 + abs(m_x.rho))
    d_x = decision_exact(m_x, X[:64], use_pallas=False)
    d_p = decision_exact(m_p, X[:64], use_pallas=True)
    d_r = decision_exact(m_r2, X[:64], use_pallas=False)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_x),
                               rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(d_r), np.asarray(d_x),
                               rtol=1e-3, atol=5e-3)


def test_shrinking_iters_accumulate_on_device():
    """Satellite: solve_with_shrinking returns a device scalar equal to the
    sum of per-round iteration counts (no per-round host sync)."""
    X, y = _problem(n=100, key=13)
    K = Kernel("rbf", gamma=4.0).pairwise(X, X) + 1e-3 * jnp.eye(100)
    Q = (y[:, None] * y[None, :]) * K
    res = solve_with_shrinking(Q, 2.0, tol=1e-4, max_iters=50_000, rounds=3)
    assert isinstance(res.iters, jax.Array)
    assert res.iters.dtype == jnp.int32
    assert int(res.iters) > 0


# ---------------------------------------------------------------------------
# The B x B sub-QP sweep as one Pallas kernel (kernels/small_qp.py)
# ---------------------------------------------------------------------------

def _subqp(B, seed=0, cb="vec", start="inside", zero_diag=False):
    """An RBF-shaped signed (B, B) block, perturbed so that it is not bitwise
    symmetric (the kernel must read Qbb's columns, not its rows), with its
    gradient, start and box."""
    r = np.random.default_rng(seed)
    Z = r.uniform(size=(B, 5))
    s = np.sign(r.normal(size=B))
    Q = s[:, None] * s[None, :] * np.exp(
        -2.0 * ((Z[:, None] - Z[None]) ** 2).sum(-1))
    Q = Q + 1e-4 * r.normal(size=(B, B))
    if zero_diag:
        Q[B // 2, B // 2] = 0.0          # the kernel meets the 1e-12 guard
    c = r.uniform(0.5, 4.0, size=B) if cb == "vec" else np.full(B, 2.0)
    a = {"zero": np.zeros(B), "box": c,
         "inside": r.uniform(size=B) * c * (r.uniform(size=B) < 0.6)}[start]
    g = r.normal(size=B)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    return f32(Q), f32(g), f32(a), (f32(c) if cb == "vec" else 2.0)


SUBQP_CASES = (
    [dict(B=B, sweeps=w) for B in (1, 7, 64, 100, 256) for w in (1, 4)]
    + [dict(B=64, sweeps=4, cb="scalar"),
       dict(B=64, sweeps=4, start="zero"),
       dict(B=64, sweeps=4, start="box"),
       dict(B=7, sweeps=4, start="box", cb="scalar"),
       dict(B=64, sweeps=4, zero_diag=True),
       dict(B=100, sweeps=4, vmap=3)])


@pytest.mark.parametrize(
    "case", SUBQP_CASES,
    ids=["-".join(f"{k}{v}" for k, v in c.items()) for c in SUBQP_CASES])
def test_small_qp_sweep_bitwise_matches_xla_loop(case):
    """``ops.small_qp_sweep`` (interpret mode here) takes the XLA loop's
    steps in the XLA loop's order with its arithmetic: bitwise equal."""
    from repro.core.solver import _broadcast, _solve_small_qp
    from repro.kernels import ops

    case = dict(case)
    B, sweeps, nv = case.pop("B"), case.pop("sweeps"), case.pop("vmap", 0)

    def both(Q, g, a, c):
        want = _solve_small_qp(Q, g, a, c, sweeps)
        got = ops.small_qp_sweep(Q.T, g, a, _broadcast(c, B, Q.dtype),
                                 jnp.maximum(jnp.diagonal(Q), 1e-12),
                                 sweeps=sweeps)
        return want, got

    if nv:
        probs = [_subqp(B, seed=s, **case) for s in range(nv)]
        args = [jnp.stack(x) for x in zip(*probs)]
        want, got = jax.vmap(both)(*args)
    else:
        Q, g, a, c = _subqp(B, **case)
        assert B == 1 or not bool(jnp.all(Q == Q.T))
        want, got = both(Q, g, a, c)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matvec_solver_small_qp_kernel_parity():
    """The operator solver with the Pallas backend (fused column update and
    the ``small_qp_sweep`` kernel) against the XLA backend on a small RBF
    problem: the same iterations, alpha within 1e-6."""
    X, y = _problem(n=160, d=7, key=5)
    kern = Kernel("rbf", gamma=4.0)
    r_x = solve_box_qp_matvec(X, y, kern, 2.0, tol=1e-3, max_iters=500,
                              block=16)
    r_p = solve_box_qp_matvec(X, y, kern, 2.0, tol=1e-3, max_iters=500,
                              block=16, use_pallas=True)
    assert int(r_p.iters) == int(r_x.iters)
    np.testing.assert_allclose(np.asarray(r_p.alpha), np.asarray(r_x.alpha),
                               atol=1e-6)


def test_fit_level0_stats_name_the_subqp_path():
    """A fit's level-0 stats say which sweep ran: the kernel on the Pallas
    operator path, the XLA loop on the XLA backend and on the dense path."""
    X, y = gaussian_mixture(jax.random.PRNGKey(4), 300, d=6,
                            modes_per_class=3, spread=0.2)
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=4.0), C=2.0, k=2, levels=1,
                      m=100, tol=1e-3, full_gram_threshold=64,
                      use_pallas=True)
    seen = {}
    for name, c in {"pallas": cfg,
                    "xla": dataclasses.replace(cfg, use_pallas=False),
                    "dense": dataclasses.replace(cfg,
                                                 full_gram_threshold=10**6)
                    }.items():
        seen[name] = fit(c, X, y).level_stats[-1]["subqp"]
    assert seen == {"pallas": "pallas", "xla": "xla", "dense": "xla"}
