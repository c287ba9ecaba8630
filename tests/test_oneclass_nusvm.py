"""Equality-constrained tasks end-to-end: one-class SVM and nu-SVC (ISSUE-4).

(a) nu/C equivalence regression: a C-SVC solve implies nu = sum(alpha)/(C n);
    the bias-free NuSVC at that nu must reproduce the decision function up
    to the positive scale C (KKT mapping beta = alpha / C);
(b) one-class SVM vs sklearn/libsvm: identical parameterization
    (0 <= alpha <= 1, sum alpha = nu n), so decision functions are directly
    comparable on gaussian_with_outliers;
(c) acceptance criterion: multilevel one-class DC-SVM matches a dense
    reference equality-constrained solve to 1e-4 in decision values;
(d) the nu property (outlier fraction <= nu <= SV fraction), rho recovery,
    per-cluster rho for early prediction, and the ocsvm serving export.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    DCSVMConfig,
    Kernel,
    NuSVC,
    OneClassSVM,
    accuracy,
    f1,
    fit,
    kkt_residual_eq,
    predict_early,
    predict_exact,
    recall,
    solve_box_qp,
    solve_eq_qp,
)
from repro.core.predict import decision_early, decision_exact
from repro.core.solver import equality_rho
from repro.data import gaussian_mixture, gaussian_with_outliers, \
    train_test_split


def _ocsvm_problem(n=500, key=0, spread=0.07, outlier_frac=0.06):
    X, y = gaussian_with_outliers(jax.random.PRNGKey(key), n, spread=spread,
                                  outlier_frac=outlier_frac)
    return X, y


# ---------------------------------------------------------------------------
# (a) nu/C equivalence
# ---------------------------------------------------------------------------

def test_nu_c_equivalence_decision_functions():
    """Fit C-SVC at cost C, read off nu = sum(alpha)/(C n), fit NuSVC at
    that nu: the decision functions must match to 1e-4 on held-out points
    after removing the positive scale C (beta = alpha / C maps one KKT
    system onto the other)."""
    X, y = gaussian_mixture(jax.random.PRNGKey(0), 400, d=6,
                            modes_per_class=3, spread=0.15)
    Xtr, ytr, Xte, _ = train_test_split(jax.random.PRNGKey(1), X, y)
    n = Xtr.shape[0]
    kern = Kernel("rbf", gamma=4.0)
    C = 2.0
    cfg = DCSVMConfig(kernel=kern, C=C, k=3, levels=1, m=200, tol=1e-7,
                      kmeans_iters=8, use_pallas=False)
    m_c = fit(cfg, Xtr, ytr)
    nu = float(m_c.alpha.sum()) / (C * n)
    assert 0.0 < nu < 1.0
    m_nu = fit(cfg, Xtr, ytr, task=NuSVC(nu=nu))
    # the mass constraint holds exactly
    assert abs(float(m_nu.alpha.sum()) - nu * n) <= 1e-3
    f_c = np.asarray(decision_exact(m_c, Xte), np.float64)
    f_nu = np.asarray(decision_exact(m_nu, Xte), np.float64)
    np.testing.assert_allclose(C * f_nu, f_c, atol=1e-4)


def test_nusvc_fit_accuracy_and_mass():
    """NuSVC through the multilevel driver: accurate on the mixture and the
    dual mass lands exactly on nu * n (the equality the box dual cannot
    express)."""
    X, y = gaussian_mixture(jax.random.PRNGKey(2), 900, d=8,
                            modes_per_class=4, spread=0.12)
    Xtr, ytr, Xte, yte = train_test_split(jax.random.PRNGKey(3), X, y)
    kern = Kernel("rbf", gamma=8.0)
    cfg = DCSVMConfig(kernel=kern, k=3, levels=2, m=300, tol=1e-5,
                      kmeans_iters=8, use_pallas=False)
    nu = 0.3
    model = fit(cfg, Xtr, ytr, task=NuSVC(nu=nu))
    n = Xtr.shape[0]
    assert abs(float(model.alpha.sum()) - nu * n) <= 1e-2
    assert accuracy(yte, predict_exact(model, Xte)) >= 0.95
    # nu bounds the support mass: at least nu*n coordinates-worth of mass,
    # each coordinate capped at 1 => at least nu*n support vectors
    assert len(model.sv_index) >= nu * n - 1


def test_nusvc_rejects_bad_nu():
    X = jnp.zeros((4, 2))
    y = jnp.asarray([1.0, -1.0, 1.0, -1.0])
    for bad in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError):
            NuSVC(nu=bad).build(X, y[None, :], 1.0)
    with pytest.raises(ValueError):
        OneClassSVM(nu=0.0).build(X, y[None, :], 1.0)
    # with bias: class-balance feasibility nu <= 2 min(n+, n-)/n
    y_imb = jnp.asarray([1.0, 1.0, 1.0, -1.0])
    with pytest.raises(ValueError):
        NuSVC(nu=0.9, with_bias=True).build(X, y_imb[None, :], 1.0)
    td = NuSVC(nu=0.4, with_bias=True).build(X, y_imb[None, :], 1.0)
    assert td.n_groups == 2 and td.Geq is not None


# ---------------------------------------------------------------------------
# (a') two-constraint nu-SVC: the bias restored via per-label-group
# constraints sum_{y=+1} u = sum_{y=-1} u = nu n / 2 (ISSUE-5)
# ---------------------------------------------------------------------------

def _nusvc_problem(n=400, key=0, d=6):
    X, y = gaussian_mixture(jax.random.PRNGKey(key), n, d=d,
                            modes_per_class=3, spread=0.15)
    return train_test_split(jax.random.PRNGKey(key + 1), X, y)


def _nusvc_margin_and_bias(model, kern):
    """(rho_m, b) of a fitted two-constraint NuSVC from the per-group
    multiplier brackets at the returned dual: r_+/- are the free-SV levels
    of g_i per class group, rho_m = (r_+ + r_-)/2, b = (r_- - r_+)/2."""
    from repro.core.solver import equality_rho_grouped
    from repro.core.kernels import gram_matvec

    td = model.task.build(model.X, model.y[None, :], model.config.C)
    s = td.S[0]
    g = s * gram_matvec(kern, model.X, s * model.alpha) + td.P[0]
    r = equality_rho_grouped(model.alpha, g, td.Cvec[0], td.A[0],
                             td.group_ids[0], 2)
    return 0.5 * float(r[0] + r[1]), 0.5 * float(r[1] - r[0])


def test_nusvc_bias_decision_matches_sklearn():
    """Decision parity vs sklearn.svm.NuSVC (rbf): libsvm rescales the
    dual by the margin rho_m so free SVs sit at +/-1 — dividing our raw
    decision (sum u_i y_i K + b) by rho_m must reproduce sklearn's
    decision_function to 2e-4, and b/rho_m its intercept."""
    sklearn_svm = pytest.importorskip("sklearn.svm")

    Xtr, ytr, Xte, _ = _nusvc_problem(n=400, key=0)
    gamma, nu = 4.0, 0.3
    kern = Kernel("rbf", gamma=gamma)
    cfg = DCSVMConfig(kernel=kern, k=3, levels=1, m=200, tol=1e-7,
                      kmeans_iters=8, use_pallas=False)
    model = fit(cfg, Xtr, ytr, task=NuSVC(nu=nu, with_bias=True))
    rho_m, b = _nusvc_margin_and_bias(model, kern)
    assert rho_m > 0
    # model.rho is -b: the uniform offset convention f = sum beta K - rho
    assert abs(model.rho + b) <= 1e-5 * (1 + abs(b))
    f_raw = np.asarray(decision_exact(model, Xte), np.float64)  # already + b
    f_ours = f_raw / rho_m

    sk = sklearn_svm.NuSVC(nu=nu, kernel="rbf", gamma=gamma,
                           tol=1e-8).fit(np.asarray(Xtr), np.asarray(ytr))
    f_sk = sk.decision_function(np.asarray(Xte))
    np.testing.assert_allclose(f_ours, f_sk, atol=2e-4)
    assert abs(b / rho_m - float(sk.intercept_[0])) <= 2e-4


def test_nusvc_bias_group_feasibility_sandwich():
    """Per class group g: the group mass lands exactly on nu n / 2, and the
    nu sandwich holds groupwise — #(bound SVs in g) <= nu n / 2 <= #(SVs
    in g) (each coordinate is capped at 1, so the mass constraint forces
    at least nu n/2 supports and at most nu n/2 cap-pinned coordinates)."""
    Xtr, ytr, Xte, yte = _nusvc_problem(n=600, key=4, d=8)
    n = Xtr.shape[0]
    nu = 0.3
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=8.0), k=3, levels=2, m=250,
                      tol=1e-5, kmeans_iters=8, use_pallas=False,
                      eq_block_size=8)
    model = fit(cfg, Xtr, ytr, task=NuSVC(nu=nu, with_bias=True))
    u = np.asarray(model.alpha, np.float64)
    yn = np.asarray(model.y)
    for sign in (1.0, -1.0):
        grp = yn * sign > 0
        mass = u[grp].sum()
        assert abs(mass - nu * n / 2) <= 1e-2, (sign, mass)
        n_sv = int((u[grp] > 1e-6).sum())
        n_bound = int((u[grp] >= 1.0 - 1e-6).sum())
        assert n_bound <= nu * n / 2 + 1, sign
        assert n_sv >= nu * n / 2 - 1, sign
    assert accuracy(yte, predict_exact(model, Xte)) >= 0.9


def test_nusvc_bias_serving_round_trip():
    """export_serving_model/serve_batch with the recovered bias: the export
    carries rho = -b through the offset-threshold path (shared with
    one-class), exact serving reproduces decision_exact, predictions are
    the +/-1 sign labels, and the early export carries per-cluster
    offsets."""
    from repro.launch.serve_svm import export_serving_model, serve_batch

    Xtr, ytr, Xte, _ = _nusvc_problem(n=500, key=8)
    kern = Kernel("rbf", gamma=4.0)
    cfg = DCSVMConfig(kernel=kern, k=3, levels=1, m=200, tol=1e-5,
                      kmeans_iters=8, use_pallas=False, eq_block_size=4)
    task = NuSVC(nu=0.3, with_bias=True)
    model = fit(cfg, Xtr, ytr, task=task)
    assert model.rho is not None
    sm = export_serving_model(model, with_bcm=False)
    assert float(sm.rho) == pytest.approx(model.rho, abs=1e-7)
    Xq = Xte[:100]
    pred, scores = serve_batch(sm, Xq, kern, "exact")
    assert bool(jnp.all(jnp.abs(pred) == 1.0))
    d_ref = decision_exact(model, Xq)
    np.testing.assert_allclose(np.asarray(scores[:, 0]), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(pred), np.asarray(predict_exact(model, Xq)))

    model_e = fit(dataclasses.replace(cfg, early_stop_level=1), Xtr, ytr,
                  task=task)
    assert model_e.rho_clusters is not None
    sm_e = export_serving_model(model_e, with_bcm=False)
    assert sm_e.rho_c.shape == (model_e.partition.k,)
    pred_e, scores_e = serve_batch(sm_e, Xq, kern, "early")
    np.testing.assert_allclose(np.asarray(scores_e[:, 0]),
                               np.asarray(decision_early(model_e, Xq)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# (b) one-class vs sklearn
# ---------------------------------------------------------------------------

def test_oneclass_dense_matches_sklearn_decision_boundary():
    """Same parameterization as libsvm (0 <= a <= 1, sum a = nu n): our
    dense equality solve must reproduce sklearn's OneClassSVM decision
    function and inlier/outlier boundary on gaussian_with_outliers."""
    sklearn_svm = pytest.importorskip("sklearn.svm")

    X, y = _ocsvm_problem(n=300, key=5)
    n = X.shape[0]
    gamma, nu = 2.0, 0.25
    kern = Kernel("rbf", gamma=gamma)
    K = kern.pairwise(X, X)
    res = solve_eq_qp(K, 1.0, 1.0, nu * n, tol=1e-7, max_iters=400_000)
    rho = float(equality_rho(res.alpha, res.grad, jnp.ones(n), jnp.ones(n)))
    f_ours = np.asarray(K, np.float64) @ np.asarray(res.alpha, np.float64) \
        - rho

    sk = sklearn_svm.OneClassSVM(kernel="rbf", gamma=gamma, nu=nu,
                                 tol=1e-9).fit(np.asarray(X))
    f_sk = sk.decision_function(np.asarray(X))
    np.testing.assert_allclose(f_ours, f_sk, atol=2e-4)
    # the decision boundary agrees wherever sklearn is not razor-thin
    clear = np.abs(f_sk) > 1e-3
    assert clear.mean() > 0.5
    assert (np.sign(f_ours[clear]) == np.sign(f_sk[clear])).all()


# ---------------------------------------------------------------------------
# (c) acceptance: multilevel DC-SVM vs dense reference to 1e-4
# ---------------------------------------------------------------------------

def test_oneclass_multilevel_matches_dense_reference():
    """Acceptance criterion: the multilevel (divide -> conquer) one-class
    fit matches a direct dense equality-constrained solve to 1e-4 in
    decision values, and |sum alpha - nu n| <= 1e-6.  x64: at f32 the KKT
    residual itself cannot be measured below ~1e-4 at these scales."""
    with jax.enable_x64():
        X, y = _ocsvm_problem(n=400, key=0)
        X = jnp.asarray(X, jnp.float64)
        n = X.shape[0]
        nu = 0.12
        kern = Kernel("rbf", gamma=4.0)
        cfg = DCSVMConfig(kernel=kern, k=3, levels=2, m=250, tol=1e-8,
                          kmeans_iters=8, use_pallas=False)
        model = fit(cfg, X, task=OneClassSVM(nu=nu))
        assert model.alpha.dtype == jnp.float64
        assert abs(float(model.alpha.sum()) - nu * n) <= 1e-6

        K = kern.pairwise(X, X)
        ref = solve_eq_qp(K, 1.0, 1.0, nu * n, tol=1e-8, max_iters=600_000)
        rho_ref = float(equality_rho(ref.alpha, ref.grad, jnp.ones(n),
                                     jnp.ones(n)))
        assert float(kkt_residual_eq(K, model.alpha, 1.0, 1.0)) <= 1e-6
        f_fit = np.asarray(K) @ np.asarray(model.alpha) - model.rho
        f_ref = np.asarray(K) @ np.asarray(ref.alpha) - rho_ref
        np.testing.assert_allclose(f_fit, f_ref, atol=1e-4)


# ---------------------------------------------------------------------------
# (d) nu property, rho, early prediction, serving
# ---------------------------------------------------------------------------

def test_oneclass_nu_sandwich_and_detection():
    """The nu property: margin-error fraction <= nu <= SV fraction (to
    discretization slack), and the detector actually finds the planted
    outliers."""
    X, y = _ocsvm_problem(n=1000, key=7)
    n_all = X.shape[0]
    nu = 0.1
    kern = Kernel("rbf", gamma=4.0)
    cfg = DCSVMConfig(kernel=kern, k=3, levels=1, m=300, tol=1e-5,
                      kmeans_iters=8, use_pallas=False)
    model = fit(cfg, X, task=OneClassSVM(nu=nu))
    f_tr = np.asarray(decision_exact(model, X), np.float64)
    out_frac = float((f_tr < -1e-6).mean())
    sv_frac = len(model.sv_index) / n_all
    slack = 2.0 / n_all
    assert out_frac <= nu + slack, (out_frac, nu)
    assert sv_frac >= nu - slack, (sv_frac, nu)
    # detection: all planted outliers are far off the inlier modes here
    pred = predict_exact(model, X)
    assert recall(y, pred, -1.0) >= 0.9
    assert f1(y, pred, -1.0) >= 0.5


def test_oneclass_label_free_fit_and_y_required_elsewhere():
    """fit() accepts y=None only for label-free tasks."""
    X, _ = _ocsvm_problem(n=120, key=9)
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=2.0), k=2, levels=1, m=60,
                      tol=1e-3, kmeans_iters=5, use_pallas=False)
    model = fit(cfg, X, task=OneClassSVM(nu=0.3))
    assert model.rho is not None
    with pytest.raises(ValueError):
        fit(cfg, X)          # default C-SVC needs labels


def test_oneclass_early_uses_per_cluster_rho():
    """Early-stopped one-class models carry per-cluster multipliers; eq.-11
    routing must subtract the assigned cluster's rho_c (the local levels
    differ by O(1), so a global offset misgrades whole clusters)."""
    X, y = _ocsvm_problem(n=1000, key=11)
    kern = Kernel("rbf", gamma=4.0)
    cfg = DCSVMConfig(kernel=kern, k=4, levels=1, m=300, tol=1e-4,
                      kmeans_iters=8, use_pallas=False, early_stop_level=1)
    model = fit(cfg, X, task=OneClassSVM(nu=0.1))
    assert model.is_early and model.rho_clusters is not None
    assert model.rho_clusters.shape == (model.partition.k,)

    # reference: per-cluster scoring with the cluster's own rho_c
    from repro.core.kkmeans import assign_points

    cid = np.asarray(assign_points(kern, model.partition.model, X)[0])
    u = np.asarray(model.alpha)
    rho_c = np.asarray(model.rho_clusters)
    raw = np.zeros(X.shape[0])
    for c in range(model.partition.k):
        mem = model.partition.idx[c][model.partition.mask[c]]
        q = np.where(cid == c)[0]
        if len(q):
            Kq = np.asarray(kern.pairwise(X[jnp.asarray(q)],
                                          X[jnp.asarray(mem)]))
            raw[q] = Kq @ u[mem] - rho_c[c]
    got = np.asarray(decision_early(model, X))
    np.testing.assert_allclose(got, raw, atol=1e-4)


def test_nusvc_bias_early_single_class_clusters():
    """Regression: an early-stopped biased NuSVC whose clusters are PURE
    (label-free kmeans on well-separated class blobs splits by class) has
    one empty constraint group per cluster — its local bias is undefined,
    and the recovery must fall back to a ZERO offset (the cluster scores
    with its raw own-class-signed decision), not to a half-level shift
    toward the absent class."""
    rng = np.random.default_rng(0)
    n_half, dim = 150, 4
    Xp = rng.normal(size=(n_half, dim)) * 0.2 + 3.0
    Xm = rng.normal(size=(n_half, dim)) * 0.2 - 3.0
    X = jnp.asarray(np.vstack([Xp, Xm]).astype(np.float32))
    y = jnp.asarray(np.concatenate([np.ones(n_half), -np.ones(n_half)])
                    .astype(np.float32))
    kern = Kernel("rbf", gamma=0.5)
    cfg = DCSVMConfig(kernel=kern, k=2, levels=1, m=150, tol=1e-5,
                      kmeans_iters=10, use_pallas=False, early_stop_level=1)
    model = fit(cfg, X, y, task=NuSVC(nu=0.3, with_bias=True))
    assert model.rho_clusters is not None
    rho_c = np.asarray(model.rho_clusters)
    assert np.isfinite(rho_c).all()
    # the clusters really are single-class (the premise of the regression)
    assign = np.asarray(model.partition.assign)
    yn = np.asarray(y)
    purity = [np.abs(yn[assign == c].mean()) for c in range(2)]
    assert min(purity) > 0.99, purity
    # a pure cluster's offset is exactly 0 -> every query routed to it is
    # graded by the raw own-class-signed score, i.e. predicted as ITS class
    np.testing.assert_allclose(rho_c, 0.0, atol=1e-6)
    pred = np.asarray(predict_early(model, X))
    assert (pred == yn).mean() == 1.0


def test_oneclass_early_prediction_bound_holds():
    """ROADMAP item 3 pinned: on fixed-seed gaussian_with_outliers data the
    measured early-prediction error max |f_early(x) - f(x)| respects the
    D(pi) + rho_c-spread bound of ``bounds.oneclass_early_gap_bound`` —
    both the a-priori form (Theorem-1 drift through sigma_n-strong
    convexity) and the semi-empirical form with the measured dual drift."""
    from repro.core.bounds import oneclass_early_gap_bound
    from repro.core.kkmeans import assign_points

    X, _ = _ocsvm_problem(n=500, key=21)
    kern = Kernel("rbf", gamma=4.0)
    nu = 0.15
    cfg = DCSVMConfig(kernel=kern, k=3, levels=1, m=250, tol=1e-5,
                      kmeans_iters=8, use_pallas=False,
                      full_gram_threshold=64)
    model_e = fit(dataclasses.replace(cfg, early_stop_level=1), X,
                  task=OneClassSVM(nu=nu))
    model = fit(cfg, X, task=OneClassSVM(nu=nu))
    Xq = X[:200]
    f_e = np.asarray(decision_early(model_e, Xq), np.float64)
    f = np.asarray(decision_exact(model, Xq), np.float64)
    gap = float(np.max(np.abs(f_e - f)))

    sigma_n = float(np.linalg.eigvalsh(
        np.asarray(kern.pairwise(X, X), np.float64)).min())
    cid_q = assign_points(kern, model_e.partition.model, Xq)[0]
    b = oneclass_early_gap_bound(
        kern, X, model_e.partition.assign, model_e.alpha, model.rho,
        model_e.rho_clusters, Xq, cid_q, sigma_n,
        alpha_exact=model.alpha)
    assert np.isfinite(b["bound"]) and np.isfinite(b["bound_measured"])
    # the semi-empirical bound is the tight(er) one; both must hold
    assert gap <= b["bound_measured"] * (1 + 1e-6) + 1e-6, (gap, b)
    assert gap <= b["bound"] * (1 + 1e-6) + 1e-6, (gap, b)
    assert b["term_rho"] > 0.0       # the clusters really carry distinct rho_c


def test_oneclass_serving_export_round_trip():
    """export_serving_model/serve_batch for task "ocsvm": single beta
    column + rho, exact strategy reproduces decision_exact, early strategy
    reproduces predict_early (per-cluster rho_c travels with the export),
    predictions are +/-1."""
    from repro.launch.serve_svm import export_serving_model, serve_batch

    X, y = _ocsvm_problem(n=800, key=13)
    kern = Kernel("rbf", gamma=4.0)
    cfg = DCSVMConfig(kernel=kern, k=3, levels=1, m=250, tol=1e-4,
                      kmeans_iters=8, use_pallas=False)
    model = fit(cfg, X, task=OneClassSVM(nu=0.1))
    sm = export_serving_model(model, with_bcm=False)
    assert sm.task == "ocsvm"
    assert sm.n_classes == 1 and sm.Wsv.shape[-1] == 1
    Xq = X[:100]
    pred, scores = serve_batch(sm, Xq, kern, "exact")
    assert bool(jnp.all(jnp.abs(pred) == 1.0))
    np.testing.assert_allclose(np.asarray(scores[:, 0]),
                               np.asarray(decision_exact(model, Xq)),
                               rtol=1e-4, atol=1e-4)

    model_e = fit(dataclasses.replace(cfg, early_stop_level=1), X,
                  task=OneClassSVM(nu=0.1))
    sm_e = export_serving_model(model_e, with_bcm=False)
    assert sm_e.rho_c.shape == (model_e.partition.k,)
    pred_e, scores_e = serve_batch(sm_e, Xq, kern, "early")
    np.testing.assert_allclose(np.asarray(scores_e[:, 0]),
                               np.asarray(predict_early_raw := decision_early(
                                   model_e, Xq)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(pred_e),
        np.where(np.asarray(predict_early_raw) >= 0, 1.0, -1.0))
