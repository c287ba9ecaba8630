"""Integration tests for multilevel DC-SVM (paper Algorithm 1 + Theorems)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    DCSVMConfig,
    Kernel,
    accuracy,
    fit,
    gram,
    kkt_residual,
    objective_value,
    predict_early,
    predict_exact,
    solve_with_shrinking,
)
from repro.core.bounds import d_pi, theorem1_bound
from repro.data import gaussian_mixture, checkerboard, train_test_split


KERN = Kernel("rbf", gamma=8.0)


def _dataset(n=1200, key=0):
    X, y = gaussian_mixture(jax.random.PRNGKey(key), n, d=8, modes_per_class=4,
                            spread=0.15)
    return train_test_split(jax.random.PRNGKey(key + 1), X, y)


def _full_Q(X, y, kern=KERN):
    K = gram(kern, X, X)
    return (y[:, None] * y[None, :]) * K


def test_dcsvm_reaches_exact_objective():
    Xtr, ytr, _, _ = _dataset()
    C = 4.0
    Q = _full_Q(Xtr, ytr)
    exact = solve_with_shrinking(Q, C, tol=1e-4, max_iters=300_000)
    f_exact = 0.5 * exact.alpha @ Q @ exact.alpha - exact.alpha.sum()

    cfg = DCSVMConfig(kernel=KERN, C=C, k=4, levels=2, m=300, tol=1e-4)
    model = fit(cfg, Xtr, ytr)
    f_dc = 0.5 * model.alpha @ Q @ model.alpha - model.alpha.sum()
    # paper's criterion: relative objective error under 1e-3 at matched tol
    assert abs(float(f_dc - f_exact)) <= 1e-3 * abs(float(f_exact))
    assert float(kkt_residual(Q, model.alpha, C)) <= 1e-3


def test_theorem1_bound_holds():
    """0 <= f(a_bar) - f(a*) <= 0.5 C^2 D(pi)  (paper Thm 1 / Fig 1)."""
    Xtr, ytr, _, _ = _dataset(800, key=5)
    C = 2.0
    Q = _full_Q(Xtr, ytr)
    exact = solve_with_shrinking(Q, C, tol=1e-5, max_iters=300_000)
    f_star = float(0.5 * exact.alpha @ Q @ exact.alpha - exact.alpha.sum())

    # a_bar: solve each cluster independently (single level, no conquer)
    cfg = DCSVMConfig(kernel=KERN, C=C, k=4, levels=1, m=300, tol=1e-5,
                      early_stop_level=1)
    model = fit(cfg, Xtr, ytr)
    f_bar = float(0.5 * model.alpha @ Q @ model.alpha - model.alpha.sum())
    bound = theorem1_bound(KERN, Xtr, jnp.asarray(model.partition.assign), C)
    gap = f_bar - f_star
    assert gap >= -1e-3 * abs(f_star)          # f(a_bar) >= f(a*)
    assert gap <= bound + 1e-3 * abs(f_star)   # Thm 1 upper bound


def test_sv_propagation_across_levels():
    """Theorem 2 in practice: lower-level SVs approximately contain the final
    SV set (high recall of final SVs among level-1 SVs)."""
    Xtr, ytr, _, _ = _dataset(1000, key=9)
    C = 4.0
    sv_sets = {}

    def cb(level, alpha, st):
        sv_sets[level] = set(np.nonzero(np.asarray(alpha) > 0)[0].tolist())

    cfg = DCSVMConfig(kernel=KERN, C=C, k=4, levels=2, m=300, tol=1e-4)
    fit(cfg, Xtr, ytr, callback=cb)
    final = sv_sets[0]
    lvl1 = sv_sets[1]
    recall = len(final & lvl1) / max(len(final), 1)
    assert recall > 0.9


def test_early_stop_returns_partitioned_model():
    Xtr, ytr, Xte, yte = _dataset(1000, key=3)
    cfg = DCSVMConfig(kernel=KERN, C=4.0, k=4, levels=2, m=300, tol=1e-3,
                      early_stop_level=1)
    model = fit(cfg, Xtr, ytr)
    assert model.is_early and model.partition is not None
    acc = accuracy(yte, predict_early(model, Xte))
    assert acc > 0.9


def test_multilevel_warm_start_speeds_final_solve():
    """The conquer step with warm start takes far fewer CD iterations than
    solving from zero (the paper's core speed claim)."""
    Xtr, ytr, _, _ = _dataset(1200, key=13)
    C = 4.0
    Q = _full_Q(Xtr, ytr)
    cold = solve_with_shrinking(Q, C, tol=1e-4, max_iters=300_000)

    iters_final = {}

    def cb(level, alpha, st):
        if level == 0:
            iters_final["iters"] = st["iters"]

    cfg = DCSVMConfig(kernel=KERN, C=C, k=4, levels=2, m=300, tol=1e-4)
    fit(cfg, Xtr, ytr, callback=cb)
    assert iters_final["iters"] < int(cold.iters) * 0.5


def test_checkerboard_accuracy():
    """Non-linearly-separable data: kernel machinery actually matters."""
    X, y = checkerboard(jax.random.PRNGKey(21), 1600, cells=3)
    Xtr, ytr, Xte, yte = train_test_split(jax.random.PRNGKey(22), X, y)
    kern = Kernel("rbf", gamma=40.0)
    cfg = DCSVMConfig(kernel=kern, C=16.0, k=4, levels=1, m=400, tol=1e-3)
    model = fit(cfg, Xtr, ytr)
    assert accuracy(yte, predict_exact(model, Xte)) > 0.90


def test_polynomial_kernel_path():
    Xtr, ytr, Xte, yte = _dataset(800, key=31)
    kern = Kernel("poly", gamma=1.0, degree=3)
    cfg = DCSVMConfig(kernel=kern, C=1.0, k=4, levels=1, m=300, tol=1e-3)
    model = fit(cfg, Xtr, ytr)
    Q = _full_Q(Xtr, ytr, kern)
    assert float(kkt_residual(Q, model.alpha, 1.0)) <= 1e-2
    assert accuracy(yte, predict_exact(model, Xte)) > 0.85


def _early_reference(model, Xq):
    """Per-query reference for eq. 11: score against the assigned cluster's
    members with a plain host-side loop."""
    from repro.core import assign_points

    kern = model.config.kernel
    cid, _ = assign_points(kern, model.partition.model, Xq)
    w = np.asarray(model.alpha * model.y)
    out = []
    for i in range(Xq.shape[0]):
        c = int(cid[i])
        mem = model.partition.idx[c][model.partition.mask[c]]
        out.append(float(kern.pairwise(Xq[i][None], model.X[mem])[0]
                         @ jnp.asarray(w[mem])))
    return np.asarray(out)


def test_decision_early_no_host_sync():
    """Regression: the serving hot path must never force a device-to-host
    transfer (the pre-fix code synced on ``int(jnp.sum(~keep))`` on EVERY
    call, overflow or not)."""
    from repro.core import decision_early

    Xtr, ytr, Xte, _ = _dataset(800, key=23)
    cfg = DCSVMConfig(kernel=KERN, C=4.0, k=4, levels=1, m=200, tol=1e-3,
                      early_stop_level=1)
    model = fit(cfg, Xtr, ytr)
    out_warm = decision_early(model, Xte)          # compile outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        out = decision_early(model, Xte)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_warm))
    np.testing.assert_allclose(np.asarray(out), _early_reference(model, Xte),
                               atol=1e-4)


def test_decision_early_overflow_path():
    """Regression: queries beyond a cluster's buffer capacity must be scored
    exactly (extra on-device rounds), not dropped or collided into slot 0."""
    from repro.core import decision_early

    Xtr, ytr, _, _ = _dataset(800, key=25)
    cfg = DCSVMConfig(kernel=KERN, C=4.0, k=4, levels=1, m=200, tol=1e-3,
                      early_stop_level=1)
    model = fit(cfg, Xtr, ytr)
    # route every query to ONE cluster: cap = 2 * nq / k < nq forces overflow
    anchor = model.X[0]
    Xq = anchor[None, :] + 0.01 * jax.random.normal(jax.random.PRNGKey(0),
                                                    (64, Xtr.shape[1]))
    Xq = Xq.astype(Xtr.dtype)
    from repro.core import assign_points
    cid, _ = assign_points(KERN, model.partition.model, Xq)
    counts = np.bincount(np.asarray(cid), minlength=model.partition.k)
    from repro.core import early_capacity
    assert counts.max() > early_capacity(64, model.partition.k), \
        "test setup must overflow the per-cluster buffer"
    with jax.transfer_guard_device_to_host("disallow"):
        out = decision_early(model, Xq)
    np.testing.assert_allclose(np.asarray(out), _early_reference(model, Xq),
                               atol=1e-4)


def test_objective_value_matches_dense():
    Xtr, ytr, _, _ = _dataset(400, key=41)
    cfg = DCSVMConfig(kernel=KERN, C=2.0)
    a = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (Xtr.shape[0],))) * 0.1
    Q = _full_Q(Xtr, ytr)
    f_dense = float(0.5 * a @ Q @ a - a.sum())
    f_chunk = float(objective_value(cfg, Xtr, ytr, a))
    assert abs(f_dense - f_chunk) < 1e-3 * (1 + abs(f_dense))


def _span_names(tracer):
    out, stack = set(), list(tracer.roots)
    while stack:
        s = stack.pop()
        out.add(s.name)
        stack.extend(s.children)
    return out


@pytest.mark.parametrize("early", [0, 1])
def test_fit_spans_name_the_host_work(early):
    """A tiny exact and a tiny early fit record every fit span: the root
    ``fit``; per level the divide's cluster (with its fetch, balance and
    partition), the gathers and the cluster solve, and the SV selection;
    level 0's conquer and selection.  No span time is left unexplained:
    ``fit``'s own time, outside its phase spans, is under 5% of it (a warm
    fit: the first one compiles outside any span)."""
    from repro.obs.spans import SpanTracer

    Xtr, ytr, _, _ = _dataset(4000, key=45)
    cfg = DCSVMConfig(kernel=KERN, C=4.0, k=4, levels=2, m=200, tol=1e-3,
                      early_stop_level=early)
    fit(cfg, Xtr, ytr)
    tracer = SpanTracer()
    with tracer.activate():
        fit(cfg, Xtr, ytr)
    want = {"fit"}
    for l in (2, 1):
        want |= {f"divide/level{l}/{p}"
                 for p in ("cluster", "fetch", "balance", "partition",
                           "solve")}
        want |= {f"interlevel/level{l}/gather", f"interlevel/level{l}/select"}
    if not early:
        want |= {"conquer/refine", "conquer/solve", "interlevel/level0/select"}
    assert _span_names(tracer) == want
    (root,) = tracer.roots
    assert root.name == "fit"
    for c in root.children:
        if c.name.endswith("/cluster"):
            level = c.name.rsplit("/", 1)[0]
            assert [k.name for k in c.children] == [
                f"{level}/fetch", f"{level}/balance", f"{level}/partition"]
    own = root.duration - sum(c.duration for c in root.children)
    assert 0 <= own < 0.05 * root.duration, (own, root.duration)


def test_balance_counters_reach_level_stats_and_spans():
    """Each level's stats carry ``balance_redirected``, the points the
    balanced assignment moved off their nearest centre, and the level's
    ``divide/level<l>/balance`` span carries it as ``redirected`` with the
    greedy's block ``steps``."""
    from repro.core import assign_points
    from repro.obs.spans import SpanTracer

    Xtr, ytr, _, _ = _dataset(1200, key=47)
    n = Xtr.shape[0]
    cfg = DCSVMConfig(kernel=KERN, C=4.0, k=4, levels=2, m=200, tol=1e-3,
                      early_stop_level=1)
    tracer = SpanTracer()
    with tracer.activate():
        model = fit(cfg, Xtr, ytr)
    spans, stack = {}, list(tracer.roots)
    while stack:
        s = stack.pop()
        spans[s.name] = s
        stack.extend(s.children)
    assert [st["level"] for st in model.level_stats] == [2, 1]
    for st in model.level_stats:
        ids = spans[f"divide/level{st['level']}/balance"].ids
        assert set(ids) == {"redirected", "steps"}
        assert st["balance_redirected"] == ids["redirected"]
        assert 0 <= ids["redirected"] < n
        assert 1 <= ids["steps"] <= 2 * cfg.k ** st["level"] + 1
    part = model.partition
    nearest = np.asarray(assign_points(KERN, part.model, Xtr)[0])
    assert model.level_stats[-1]["balance_redirected"] == int(
        np.count_nonzero(part.assign != nearest))
