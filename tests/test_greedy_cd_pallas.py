"""The ``greedy_cd`` kernel against ``solve_box_qp``'s XLA loop, bit for bit.

On the CPU the kernel runs in interpret mode (``use_pallas=True``).  Every
case compares the kernel's ``alpha``, ``grad``, ``iters`` and ``pg_max``
with those of the XLA loop (``use_pallas=False``) on the same problem, in
both places the kernel keeps ``Q.T``: whole in VMEM, or in HBM with one row
copied in a step (forced here by a zero VMEM allowance).
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import DCSVMConfig, Kernel, fit, solve_box_qp
from repro.core import solver as S
from repro.data import gaussian_mixture
from repro.kernels import greedy_cd as GK
from repro.obs.spans import SpanTracer

C = 2.0
TOL = 1e-3


def _gram(n, key, d=5, dup=0):
    """Q = (y y') ∘ K of an RBF kernel on n points; the last ``dup`` points
    repeat the first ones, so their columns of Q and their |pg| tie."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    X = jax.random.normal(k1, (n - dup, d))
    y = jnp.sign(jax.random.normal(k2, (n - dup,)))
    if dup:
        X, y = jnp.concatenate([X, X[:dup]]), jnp.concatenate([y, y[:dup]])
    sq = jnp.sum(X * X, axis=1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(
        X, X.T, precision=jax.lax.Precision.HIGHEST), 0.0)
    return (y[:, None] * y[None, :]) * jnp.exp(-0.5 * d2)


def _case(name):
    """(Q, keyword arguments of solve_box_qp) for one case."""
    rng = np.random.default_rng(len(name))
    n = 200                                      # not a multiple of 128
    kw = dict(tol=TOL, max_iters=5000)
    if name == "ties":
        return _gram(n, 3, dup=40), kw
    Q = _gram(n, 1)
    if name == "mask":
        kw["active_mask"] = jnp.asarray(rng.random(n) < 0.7)
    elif name == "c_and_p":
        kw["p"] = jnp.asarray(-1.0 + 0.5 * rng.random(n), jnp.float32)
        return Q, dict(kw, C=jnp.asarray(0.5 + 3.0 * rng.random(n),
                                         jnp.float32))
    elif name == "warm":
        kw["alpha0"] = jnp.asarray(np.clip(rng.random(n) * 4 - 2, 0, C),
                                   jnp.float32)
    elif name == "at_tol":
        kw["alpha0"] = solve_box_qp(Q, C, tol=1e-6, max_iters=50_000).alpha
    elif name == "max_iters":
        kw["max_iters"] = 37
    return Q, kw


CASES = ["n_not_tile", "mask", "c_and_p", "warm", "at_tol", "max_iters",
         "ties"]


@pytest.fixture(params=["vmem", "hbm"])
def placement(request, monkeypatch):
    """Where the kernel keeps Q.T; jit caches are cleared around a change
    so no trace of the other placement is reused."""
    if request.param == "hbm":
        monkeypatch.setattr(GK, "VMEM_QT_BYTES", 0)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


def _assert_same(ref, got):
    np.testing.assert_array_equal(np.asarray(got.alpha), np.asarray(ref.alpha))
    np.testing.assert_array_equal(np.asarray(got.grad), np.asarray(ref.grad))
    np.testing.assert_array_equal(np.asarray(got.iters), np.asarray(ref.iters))
    np.testing.assert_array_equal(np.asarray(got.pg_max),
                                  np.asarray(ref.pg_max))


@pytest.mark.parametrize("name", CASES)
def test_kernel_matches_xla_loop(placement, name):
    Q, kw = _case(name)
    c = kw.pop("C", C)
    assert GK.qt_in_vmem(Q.shape[0]) == (placement == "vmem")
    ref = solve_box_qp(Q, c, use_pallas=False, **kw)
    got = solve_box_qp(Q, c, use_pallas=True, **kw)
    _assert_same(ref, got)
    if name == "at_tol":
        assert int(got.iters) == 0
    if name == "max_iters":
        assert int(got.iters) == 37 and float(got.pg_max) > TOL


@pytest.mark.parametrize("how", ["vmap", "lax_map"])
def test_kernel_matches_xla_loop_batched(placement, how):
    """Three problems of one batch, each with its own warm start and mask:
    under vmap the kernel solves them one after another, the XLA loop in
    lockstep; under lax.map both solve one at a time."""
    Qs = jnp.stack([_gram(150, k) for k in range(3)])
    rng = np.random.default_rng(7)
    a0 = jnp.asarray(np.clip(rng.random((3, 150)) * 4 - 2, 0, C), jnp.float32)
    m = jnp.asarray(rng.random((3, 150)) < 0.8)

    def solve(use_pallas):
        def one(args):
            Q, a, mi = args
            return solve_box_qp(Q, C, alpha0=a, tol=TOL, max_iters=5000,
                                active_mask=mi, use_pallas=use_pallas)
        if how == "vmap":
            return jax.vmap(lambda *a: one(a))(Qs, a0, m)
        return jax.lax.map(one, (Qs, a0, m))

    _assert_same(solve(False), solve(True))


def test_placement_follows_cluster_size():
    """The covtype cell's level-1 clusters (12,500 points), webspam's
    (6,250) and the refines (about 15,000 and 10,000) keep Q.T in HBM;
    covtype's level 2 (3,125), webspam's (1,563) and the smaller clusters of
    levels 3 and 4 hold it in VMEM."""
    assert not any(GK.qt_in_vmem(n) for n in (15_446, 12_500, 9_757, 6_250))
    assert all(GK.qt_in_vmem(n) for n in (3_125, 1_563, 782, 196, 98))
    assert GK.tile_rows(200) == 8 and GK.tile_rows(1025) == 16


@pytest.mark.parametrize("batch,engine", [(1, "pallas"), (16, "pallas"),
                                          (17, "xla"), (64, "xla"),
                                          (256, "xla")])
def test_engine_follows_batch(batch, engine):
    """The kernel solves a batch one problem after another, the XLA loop in
    lockstep: the kernel up to 16 problems run together, the loop above."""
    assert S.greedy_cd_engine(batch, use_pallas=True) == engine
    assert S.greedy_cd_engine(batch, use_pallas=False) == "xla"


def _data(n=500, key=11):
    return gaussian_mixture(jax.random.PRNGKey(key), n, d=6,
                            modes_per_class=3, spread=0.25)


def test_fit_with_kernel_matches_fit_with_xla_loop(monkeypatch):
    """A whole exact fit on the Pallas backend (interpret mode): cluster
    solves, refine and the dense level-0 solve with the kernel give the
    same alpha as with the XLA loop."""
    X, y = _data()
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=2.0), C=C, k=2, levels=2,
                      m=100, tol=TOL, use_pallas=True)
    jax.clear_caches()
    with_kernel = fit(cfg, X, y)
    monkeypatch.setattr(S, "greedy_cd_kernel_wins", lambda batch: False)
    jax.clear_caches()
    with_loop = fit(cfg, X, y)
    jax.clear_caches()
    assert [st["cd"] for st in with_kernel.level_stats[:-1]] == ["pallas"] * 2
    assert [st["cd"] for st in with_loop.level_stats[:-1]] == ["xla"] * 2
    np.testing.assert_array_equal(np.asarray(with_kernel.alpha),
                                  np.asarray(with_loop.alpha))
    assert ([st["iters"] for st in with_kernel.level_stats]
            == [st["iters"] for st in with_loop.level_stats])


def test_wide_level_keeps_the_lockstep_loop():
    """A level of 25 clusters solved together (k=5, level 2) runs the
    lockstep XLA loop even on the Pallas backend; level 1's 5 run the
    kernel.  The alpha is that of the fit with the loop everywhere."""
    X, y = _data()
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=2.0), C=C, k=5, levels=2,
                      m=100, tol=TOL, use_pallas=True, refine=False,
                      early_stop_level=1)
    jax.clear_caches()
    model = fit(cfg, X, y)
    ref = fit(dataclasses.replace(cfg, use_pallas=False), X, y)
    assert [(st["level"], st["cd"]) for st in model.level_stats] == [
        (2, "xla"), (1, "pallas")]
    np.testing.assert_array_equal(np.asarray(model.alpha),
                                  np.asarray(ref.alpha))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_level_stats_and_spans_name_the_cd_engine(use_pallas):
    """Levels l >= 1 carry ``iters`` (CD steps summed over the clusters)
    and ``cd``; ``divide/level<l>/solve`` and ``conquer/refine`` carry the
    same ``cd`` as an identifier."""
    X, y = _data()
    cfg = DCSVMConfig(kernel=Kernel("rbf", gamma=2.0), C=C, k=2, levels=2,
                      m=100, tol=TOL, use_pallas=use_pallas)
    tracer = SpanTracer()
    with tracer.activate():
        model = fit(cfg, X, y)
    spans, stack = {}, list(tracer.roots)
    while stack:
        s = stack.pop()
        spans[s.name] = s
        stack.extend(s.children)
    want = "pallas" if use_pallas else "xla"
    for st in model.level_stats[:-1]:
        assert st["cd"] == want
        assert isinstance(st["iters"], int) and st["iters"] > 0
        assert spans[f"divide/level{st['level']}/solve"].ids == {"cd": want}
    assert spans["conquer/refine"].ids == {"cd": want}
