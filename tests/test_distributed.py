"""Distributed DC-SVM: sharded parallel-block conquer vs the dense solution.

The multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the dryrun pattern); the
in-process tests exercise the same code paths on a 1-device mesh.

Covers the communication-efficient parallel block minimization (CE-PBM)
conquer: both modes reach dense-solver parity, cached and uncached parallel
paths agree exactly, padding removes the n % P == 0 restriction, the returned
pg_max is the residual at the RETURNED alpha (regression: it used to be the
stale pre-update stopping value), and the conquer while-loop stays free of
device-to-host syncs.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import DCSVMConfig, Kernel, gram, kkt_residual
from repro.core.distributed import (
    ConquerConfig,
    conquer_step,
    divide_step,
    fit_distributed,
    fit_distributed_model,
)
from repro.core.solver import combination_step_size, solve_with_shrinking
from repro.core.tasks import EpsilonSVR, OneClassSVM, WeightedCSVC
from repro.data import gaussian_mixture
from repro.launch.mesh import make_conquer_mesh, make_host_mesh

KERN = Kernel("rbf", gamma=8.0)


def _mesh1():
    return make_conquer_mesh("i", jax.devices()[:1])


def _svc_objective(Q, alpha):
    return float(0.5 * jnp.vdot(alpha, Q @ alpha) - jnp.sum(alpha))


@pytest.mark.parametrize("mode", ["parallel", "replicated"])
def test_conquer_single_device_mesh_matches_dense(mode):
    X, y = gaussian_mixture(jax.random.PRNGKey(0), 512, d=6, modes_per_class=3)
    cfg = ConquerConfig(kernel=KERN, C=2.0, tol=1e-4, max_iters=3000,
                        block=32, mode=mode)
    alpha, iters, pg = conquer_step(_mesh1(), "i", cfg, X, y, jnp.zeros(512))
    Q = (y[:, None] * y[None, :]) * gram(KERN, X, X)
    assert float(pg) <= 1e-4 * 1.5
    assert float(kkt_residual(Q, alpha, 2.0)) <= 1e-3


def test_conquer_cache_path_matches_uncached():
    X, y = gaussian_mixture(jax.random.PRNGKey(4), 384, d=6, modes_per_class=3)
    base = ConquerConfig(kernel=KERN, C=2.0, tol=1e-4, max_iters=3000,
                         block=16)
    a0, r0, pg0 = conquer_step(_mesh1(), "i", base, X, y, jnp.zeros(384))
    cached = dataclasses.replace(base, cache_cap=256)
    a1, r1, pg1 = conquer_step(_mesh1(), "i", cached, X, y, jnp.zeros(384))
    # the cache only changes WHERE Q rows come from, never their values;
    # the served path contracts (PB,)@(PB,n) instead of (n,PB)@(PB,), so
    # float32 reassociation allows ~1e-6 drift but the trajectory (round
    # count) and the iterate must agree
    assert int(r0) == int(r1)
    np.testing.assert_allclose(np.asarray(a0), np.asarray(a1), atol=1e-4)


def test_conquer_pg_max_is_residual_at_returned_alpha():
    """Regression: conquer_step used to report the stopping value measured
    BEFORE the final update — one stale round behind the returned alpha."""
    X, y = gaussian_mixture(jax.random.PRNGKey(2), 256, d=6, modes_per_class=3)
    Q = (y[:, None] * y[None, :]) * gram(KERN, X, X)
    cfg = ConquerConfig(kernel=KERN, C=2.0, tol=1e-9, max_iters=1, block=32)
    alpha, iters, pg = conquer_step(_mesh1(), "i", cfg, X, y, jnp.zeros(256))
    assert int(iters) == 1
    after = float(kkt_residual(Q, alpha, 2.0))
    before = float(kkt_residual(Q, jnp.zeros(256), 2.0))
    assert abs(float(pg) - after) <= 1e-5 * (1.0 + after)
    # the stale value (residual at the starting point) is far away
    assert abs(float(pg) - before) > 1e-3


def test_conquer_vector_box_and_linear_term():
    """Weighted per-coordinate box + nonuniform linear term (the TaskDual
    generalization) against the dense shrinking solver."""
    X, y = gaussian_mixture(jax.random.PRNGKey(5), 300, d=6, modes_per_class=3)
    td = WeightedCSVC(w_pos=2.0, w_neg=0.5).build(X, y[None, :], 2.0)
    s, p, c = td.S[0], td.P[0], td.Cvec[0]
    Q = (s[:, None] * s[None, :]) * gram(KERN, X, X)
    ref = solve_with_shrinking(Q, c, tol=1e-6, max_iters=200_000, block=32,
                               p=p)
    cfg = ConquerConfig(kernel=KERN, C=2.0, tol=1e-4, max_iters=4000,
                        block=16)
    alpha, _, pg = conquer_step(_mesh1(), "i", cfg, X, s, jnp.zeros(300),
                                p=p, c=c)
    f = lambda a: float(0.5 * jnp.vdot(a, Q @ a) + jnp.vdot(p, a))
    rel = abs(f(alpha) - f(ref.alpha)) / abs(f(ref.alpha))
    assert float(pg) <= 1e-3
    assert rel <= 1e-3


def test_conquer_pads_unaligned_n():
    """n need not divide the device count: rows are padded with c=0
    coordinates that can never move nor report violations."""
    X, y = gaussian_mixture(jax.random.PRNGKey(6), 333, d=6, modes_per_class=3)
    cfg = ConquerConfig(kernel=KERN, C=2.0, tol=1e-4, max_iters=3000,
                        block=16)
    alpha, _, pg = conquer_step(_mesh1(), "i", cfg, X, y, jnp.zeros(333))
    assert alpha.shape == (333,)
    Q = (y[:, None] * y[None, :]) * gram(KERN, X, X)
    assert float(kkt_residual(Q, alpha, 2.0)) <= 1e-3


def test_conquer_loop_is_host_sync_free():
    """The conquer while-loop must run device-resident: no host round-trips
    between rounds (transfer_guard trips on any device->host copy)."""
    X, y = gaussian_mixture(jax.random.PRNGKey(7), 256, d=6, modes_per_class=3)
    cfg = ConquerConfig(kernel=KERN, C=2.0, tol=1e-4, max_iters=2000,
                        block=16)
    # warm call compiles (compilation itself may inspect host values)
    conquer_step(_mesh1(), "i", cfg, X, y, jnp.zeros(256))
    with jax.transfer_guard_device_to_host("disallow"):
        alpha, iters, pg = conquer_step(_mesh1(), "i", cfg, X, y,
                                        jnp.zeros(256))
    Q = (y[:, None] * y[None, :]) * gram(KERN, X, X)
    assert float(kkt_residual(Q, alpha, 2.0)) <= 1e-3


@pytest.mark.parametrize("mode,cache", [("parallel", 0), ("parallel", 128),
                                        ("replicated", 0)])
def test_conquer_trace_bit_identical_and_host_sync_free(mode, cache):
    """trace_cap > 0 threads a device-resident ConvTrace through the conquer
    rounds: the iterate must stay bit-identical to the untraced run, the
    traced loop must add no device->host sync (the ring is fetched after),
    and the per-round samples must line up with the round count."""
    from repro.obs.trace import trace_fetch

    X, y = gaussian_mixture(jax.random.PRNGKey(9), 256, d=6, modes_per_class=3)
    base = ConquerConfig(kernel=KERN, C=2.0, tol=1e-4, max_iters=2000,
                         block=16, mode=mode, cache_cap=cache)
    traced = dataclasses.replace(base, trace_cap=64)
    a0, r0, pg0 = conquer_step(_mesh1(), "i", base, X, y, jnp.zeros(256))
    conquer_step(_mesh1(), "i", traced, X, y, jnp.zeros(256))   # warm compile
    with jax.transfer_guard_device_to_host("disallow"):
        a1, r1, pg1, tr = conquer_step(_mesh1(), "i", traced, X, y,
                                       jnp.zeros(256))
        a1.block_until_ready()
    assert np.array_equal(np.asarray(a0), np.asarray(a1))
    assert int(r0) == int(r1)
    out = trace_fetch(tr)
    assert out["samples"] + out["dropped"] == int(r1)
    # per-round pg is the selection-time violation (pre-update), so the last
    # sample sits one round behind the exit residual but the same order
    assert all(np.isfinite(v) and v > 0 for v in out["pg_max"])
    assert out["pg_max"][-1] >= float(pg1) * 0.1
    assert all(np.isfinite(v) for v in out["objective"])
    if mode == "parallel":
        assert "gamma" in out       # CE-PBM records the combination step γ*
        assert all(0.0 <= g <= 1.0 for g in out["gamma"])
    else:
        assert "gamma" not in out   # replicated has no combination step
    if cache:
        assert "cache_hits" in out  # per-round hit deltas


def test_combination_step_size_properties():
    # interior optimum of the 1-d quadratic: gamma = -g*d/(d*Q*d)
    assert float(combination_step_size(jnp.float32(-1.0),
                                       jnp.float32(4.0))) == 0.25
    # descent directions want gamma >= 0; clip at the full block step
    assert float(combination_step_size(jnp.float32(-8.0),
                                       jnp.float32(4.0))) == 1.0
    # degenerate curvature falls back to the full step
    assert float(combination_step_size(jnp.float32(-1.0),
                                       jnp.float32(0.0))) == 1.0
    # ascent direction (cannot happen for exact block solves) is rejected
    assert float(combination_step_size(jnp.float32(2.0),
                                       jnp.float32(4.0))) == 0.0


def test_divide_single_device_mesh():
    X, y = gaussian_mixture(jax.random.PRNGKey(1), 256, d=6)
    cfg = DCSVMConfig(kernel=KERN, C=2.0, tol=1e-4)
    Xc = X.reshape(4, 64, 6)
    yc = y.reshape(4, 64)
    mask = jnp.ones((4, 64), bool)
    pc = jnp.full((4, 64), -1.0)
    cc = jnp.full((4, 64), 2.0)
    ac = divide_step(_mesh1(), "i", cfg, Xc, yc, pc, cc,
                     jnp.zeros((4, 64)), mask)
    # each block solves its own subproblem to KKT
    for c in range(4):
        Qc = (yc[c][:, None] * yc[c][None, :]) * gram(KERN, Xc[c], Xc[c])
        assert float(kkt_residual(Qc, ac[c], 2.0)) <= 1e-3


def test_divide_sequential_fallback_matches_vmap():
    """gram_budget too small for per-device Gram residency -> lax.map path;
    the answer must not change."""
    X, y = gaussian_mixture(jax.random.PRNGKey(8), 256, d=6)
    Xc, yc = X.reshape(4, 64, 6), y.reshape(4, 64)
    mask = jnp.ones((4, 64), bool)
    pc = jnp.full((4, 64), -1.0)
    cc = jnp.full((4, 64), 2.0)
    a0 = jnp.zeros((4, 64))
    cfg = DCSVMConfig(kernel=KERN, C=2.0, tol=1e-4)
    small = dataclasses.replace(cfg, gram_budget=1)
    av = divide_step(_mesh1(), "i", cfg, Xc, yc, pc, cc, a0, mask)
    As = divide_step(_mesh1(), "i", small, Xc, yc, pc, cc, a0, mask)
    np.testing.assert_allclose(np.asarray(av), np.asarray(As), atol=1e-6)


def test_fit_distributed_svr_single_device():
    key = jax.random.PRNGKey(9)
    X = jax.random.uniform(key, (300, 6))
    yr = jnp.sin(3.0 * X[:, 0]) + 0.5 * X[:, 1]
    task = EpsilonSVR(eps=0.1)
    td = task.build(X, yr[None, :], 2.0)
    s, p, c = td.S[0], td.P[0], td.Cvec[0]
    Q = (s[:, None] * s[None, :]) * gram(KERN, td.Xd, td.Xd)
    ref = solve_with_shrinking(Q, c, tol=1e-6, max_iters=400_000, block=32,
                               p=p)
    cfg = DCSVMConfig(kernel=KERN, C=2.0, k=4, levels=1, m=128, tol=1e-4,
                      use_pallas=False)
    alpha, stats = fit_distributed(cfg, _mesh1(), "i", X, yr, task=task,
                                   conquer_block=16, conquer_iters=6000)
    f = lambda a: float(0.5 * jnp.vdot(a, Q @ a) + jnp.vdot(p, a))
    rel = abs(f(alpha) - f(ref.alpha)) / abs(f(ref.alpha))
    assert rel <= 1e-3
    # stats must already be host scalars (no lingering device arrays)
    for row in stats:
        for v in row.values():
            assert isinstance(v, (int, float)), type(v)


def test_fit_distributed_model_builds_beta():
    X, y = gaussian_mixture(jax.random.PRNGKey(10), 256, d=6,
                            modes_per_class=3)
    cfg = DCSVMConfig(kernel=KERN, C=2.0, k=4, levels=1, m=128, tol=1e-4,
                      use_pallas=False)
    model = fit_distributed_model(cfg, _mesh1(), "i", X, y, conquer_block=16)
    from repro.core.predict import predict_exact
    acc = float(jnp.mean(jnp.sign(predict_exact(model, X)) == y))
    assert acc >= 0.9
    assert model.beta is not None and model.beta.shape == (256,)


def test_fit_distributed_rejects_equality_tasks():
    X, _ = gaussian_mixture(jax.random.PRNGKey(11), 64, d=4)
    cfg = DCSVMConfig(kernel=KERN, C=1.0, levels=1, tol=1e-3)
    with pytest.raises(NotImplementedError, match="equality"):
        fit_distributed(cfg, _mesh1(), "i", X, task=OneClassSVM(nu=0.5))


def test_conquer_rejects_unknown_mode():
    X, y = gaussian_mixture(jax.random.PRNGKey(12), 64, d=4)
    cfg = ConquerConfig(kernel=KERN, C=1.0, mode="gossip")
    with pytest.raises(ValueError, match="mode"):
        conquer_step(_mesh1(), "i", cfg, X, y, jnp.zeros(64))


def test_make_host_mesh_clear_error_on_bad_axis():
    with pytest.raises(ValueError, match="model_axis"):
        make_host_mesh(model_axis=3 * jax.device_count())


_SUBPROCESS_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import DCSVMConfig, Kernel, gram, kkt_residual
    from repro.core.distributed import (ConquerConfig, conquer_step,
                                        fit_distributed)
    from repro.core.solver import solve_with_shrinking
    from repro.core.tasks import EpsilonSVR, WeightedCSVC
    from repro.data import gaussian_mixture
    from repro.launch.mesh import make_conquer_mesh

    assert jax.device_count() == 8, jax.device_count()
    mesh = make_conquer_mesh("i")
    KERN = Kernel("rbf", gamma=8.0)
    # 1001 % 8 != 0: exercises the padded shards on every device
    X, y = gaussian_mixture(jax.random.PRNGKey(0), 1001, d=8,
                            modes_per_class=4)
    Q = (y[:, None] * y[None, :]) * gram(KERN, X, X)
    f = lambda a: float(0.5 * a @ Q @ a - a.sum())
    ref = solve_with_shrinking(Q, 4.0, tol=1e-5, max_iters=200_000, block=64)
    fref = f(ref.alpha)

    # parallel-block conquer from zero: dense parity + STRICTLY fewer
    # communication rounds than the replicated single-block baseline
    cfg = ConquerConfig(kernel=KERN, C=4.0, tol=1e-4, max_iters=4000,
                        block=16, mode="parallel")
    alpha, rounds_p, pg = conquer_step(mesh, "i", cfg, X, y, jnp.zeros(1001))
    rel = abs(f(alpha) - fref) / abs(fref)
    assert rel <= 1e-3, rel
    rcfg = dataclasses.replace(cfg, mode="replicated")
    alpha_r, rounds_r, _ = conquer_step(mesh, "i", rcfg, X, y,
                                        jnp.zeros(1001))
    rel_r = abs(f(alpha_r) - fref) / abs(fref)
    assert rel_r <= 1e-3, rel_r
    assert int(rounds_p) < int(rounds_r), (int(rounds_p), int(rounds_r))

    # full multilevel distributed fit matches the dense objective
    dcfg = DCSVMConfig(kernel=KERN, C=4.0, k=8, levels=2, m=256, tol=1e-4,
                       use_pallas=False)
    alpha2, stats = fit_distributed(dcfg, mesh, "i", X, y, conquer_block=16)
    rel2 = abs(f(alpha2) - fref) / abs(fref)
    assert rel2 <= 1e-3, rel2
    # the fit's own state stays on one device: Pallas kernels outside the
    # shard_mapped steps (kmeans, prediction) cannot be partitioned
    assert len(alpha2.sharding.device_set) == 1, alpha2.sharding

    # weighted-class box on 8 devices
    wt = WeightedCSVC(w_pos=2.0, w_neg=0.5)
    tdw = wt.build(X, y[None, :], 4.0)
    sw, pw, cw = tdw.S[0], tdw.P[0], tdw.Cvec[0]
    Qw = (sw[:, None] * sw[None, :]) * gram(KERN, X, X)
    refw = solve_with_shrinking(Qw, cw, tol=1e-5, max_iters=200_000,
                                block=64, p=pw)
    fw = lambda a: float(0.5 * a @ Qw @ a + pw @ a)
    aw, s2 = fit_distributed(dcfg, mesh, "i", X, y, task=wt,
                             conquer_block=16)
    relw = abs(fw(aw) - fw(refw.alpha)) / abs(fw(refw.alpha))
    assert relw <= 1e-3, relw

    # epsilon-SVR (2n mirrored dual) on 8 devices
    key = jax.random.PRNGKey(1)
    Xr = jax.random.uniform(key, (600, 6))
    yr = jnp.sin(3.0 * Xr[:, 0]) + 0.5 * Xr[:, 1]
    KR = Kernel("rbf", gamma=2.0)
    task = EpsilonSVR(eps=0.1)
    td = task.build(Xr, yr[None, :], 2.0)
    Qr = (td.S[0][:, None] * td.S[0][None, :]) * gram(KR, td.Xd, td.Xd)
    refr = solve_with_shrinking(Qr, td.Cvec[0], tol=1e-5,
                                max_iters=400_000, block=64, p=td.P[0])
    fr = lambda a: float(0.5 * a @ Qr @ a + td.P[0] @ a)
    rcfg2 = DCSVMConfig(kernel=KR, C=2.0, k=8, levels=1, m=200, tol=1e-4,
                        use_pallas=False)
    ar, s3 = fit_distributed(rcfg2, mesh, "i", Xr, yr, task=task,
                             conquer_block=16, conquer_iters=6000)
    relr = abs(fr(ar) - fr(refr.alpha)) / abs(fr(refr.alpha))
    assert relr <= 1e-3, relr
    print("OK", rel, rel2, relw, relr, int(rounds_p), int(rounds_r))
    """
)


@pytest.mark.slow
def test_multi_device_conquer_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
