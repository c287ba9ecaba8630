"""Paper Figure 1: Theorem-1 bound tightness, kernel kmeans vs random.

For k in {8,16,32,64}: partition by two-step kernel kmeans, solve the
subproblems, and compare f(a_bar) - f(a*) against (1/2) C^2 D(pi), plus the
same gap under a RANDOM partition (the paper's control showing the clustering
is what makes the bound small).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import bench_dataset, emit, exact_reference, timed
from repro.core import DCSVMConfig, fit
from repro.core.bounds import d_pi, theorem1_bound


def run(n: int = 2000) -> list:
    Xtr, ytr, _, _, kern, C = bench_dataset("gaussian", n)
    Q, ref, f_star = exact_reference(kern, C, Xtr, ytr)
    rows = []
    rng = np.random.default_rng(0)
    for k_log in (1, 2, 3):
        k = 4 ** k_log
        cfg = DCSVMConfig(kernel=kern, C=C, k=k, levels=1, m=400, tol=1e-4,
                          early_stop_level=1)
        model, dt = timed(fit, cfg, Xtr, ytr)
        f_bar = float(0.5 * model.alpha @ Q @ model.alpha - model.alpha.sum())
        bound = theorem1_bound(kern, Xtr, jnp.asarray(model.partition.assign), C)
        gap = f_bar - f_star

        rand_assign = rng.integers(0, k, size=Xtr.shape[0]).astype(np.int32)
        # random-partition a_bar: solve per random cluster via the same machinery
        from repro.core.kkmeans import Partition
        from repro.core.dcsvm import _solve_clusters
        part = Partition.build(rand_assign, k, model.partition.model)
        mask = jnp.asarray(part.mask)
        # _solve_clusters takes class-stacked (k, n_rows, nc) sign/linear/
        # box/dual vectors (the generalized dual; hinge: s=y, p=-1, c=C)
        yc = part.gather(ytr)[:, None, :]
        pc = jnp.full_like(yc, -1.0)
        cc = jnp.full_like(yc, C)
        ac = jnp.where(mask, part.gather(jnp.zeros(Xtr.shape[0])), 0.0)[:, None, :]
        ac, _ = _solve_clusters(cfg, part.gather(Xtr), yc, pc, cc, ac, mask)
        a_rand = part.scatter(ac[:, 0, :], Xtr.shape[0])
        f_rand = float(0.5 * a_rand @ Q @ a_rand - a_rand.sum())
        bound_rand = theorem1_bound(kern, Xtr, jnp.asarray(rand_assign), C)

        rows += [
            (f"fig1.gap_kkmeans.k{k}", dt * 1e6,
             f"gap={gap:.4f};bound={bound:.4f};fstar={f_star:.2f}"),
            (f"fig1.gap_random.k{k}", 0.0,
             f"gap={f_rand - f_star:.4f};bound={bound_rand:.4f}"),
        ]
        # Theorem 1 must hold; kkmeans partition should beat random clearly
        assert -1e-2 * abs(f_star) <= gap <= bound * 1.01 + 1e-2 * abs(f_star)
        assert gap <= (f_rand - f_star) + 1e-2 * abs(f_star)
    return rows


if __name__ == "__main__":
    emit(run())
