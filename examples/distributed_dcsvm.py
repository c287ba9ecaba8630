"""Distributed DC-SVM on an 8-device (virtual) mesh via shard_map.

Demonstrates the pod-mapping of the paper: the divide step solves clusters
device-parallel with zero collectives (per-device Gram residency); the
conquer step runs communication-efficient parallel block minimization —
every device solves its OWN top-B sub-QP per round and one all-gather ships
the P rank-B updates, so descent per communication round scales with the
device count.  The replicated mode (one global block per round) is timed for
comparison.

    PYTHONPATH=src python examples/distributed_dcsvm.py
(sets XLA_FLAGS itself — run as a fresh process)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import DCSVMConfig, Kernel, gram, kkt_residual
from repro.core.distributed import ConquerConfig, conquer_step, fit_distributed
from repro.data import gaussian_mixture
from repro.launch.mesh import make_conquer_mesh


def main():
    print(f"devices: {jax.device_count()}")
    mesh = make_conquer_mesh("i")
    kern = Kernel("rbf", gamma=8.0)
    X, y = gaussian_mixture(jax.random.PRNGKey(0), 4096, d=8, modes_per_class=4)
    C = 4.0

    cfg = DCSVMConfig(kernel=kern, C=C, k=4, levels=2, m=400, tol=1e-3)
    t0 = time.perf_counter()
    alpha, stats = fit_distributed(cfg, mesh, "i", X, y, conquer_block=32)
    t = time.perf_counter() - t0
    for st in stats:
        print("  ", st)

    Q = (y[:, None] * y[None, :]) * gram(kern, X, X)
    print(f"distributed DC-SVM: {t:.1f}s | "
          f"KKT residual {float(kkt_residual(Q, alpha, C)):.2e} | "
          f"SVs {int(jnp.sum(alpha > 0))}")

    # conquer-only from zero: P parallel blocks vs one replicated block
    ccfg = ConquerConfig(kernel=kern, C=C, tol=1e-3, max_iters=10_000,
                         block=32, mode="parallel")
    for mode in ("parallel", "replicated"):
        mcfg = dataclasses.replace(ccfg, mode=mode)
        t0 = time.perf_counter()
        a2, rounds, pg = conquer_step(mesh, "i", mcfg, X, y,
                                      jnp.zeros(X.shape[0]))
        t2 = time.perf_counter() - t0
        print(f"conquer from zero [{mode:>10}]: {t2:.1f}s, "
              f"{int(rounds)} communication rounds, "
              f"pg_max {float(pg):.2e}")


if __name__ == "__main__":
    main()
