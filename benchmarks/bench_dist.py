"""Communication-efficient sharded conquer benchmark.

Measures the parallel-block conquer (CE-PBM: every device solves its own
top-B sub-QP per communication round) against the replicated single-block
baseline over meshes of the first P devices, for every P in 1/2/4/8 that
exists — all in ONE process, so a single process holds the chips.  On a
host without accelerators, set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the run to
rehearse on virtual CPU devices.  Asserts

  * both modes reach the dense single-device objective to 1e-3 relative, and
  * at the largest device count (when > 1) the parallel conquer needs
    STRICTLY fewer communication rounds to reach tol than the replicated
    baseline,

and writes BENCH_dist.json (rounds-to-tol + wall-clock per device count and
mode, plus the bytes-per-round accounting from DESIGN.md §11).

    PYTHONPATH=src python -m benchmarks.run --only dist [--dry-run]
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, emit_json
from repro.core import Kernel, gram
from repro.core.distributed import ConquerConfig, conquer_step
from repro.core.solver import solve_with_shrinking
from repro.data import gaussian_mixture
from repro.launch.mesh import make_conquer_mesh

DEVICE_COUNTS = [1, 2, 4, 8]
D_FEAT = 8


def _measure(devices: int, X, y, Q, fref: float, block: int, tol: float,
             max_iters: int) -> dict:
    mesh = make_conquer_mesh("i", jax.devices()[:devices])
    n = X.shape[0]
    f = lambda a: float(0.5 * a @ Q @ a - a.sum())
    out = {"devices": devices}
    base = ConquerConfig(kernel=Kernel("rbf", gamma=8.0), C=4.0, tol=tol,
                         max_iters=max_iters, block=block, mode="parallel")
    for mode in ("parallel", "replicated"):
        cfg = dataclasses.replace(base, mode=mode)
        # warm call compiles; the timed call measures the solve alone
        conquer_step(mesh, "i", cfg, X, y, jnp.zeros(n))[0].block_until_ready()
        t0 = time.perf_counter()
        alpha, rounds, pg = conquer_step(mesh, "i", cfg, X, y, jnp.zeros(n))
        alpha.block_until_ready()
        wall = time.perf_counter() - t0
        out[mode] = {
            "rounds": int(rounds),
            "wall_s": wall,
            "pg_max": float(pg),
            "rel_obj_err": abs(f(alpha) - fref) / abs(fref),
        }
    return out


def run(dry_run: bool = False) -> list:
    n, block, tol = (768, 16, 1e-3) if dry_run else (4096, 16, 1e-3)
    max_iters = 4000 if dry_run else 20000
    avail = jax.device_count()
    counts = [p for p in ([1, 8] if dry_run else DEVICE_COUNTS)
              if p <= avail]
    if counts[-1] < min(avail, 8):
        counts.append(min(avail, 8))

    kern = Kernel("rbf", gamma=8.0)
    X, y = gaussian_mixture(jax.random.PRNGKey(0), n, d=D_FEAT,
                            modes_per_class=4)
    Q = (y[:, None] * y[None, :]) * gram(kern, X, X)
    ref = solve_with_shrinking(Q, 4.0, tol=tol / 10.0,
                               max_iters=50 * max_iters, block=64)
    fref = float(0.5 * ref.alpha @ Q @ ref.alpha - ref.alpha.sum())

    results = {"n": n, "block": block, "tol": tol, "per_devices": {}}
    rows = []
    for devices in counts:
        rec = _measure(devices, X, y, Q, fref, block, tol, max_iters)
        results["per_devices"][str(devices)] = rec
        for mode in ("parallel", "replicated"):
            m = rec[mode]
            assert m["rel_obj_err"] <= 1e-3, (devices, mode, m)
            rows.append((f"dist.conquer.{mode}.p{devices}",
                         m["wall_s"] * 1e6,
                         f"rounds={m['rounds']} "
                         f"rel={m['rel_obj_err']:.1e}"))

    # the headline claim: P simultaneous blocks -> strictly fewer
    # communication rounds than one global block at the same tolerance
    top = results["per_devices"][str(counts[-1])]
    if counts[-1] > 1:
        assert top["parallel"]["rounds"] < top["replicated"]["rounds"], top
    results["rounds_ratio_at_max_devices"] = (
        top["replicated"]["rounds"] / top["parallel"]["rounds"])

    # bytes-per-round accounting (DESIGN.md §11): both modes gather O(P*B*d)
    # per round; parallel applies P*B coordinate updates per round instead
    # of B, so descent per byte scales with P
    results["bytes_per_round_model"] = {
        "all_gather_floats": counts[-1] * block * (D_FEAT + 2),
        "updates_per_round": {"parallel": counts[-1] * block,
                              "replicated": block},
    }
    emit_json("BENCH_dist.json", results)
    return rows


if __name__ == "__main__":
    emit(run())
