"""Shared benchmark utilities: datasets, timing, CSV row + JSON artifact emission."""
from __future__ import annotations

import json
import sys
import time
from typing import Callable, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import Kernel, gram, solve_with_shrinking
from repro.data import covtype_like, gaussian_mixture, train_test_split, webspam_like

Row = Tuple[str, float, str]


def emit(rows: List[Row]) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def emit_json(path: str, payload: dict, merge: bool = False) -> None:
    """Write a structured benchmark artifact (e.g. BENCH_conquer.json).

    ``merge=True`` read-merges into an existing artifact: top-level keys in
    ``payload`` replace/extend the file's, other sections survive — for
    benches that share one JSON (a corrupt/missing file starts fresh)."""
    if merge:
        base = {}
        try:
            with open(path) as f:
                base = json.load(f)
        except (OSError, json.JSONDecodeError):
            base = {}
        if isinstance(base, dict):
            payload = {**base, **payload}
    dev = jax.devices()[0]
    payload = dict(payload, backend=dev.platform,
                   device={"platform": dev.platform, "kind": dev.device_kind,
                           "count": jax.device_count()})
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}", file=sys.stderr, flush=True)


def timed(fn: Callable, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(jax.tree.leaves(out)[0] if jax.tree.leaves(out) else out)
    return out, time.perf_counter() - t0


def bench_dataset(name: str, n: int, seed: int = 0):
    # gammas are scaled to the data dimension (gamma ~ 1/median ||x-x'||^2),
    # matching the paper's cross-validated parameter regime: meaningful SV
    # sparsity, kernel matrix far from identity
    key = jax.random.PRNGKey(seed)
    if name == "covtype_like":
        X, y = covtype_like(key, n)
        kern, C = Kernel("rbf", gamma=1.0), 8.0
    elif name == "webspam_like":
        X, y = webspam_like(key, n)
        kern, C = Kernel("rbf", gamma=0.5), 8.0
    else:
        X, y = gaussian_mixture(key, n, d=16, modes_per_class=8, spread=0.12)
        kern, C = Kernel("rbf", gamma=2.0), 4.0
    Xtr, ytr, Xte, yte = train_test_split(jax.random.fold_in(key, 7), X, y)
    return Xtr, ytr, Xte, yte, kern, C


def full_Q(kern: Kernel, X, y):
    return (y[:, None] * y[None, :]) * gram(kern, X, X)


def exact_reference(kern, C, Xtr, ytr, tol=1e-4):
    """High-accuracy reference solution + objective."""
    Q = full_Q(kern, Xtr, ytr)
    res = solve_with_shrinking(Q, C, tol=tol, max_iters=500_000)
    f = float(0.5 * res.alpha @ Q @ res.alpha - res.alpha.sum())
    return Q, res, f
