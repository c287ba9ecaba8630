"""Plain references for the benchmark's correctness checks.

Nothing here imports the program under test or takes anything it computed
except the answer being checked (a dual solution, a partition, served
scores).  Two references of the RBF kernel machine:

* ``rbf64``: the kernel in float64 on the host (small sizes, tests);
* ``rbf_matvec``: K(X, Z) @ v streamed in row blocks through XLA, its
  matmuls at ``HIGHEST`` for the reference, or at ``BF16_3X`` for the
  control, "the reference at the next precision down": every f32 operand
  split into two bfloat16 parts and the three leading products summed in
  f32, which is what the TPU's ``Precision.HIGH`` does, emulated so that it
  reads the same on any backend.

On top of them, ``box_kkt`` gives the C-SVC dual's projected-gradient KKT
residual and box violation, ``cluster_kkt`` the same per cluster of an
early-stopped (block-diagonal) solution, ``decision`` the plain
decision function f(x) = sum_i w_i K(x_i, x), and ``partition_assign`` the
balanced partition that a kernel k-means model (its sampled points and
their cluster weights) defines over the data.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BF16_3X = "bf16_3x"


def rbf64(A, B, gamma: float) -> np.ndarray:
    """RBF kernel in float64 on the host."""
    A, B = np.asarray(A, np.float64), np.asarray(B, np.float64)
    sq = (A * A).sum(1)[:, None] + (B * B).sum(1)[None, :] - 2.0 * A @ B.T
    return np.exp(-gamma * np.maximum(sq, 0.0))


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _matmul(a, b, precision):
    if precision != BF16_3X:
        return jnp.matmul(a, b, precision=precision)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.matmul(ah, bh, precision=HIGHEST)
            + jnp.matmul(ah, bl, precision=HIGHEST)
            + jnp.matmul(al, bh, precision=HIGHEST))


@partial(jax.jit, static_argnames=("gamma", "rows", "precision"))
def _matvec_blocks(Xp, Z, v, *, gamma: float, rows: int, precision):
    zz = jnp.sum(Z * Z, axis=-1)

    def block(Xc):
        g = _matmul(Xc, Z.T, precision)
        xx = jnp.sum(Xc * Xc, axis=-1)
        sq = jnp.maximum(xx[:, None] + zz[None, :] - 2.0 * g, 0.0)
        return _matmul(jnp.exp(-gamma * sq), v[:, None], precision)[:, 0]

    return jax.lax.map(block, Xp.reshape(-1, rows, Xp.shape[1])).reshape(-1)


def rbf_matvec(gamma: float, X, Z, v, rows: int = 2048,
               precision=HIGHEST) -> np.ndarray:
    """K(X, Z) @ v for the RBF kernel, in row blocks of ``rows`` so that
    only a (rows, len(Z)) tile is live.  Returns float64 on the host."""
    X = np.asarray(X, np.float32)
    n = X.shape[0]
    rows = max(8, min(rows, n))
    pad = (-n) % rows
    Xp = jnp.asarray(np.pad(X, ((0, pad), (0, 0))))
    out = _matvec_blocks(Xp, jnp.asarray(Z, jnp.float32),
                         jnp.asarray(v, jnp.float32), gamma=float(gamma),
                         rows=rows, precision=precision)
    return np.asarray(out, np.float64)[:n]


def _pg(alpha: np.ndarray, g: np.ndarray, C: float) -> np.ndarray:
    """Projected gradient of the box-constrained dual (0 <= alpha <= C)."""
    pg = np.where(alpha <= 0.0, np.minimum(g, 0.0), g)
    return np.where(alpha >= C, np.maximum(g, 0.0), pg)


def box_kkt(gamma: float, C: float, X, y, alpha, **kw) -> dict:
    """KKT residual max|pg| of the C-SVC dual min 1/2 a'Qa - 1'a,
    Q = (y y') o K, 0 <= a <= C, at ``alpha``, from a fresh matvec; with the
    dual objective and the largest box violation."""
    a = np.asarray(alpha, np.float64)
    yv = np.asarray(y, np.float64)
    g = yv * rbf_matvec(gamma, X, X, yv * a, **kw) - 1.0
    box = float(max(0.0, -a.min(), (a - C).max()))
    return {"kkt": float(np.abs(_pg(a, g, C)).max()),
            "objective": float(0.5 * a @ (g + 1.0) - a.sum()),
            "box": box}


def cluster_kkt(gamma: float, C: float, X, y, alpha, assign, **kw) -> dict:
    """``box_kkt`` of each cluster's own sub-QP (the block-diagonal problem
    an early-stopped level solves), worst cluster."""
    X, y, a = np.asarray(X), np.asarray(y), np.asarray(alpha, np.float64)
    assign = np.asarray(assign)
    worst = {"kkt": 0.0, "box": 0.0, "objective": 0.0}
    for c in np.unique(assign):
        m = assign == c
        r = box_kkt(gamma, C, X[m], y[m], a[m], **kw)
        worst["kkt"] = max(worst["kkt"], r["kkt"])
        worst["box"] = max(worst["box"], r["box"])
        worst["objective"] += r["objective"]
    return worst


def decision(gamma: float, Xsv, w, Xq, **kw) -> np.ndarray:
    """f(x) = sum_i w_i K(x_i, x) for each row of ``Xq``."""
    return rbf_matvec(gamma, Xq, Xsv, w, **kw)


def _bf16(A) -> np.ndarray:
    return np.asarray(jnp.asarray(A, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def _rbf_host(A, B, gamma: float, bf16: bool) -> np.ndarray:
    """``rbf64``, or with the cross products' operands rounded to bfloat16
    (the norms stay exact), as a Gram with bf16 operands computes it."""
    A, B = np.asarray(A, np.float64), np.asarray(B, np.float64)
    Ad, Bd = (_bf16(A), _bf16(B)) if bf16 else (A, B)
    sq = (A * A).sum(1)[:, None] + (B * B).sum(1)[None, :] - 2.0 * Ad @ Bd.T
    return np.exp(-gamma * np.maximum(sq, 0.0))


def balanced_assign(D: np.ndarray, capacity: int) -> np.ndarray:
    """The divide step's rule: points in order of confidence (the gap between
    their nearest and second-nearest centre, largest first) each take their
    nearest centre that still has room for ``capacity`` points."""
    n, k = D.shape
    pref = np.argsort(D, axis=1, kind="stable")
    conf = (np.partition(D, 1, axis=1)[:, 1] - D.min(axis=1)
            if k > 1 else np.zeros(n))
    room = np.full(k, capacity, np.int64)
    out = np.full(n, -1, np.int64)
    for i in np.argsort(-conf, kind="stable"):
        for c in pref[i]:
            if room[c] > 0:
                out[i] = c
                room[c] -= 1
                break
    return out


def partition_assign(gamma: float, X, Xm, W, rows: int = 4096,
                     bf16: bool = False) -> np.ndarray:
    """The partition that a kernel k-means model defines over ``X``: centre c
    is the kernel-space mean sum_j W[j, c] phi(Xm[j]), the distance
    d(x, c) = K(x, x) - 2 K(x, Xm) W[:, c] + W[:, c]' K(Xm, Xm) W[:, c], a
    centre with no sampled point is never chosen, and the clusters are
    balanced to ceil(n / k) points by ``balanced_assign``.  In float64 on
    the host, or with bf16 operands (``bf16``, the control)."""
    X, W = np.asarray(X), np.asarray(W, np.float64)
    n, k = X.shape[0], W.shape[1]
    s = np.einsum("mk,mk->k", W, _rbf_host(Xm, Xm, gamma, bf16) @ W)
    D = np.concatenate([1.0 - 2.0 * _rbf_host(X[i:i + rows], Xm, gamma, bf16)
                        @ W for i in range(0, n, rows)]) + s[None, :]
    D[:, W.sum(axis=0) <= 0.0] = np.inf
    return balanced_assign(D, -(-n // k))
