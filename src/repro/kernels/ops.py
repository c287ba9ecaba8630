"""Public jit'd wrappers around the Pallas kernels.

Handles padding to tile multiples, dtype policy, and backend dispatch:
compiled Pallas on TPU, ``interpret=True`` (Python evaluation of the kernel
body) elsewhere — the correctness-validation mode this container uses.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import kermat as _kermat
from repro.kernels import kermatvec as _kermatvec
from repro.kernels import kmeans_assign as _assign
from repro.kernels import cd_update as _cd
from repro.kernels import greedy_cd as _greedy
from repro.kernels import small_qp as _small_qp


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(A: jax.Array, mult: int) -> Tuple[jax.Array, int]:
    n = A.shape[0]
    pad = (-n) % mult
    if pad:
        A = jnp.pad(A, ((0, pad),) + ((0, 0),) * (A.ndim - 1))
    return A, n


def _cd_static(compute_dtype, ref_dtype):
    """Normalize the precision policy to a canonical static string: ``None``
    — or a dtype equal to the data's own — keeps the exact historical kernel
    body (no cast inserted, one trace cache entry)."""
    if compute_dtype is None:
        return None
    cd = jnp.dtype(compute_dtype)
    return None if cd == jnp.dtype(ref_dtype) else str(cd)


def kernel_matrix(X: jax.Array, Y: jax.Array, kernel, bm: int = 256,
                  bn: int = 256, compute_dtype=None) -> jax.Array:
    """K(X, Y) via the tiled Pallas kernel. ``kernel`` is a core.kernels.Kernel.

    ``compute_dtype`` (e.g. "bfloat16") quantizes the operand tiles inside
    the kernel body; accumulation stays f32 (DESIGN.md §12)."""
    bm = min(bm, max(8, X.shape[0]))
    bn = min(bn, max(8, Y.shape[0]))
    Xp, n = _pad_rows(X, bm)
    Yp, m = _pad_rows(Y, bn)
    out = _kermat.kermat(
        Xp, Yp, kind=kernel.kind, gamma=float(kernel.gamma),
        degree=int(kernel.degree), coef0=float(kernel.coef0),
        bm=bm, bn=bn, interpret=_interpret(),
        compute_dtype=_cd_static(compute_dtype, X.dtype),
    )
    return out[:n, :m]


def kernel_matvec(X: jax.Array, Z: jax.Array, v: jax.Array, kernel,
                  bm: int = 256, bn: int = 256,
                  compute_dtype=None) -> jax.Array:
    """out (n,) = K(X, Z) @ v via the streaming Pallas kernel.

    Zero-padded Z rows carry zero v weights, so they contribute nothing to
    the accumulated output for every kernel kind.
    """
    bm = min(bm, max(8, X.shape[0]))
    bn = min(bn, max(8, Z.shape[0]))
    Xp, n = _pad_rows(X, bm)
    Zp, _ = _pad_rows(Z, bn)
    vp, _ = _pad_rows(v, bn)
    out = _kermatvec.kernel_matvec(
        Xp, Zp, vp, kind=kernel.kind, gamma=float(kernel.gamma),
        degree=int(kernel.degree), coef0=float(kernel.coef0),
        bm=bm, bn=bn, interpret=_interpret(),
        compute_dtype=_cd_static(compute_dtype, X.dtype),
    )
    return out[:n]


def q_rows(X: jax.Array, y: jax.Array, Xb: jax.Array, yb: jax.Array,
           kernel, bm: int = 256, bn: int = 256,
           compute_dtype=None) -> jax.Array:
    """Signed generalized-dual rows ``Q[b, :] = y_b * (K(X_b, X) ∘ y)`` of
    shape (B, n) via the tiled Pallas kernel matrix (Q is symmetric, so the
    block's rows double as its columns — the cache-refill unit shared by the
    matvec solver and the distributed conquer)."""
    Kb = kernel_matrix(Xb, X, kernel, bm=bm, bn=bn,
                       compute_dtype=compute_dtype)
    return yb[:, None] * (Kb * y[None, :])


def kmeans_assign(X: jax.Array, Xm: jax.Array, W: jax.Array, s: jax.Array,
                  gamma: float, bm: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Fused assignment. W: (m, k), s: (k,). Returns (assign (n,), scores (n, k))."""
    kreal = W.shape[1]
    kpad = max(128, -(-kreal // 128) * 128)
    Wp = jnp.pad(W, ((0, 0), (0, kpad - kreal)))
    sp = jnp.pad(s, (0, kpad - kreal), constant_values=jnp.inf)[None, :]
    bm = min(bm, max(8, X.shape[0]))
    Xp, n = _pad_rows(X, bm)
    assign, scores = _assign.kmeans_assign(
        Xp, Xm, Wp, sp, gamma=float(gamma), bm=bm, interpret=_interpret()
    )
    return assign[:n], scores[:n, :kreal]


def cd_column_update(X: jax.Array, y: jax.Array, Xb: jax.Array, w: jax.Array,
                     kernel, bm: int = 512, compute_dtype=None) -> jax.Array:
    """dg = y * (K(X, Xb) @ w) via the fused Pallas kernel.

    ``y`` is the generalized dual's sign vector ``s`` — class labels for
    C-SVC, the mixed (+1, -1) mirror signs of epsilon-SVR's duplicated-row
    dual — and ``w = s_b * delta``; both are plain data, so every task flows
    through the same kernel (parity pinned for non-tile-aligned SVR shapes
    in tests/test_conquer_pallas.py).
    """
    bm = min(bm, max(8, X.shape[0]))
    Xp, n = _pad_rows(X, bm)
    yp, _ = _pad_rows(y, bm)
    out = _cd.cd_column_update(
        Xp, yp, Xb, w, kind=kernel.kind, gamma=float(kernel.gamma),
        degree=int(kernel.degree), coef0=float(kernel.coef0),
        bm=bm, interpret=_interpret(),
        compute_dtype=_cd_static(compute_dtype, X.dtype),
    )
    return out[:n]


def small_qp_sweep(QbbT: jax.Array, gb: jax.Array, ab: jax.Array,
                   cb: jax.Array, diag: jax.Array, *, sweeps: int) -> jax.Array:
    """New a_b after ``sweeps`` cyclic CD passes on the B×B box sub-QP, the
    whole sweep in one Pallas kernel.  ``QbbT`` is ``Qbb.T``; ``cb`` and
    ``diag`` (the guarded diagonal of ``Qbb``) are (B,) vectors."""
    return _small_qp.small_qp_sweep(QbbT, gb, ab, cb, diag, sweeps=sweeps,
                                    interpret=_interpret())


def greedy_cd(QT: jax.Array, alpha: jax.Array, g: jax.Array, c: jax.Array,
              d: jax.Array, mask: jax.Array, tol: jax.Array, *,
              max_iters: int):
    """Greedy single-coordinate CD on a batch of dense box QPs, each whole
    solve in one Pallas kernel.  ``QT`` is (b, n, n), each problem's
    ``Q.T``; ``alpha``, ``g``, ``c``, ``d`` (the guarded diagonal of Q) and
    ``mask`` are (b, n), ``tol`` (b,).  Returns ``(alpha, g, iters,
    pg_max)``."""
    return _greedy.greedy_cd(QT, alpha, g, c, d, mask, tol,
                             max_iters=max_iters, interpret=_interpret())
