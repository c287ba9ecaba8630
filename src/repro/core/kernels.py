"""Kernel functions for DC-SVM.

A ``Kernel`` is a small dataclass carrying the kernel hyper-parameters plus
pure-jnp pairwise evaluation.  All heavy Gram computation goes through
``gram(kernel, X, Y)`` / ``gram_matvec`` which tile the computation; the
Pallas fast paths (``repro.kernels.ops.kernel_matrix`` / ``kernel_matvec``)
are selected via ``use_pallas`` (``resolve_use_pallas(None)`` auto-picks
compiled Pallas on TPU and jnp/XLA elsewhere).

The paper uses the RBF kernel K(x,z) = exp(-gamma ||x-z||^2) for the main
experiments and the degree-3 polynomial kernel K(x,z) = (gamma x'z + coef0)^d
for Section 5's polynomial experiments.  Both are implemented here, plus
linear (the gamma->0 degenerate baseline used in unit tests).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# Gram memory budget in BYTES (2**29 = 512 MiB = the historical 2**27 f32
# element slots, so default behavior is unchanged).  Byte denomination makes
# bf16 storage fit twice the rows the same budget allows f32 — the policy
# knob the GramOperator layer (core.gramop) sizes caches, chunking, and
# spill panels against.
DEFAULT_GRAM_BUDGET = 2 ** 29

# f32 means f32 on every backend.  At DEFAULT precision the TPU (XLA and
# Mosaic alike) may run an f32 contraction as one bf16 pass: the RBF
# expansion xx + yy - 2<x, y> then cancels catastrophically and the solvers'
# gradient matvecs drift by more than the stopping tolerance.  Every f32
# contraction of the solver and serving paths asks for HIGHEST; CPU computes
# f32 exactly either way, so the CPU results are unchanged.
HIGHEST = jax.lax.Precision.HIGHEST


def f32_matmul(a: Array, b: Array) -> Array:
    """``a @ b`` at full f32 precision (see ``HIGHEST``)."""
    return jnp.matmul(a, b, precision=HIGHEST)


def auto_num_chunks(n_rows: int, n_cols: int, itemsize: int = 4,
                    budget_bytes: Optional[int] = None) -> int:
    """Smallest chunk count whose (n_rows/chunks, n_cols) row block fits the
    byte budget — replaces the historical hardcoded ``num_chunks=8``, which
    over-chunks small problems and under-chunks at extreme n.  Chunking only
    partitions output rows, so any chunk count is bit-identical."""
    budget = DEFAULT_GRAM_BUDGET if budget_bytes is None else int(budget_bytes)
    total = int(n_rows) * int(n_cols) * int(itemsize)
    return max(1, min(int(n_rows), -(-total // max(budget, 1))))


def _resolve_cd(compute_dtype, ref_dtype):
    """``None`` — or a policy dtype equal to the data's own — means "don't
    cast": the original (bit-identical) expressions are used."""
    if compute_dtype is None:
        return None
    cd = jnp.dtype(compute_dtype)
    return None if cd == jnp.dtype(ref_dtype) else cd


@dataclasses.dataclass(frozen=True)
class Kernel:
    """Kernel hyper-parameters. ``kind`` in {"rbf", "poly", "linear"}."""

    kind: str = "rbf"
    gamma: float = 1.0
    degree: int = 3
    coef0: float = 0.0

    def __post_init__(self):
        if self.kind not in ("rbf", "poly", "linear"):
            raise ValueError(f"unknown kernel kind: {self.kind}")

    # -- pure-jnp pairwise evaluation ------------------------------------
    def pairwise(self, X: Array, Y: Array, compute_dtype=None) -> Array:
        """K(X, Y): (n, d) x (m, d) -> (n, m), pure jnp (XLA) path.

        ``compute_dtype`` (e.g. "bfloat16") casts the matmul operands only;
        the Gram contraction accumulates in f32 (``preferred_element_type``)
        and the kernel transform runs in f32 — the flash-attention precision
        idiom.  ``None`` keeps the historical exact path."""
        cd = _resolve_cd(compute_dtype, X.dtype)
        if cd is None:
            if self.kind == "linear":
                return f32_matmul(X, Y.T)
            if self.kind == "poly":
                return ((self.gamma * f32_matmul(X, Y.T) + self.coef0)
                        ** self.degree)
            return jnp.exp(-self.gamma * sqdist(X, Y))
        Xc, Yc = X.astype(cd), Y.astype(cd)
        g = jax.lax.dot_general(Xc, Yc, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if self.kind == "linear":
            return g
        if self.kind == "poly":
            return (self.gamma * g + self.coef0) ** self.degree
        # rbf: norms from the *quantized* tiles, accumulated in f32, so the
        # expansion xx + yy - 2g cancels consistently with the matmul inputs
        xx = jnp.sum(Xc.astype(jnp.float32) ** 2, axis=-1)[:, None]
        yy = jnp.sum(Yc.astype(jnp.float32) ** 2, axis=-1)[None, :]
        return jnp.exp(-self.gamma * jnp.maximum(xx + yy - 2.0 * g, 0.0))

    def diag(self, X: Array) -> Array:
        """K(x_i, x_i) for all rows — O(n), never forms the Gram matrix."""
        if self.kind == "linear":
            return jnp.sum(X * X, axis=-1)
        if self.kind == "poly":
            return (self.gamma * jnp.sum(X * X, axis=-1) + self.coef0) ** self.degree
        return jnp.ones(X.shape[0], X.dtype)

    @property
    def k_max(self) -> float:
        """Upper bound on K(x,x) used by the Theorem-2 margin (RBF: 1)."""
        return 1.0 if self.kind == "rbf" else float("inf")


def sqdist(X: Array, Y: Array) -> Array:
    """Squared euclidean distances via the Gram expansion (MXU-friendly)."""
    xx = jnp.sum(X * X, axis=-1)[:, None]
    yy = jnp.sum(Y * Y, axis=-1)[None, :]
    sq = xx + yy - 2.0 * f32_matmul(X, Y.T)
    return jnp.maximum(sq, 0.0)


# ---------------------------------------------------------------------------
# Gram computation.  ``use_pallas`` routes the tile computation through the
# Pallas kernel (validated in interpret mode on CPU; compiled on TPU).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("kernel", "use_pallas", "compute_dtype"))
def gram(kernel: Kernel, X: Array, Y: Array, use_pallas: bool = False,
         compute_dtype: Optional[str] = None) -> Array:
    """Full kernel matrix K(X, Y) of shape (n, m)."""
    if use_pallas:
        from repro.kernels import ops as kops

        return kops.kernel_matrix(X, Y, kernel, compute_dtype=compute_dtype)
    return kernel.pairwise(X, Y, compute_dtype=compute_dtype)


@partial(jax.jit, static_argnames=("kernel", "compute_dtype"))
def gram_blocks(kernel: Kernel, Xc: Array,
                compute_dtype: Optional[str] = None) -> Array:
    """Per-cluster Gram matrices: (k, nc, d) -> (k, nc, nc) via vmap."""
    return jax.vmap(
        lambda Xi: kernel.pairwise(Xi, Xi, compute_dtype=compute_dtype))(Xc)


def resolve_use_pallas(flag: Optional[bool]) -> bool:
    """Backend policy: ``None`` auto-detects (compiled Pallas on TPU, jnp/XLA
    elsewhere — interpret-mode Pallas is a correctness tool, not a fast path
    on CPU)."""
    if flag is None:
        return jax.default_backend() == "tpu"
    return bool(flag)


@partial(jax.jit, static_argnames=("kernel", "num_chunks", "use_pallas",
                                   "compute_dtype", "budget_bytes"))
def gram_matvec(kernel: Kernel, X: Array, v: Array,
                num_chunks: Optional[int] = None, use_pallas: bool = False,
                compute_dtype: Optional[str] = None,
                budget_bytes: Optional[int] = None) -> Array:
    """K(X, X) @ v computed without materializing the Gram matrix.

    ``use_pallas=True`` streams (bm, bn) kernel tiles through VMEM and
    accumulates the matvec in-register (one fused ``kernel_matvec`` call);
    otherwise row chunks via ``lax.map`` — O(n^2 d) compute either way, but
    the fused path's HBM traffic is O(n d) instead of O(n^2 / chunks).
    ``num_chunks=None`` derives the chunk count from the byte budget
    (``auto_num_chunks`` — any chunking is bit-identical, it only partitions
    output rows).  Used for the top-level conquer step when the full Gram
    does not fit.
    """
    if use_pallas:
        from repro.kernels import ops as kops

        return kops.kernel_matvec(X, X, v, kernel,
                                  compute_dtype=compute_dtype)
    n = X.shape[0]
    if num_chunks is None:
        num_chunks = auto_num_chunks(n, n, budget_bytes=budget_bytes)
    pad = (-n) % num_chunks
    Xp = jnp.pad(X, ((0, pad), (0, 0))) if pad else X
    rows = (n + pad) // num_chunks
    Xr = Xp.reshape(num_chunks, rows, -1)

    def one(Xi):
        return f32_matmul(
            kernel.pairwise(Xi, X, compute_dtype=compute_dtype), v)

    return jax.lax.map(one, Xr).reshape(-1)[:n]


def offdiag_mass(kernel: Kernel, X: Array, labels: Array, num_chunks: int = 8) -> Array:
    """D(pi) = sum_{i,j: pi(i) != pi(j)} |K(x_i, x_j)|   (Theorem 1 quantity).

    Chunked over rows so it never materializes the full Gram.
    """
    n = X.shape[0]
    pad = (-n) % num_chunks
    if pad:
        Xp = jnp.concatenate([X, jnp.zeros((pad, X.shape[1]), X.dtype)], 0)
        lp = jnp.concatenate([labels, jnp.full((pad,), -1, labels.dtype)], 0)
        valid = jnp.concatenate([jnp.ones(n, bool), jnp.zeros(pad, bool)], 0)
    else:
        Xp, lp, valid = X, labels, jnp.ones(n, bool)
    rows = Xp.shape[0] // num_chunks
    Xr = Xp.reshape(num_chunks, rows, -1)
    lr = lp.reshape(num_chunks, rows)
    vr = valid.reshape(num_chunks, rows)

    def one(args):
        Xi, li, vi = args
        Krow = jnp.abs(kernel.pairwise(Xi, Xp))          # (rows, n_pad)
        mask = (li[:, None] != lp[None, :]) & vi[:, None] & valid[None, :]
        return jnp.sum(Krow * mask)

    return jnp.sum(jax.lax.map(one, (Xr, lr, vr)))
