"""Two-step kernel kmeans (Ghitta et al., 2011 as used by DC-SVM).

Step 1: run kernel kmeans on m sampled points (m << n) entirely in kernel
space — O(m^2) memory.  Step 2: assign every point to its nearest center via
the (n x m) cross-kernel — O(nmd) compute, never O(n^2).

Centers are represented implicitly: a center c is the kernel-space mean of
the sampled points assigned to it, so distances only need

    d(x, c) = K(x,x) - 2 * K(x, X_m) @ w_c + s_c,
    w_c = H[:, c] / |V_c|,   s_c = w_c' K_mm w_c.

The returned ``KKMeansModel`` carries (X_m, W, s) and is the routing model
used at serving time by early prediction (paper eq. 11).

Balanced partitioning: SPMD shards must be equal-sized, and the paper itself
prefers balanced partitions (Sec. 3).  ``balanced_assign`` does a greedy
capacity-constrained assignment ordered by assignment confidence.  It runs
once per level of every fit, on the fit's critical path while the device
waits: the row reductions (nearest centre, confidence) run on the device
that made the distances, and the greedy walks the points in vectorised
NumPy blocks over the fetched distances, not one at a time.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.kernels import HIGHEST, Kernel, f32_matmul, gram
from repro.obs.spans import span

Array = jax.Array


class KKMeansModel(NamedTuple):
    """Implicit kernel-space centers: d(x,c) = K(x,x) - 2 K(x,Xm) W[:,c] + s[c]."""

    Xm: Array       # (m, d) sampled points
    W: Array        # (m, k) normalized one-hot weights H / counts
    s: Array        # (k,)  per-center self-term  w_c' K_mm w_c

    @property
    def k(self) -> int:
        return self.W.shape[1]


def _center_terms(Kmm: Array, assign: Array, k: int) -> Tuple[Array, Array]:
    H = jax.nn.one_hot(assign, k, dtype=Kmm.dtype)              # (m, k)
    counts = jnp.maximum(H.sum(axis=0), 1.0)
    W = H / counts[None, :]
    M = f32_matmul(Kmm, W)                                              # (m, k)
    s = jnp.einsum("mk,mk->k", W, M, precision=HIGHEST)
    return W, s


@partial(jax.jit, static_argnames=("k", "iters"))
def kernel_kmeans(Kmm: Array, k: int, key: Array, iters: int = 20) -> Tuple[Array, Array, Array]:
    """Kernel kmeans on an (m, m) kernel matrix. Returns (assign, W, s)."""
    m = Kmm.shape[0]
    diag = jnp.diagonal(Kmm)
    # balanced random init (round-robin over a permutation)
    perm = jax.random.permutation(key, m)
    assign0 = jnp.zeros(m, jnp.int32).at[perm].set(jnp.arange(m, dtype=jnp.int32) % k)

    def body(_, assign):
        W, s = _center_terms(Kmm, assign, k)
        D = diag[:, None] - 2.0 * f32_matmul(Kmm, W) + s[None, :]
        new_assign = jnp.argmin(D, axis=1).astype(jnp.int32)
        # reseed ALL empty clusters in one shot: the e-th empty cluster takes
        # the e-th point farthest from its own center.  Reseeding one per
        # iteration leaves up to k-2 phantom centers when argmin collapses
        # many clusters at once (fixed-point at iters < #empties); a phantom
        # center's distance column degenerates to K(x,x) and can capture
        # arbitrary queries at serving time.
        counts = jnp.sum(jax.nn.one_hot(new_assign, k, dtype=Kmm.dtype), axis=0)
        empty = counts <= 0.0
        eids = jnp.nonzero(empty, size=k, fill_value=-1)[0]          # (k,)
        dist_own = D[jnp.arange(m), new_assign]
        order = jnp.argsort(-dist_own)                               # (m,) distinct
        rank = jnp.arange(k)
        # at most m clusters can be populated by m points: empties ranked
        # past m stay empty (the k > m degenerate case must not crash)
        valid = (eids >= 0) & (rank < m)
        targets = jnp.where(valid, order[jnp.clip(rank, 0, m - 1)], m)
        new_assign = new_assign.at[targets].set(                     # m = dropped
            jnp.where(valid, eids, 0).astype(jnp.int32), mode="drop")
        return new_assign

    assign = lax.fori_loop(0, iters, body, assign0)
    W, s = _center_terms(Kmm, assign, k)
    return assign, W, s


@partial(jax.jit, static_argnames=("kernel", "use_pallas"))
def assign_points(
    kernel: Kernel, model: KKMeansModel, X: Array, use_pallas: bool = False
) -> Tuple[Array, Array]:
    """Nearest-center assignment for arbitrary points. Returns (assign, D).

    Empty centers (zero W column — no sampled point assigned) have no
    kernel-space location: their distance column degenerates to K(x,x)
    (a constant 1 for RBF), so without masking a phantom center can win
    ``argmin`` and silently capture queries.  Their distances are forced
    to +inf so only populated centers are routable.
    """
    Knm = gram(kernel, X, model.Xm, use_pallas=use_pallas)      # (n, m)
    D = kernel.diag(X)[:, None] - 2.0 * f32_matmul(Knm, model.W) + model.s[None, :]
    empty = jnp.sum(model.W, axis=0) <= 0.0                     # (k,)
    D = jnp.where(empty[None, :], jnp.inf, D)
    return jnp.argmin(D, axis=1).astype(jnp.int32), D


def route(kernel: Kernel, model: KKMeansModel, X: Array) -> Array:
    """Serving-time router: cluster id per query point (early prediction)."""
    return assign_points(kernel, model, X)[0]


@jax.jit
def _nearest_two(D: Array) -> Tuple[Array, Array, Array]:
    """Per row of the (n, k) distances: the nearest centre (the lowest index
    among ties), its distance, and the second-smallest distance (the row
    minimum with the nearest masked; +inf when k = 1).  These are the only
    full passes over D, so they run where D is made, on the device."""
    first = jnp.argmin(D, axis=1)
    nearest = jnp.arange(D.shape[1])[None, :] == first[:, None]
    d0 = jnp.min(D, axis=1)
    d1 = jnp.min(jnp.where(nearest, jnp.inf, D), axis=1)
    return first, d0, d1


def _balance(D: np.ndarray, capacity: int,
             nearest: Tuple[Array, Array, Array]) -> Tuple[np.ndarray, int, int]:
    """``balanced_assign`` with its counters: ``(assign, redirected, steps)``,
    the points not at their nearest centre and the greedy's block steps.
    ``nearest`` is ``_nearest_two`` of the same distances."""
    n, k = D.shape
    if n > k * capacity:
        raise ValueError(f"capacity {capacity} x {k} clusters < n={n}")
    # blocks of rows are gathered below; a TPU hands an (n, k < 128) array
    # over column-major, where each gathered row is a strided read
    D = np.ascontiguousarray(D)
    first, d0, d1 = jax.device_get(nearest)
    gap = d1.astype(np.float64) - d0.astype(np.float64)
    order = np.argsort(-gap, kind="stable")    # big gap first, ties by index
    rows = np.arange(n)

    # The greedy over blocks of B ordered points.  A block's points take
    # their nearest open centre; ranking each among the block's points that
    # chose the same centre finds the first point that would overfill its
    # centre.  The points before it get what the one-at-a-time rule gives
    # them (a centre that fills inside the block matters only to the points
    # that chose it), so the step accepts them, shuts the full centres and
    # resumes at that point.  Each step accepts a point and each that stops
    # early shuts a centre: at most about n / B + k steps.
    room = np.full(k, capacity, np.int64)
    shut = np.zeros(k, D.dtype)                # +inf on full centres
    out = np.empty(n, np.int32)
    B = -(-n // k)
    buf = np.empty((B, k), D.dtype)
    pos = steps = 0
    while pos < n:
        steps += 1
        blk = order[pos:pos + B]
        b = len(blk)
        sub = np.take(D, blk, axis=0, out=buf[:b])
        sub += shut
        choice = np.argmin(sub, axis=1)
        # a row whose open centres are all +inf (centres no sampled point
        # reached, ``assign_points``) takes the lowest open one, as a stable
        # sort of its row would; argmin may have picked a shut one
        choice[np.isinf(sub[rows[:b], choice])] = np.argmax(room > 0)
        srt = np.argsort(choice, kind="stable")
        cs = choice[srt]
        starts = np.concatenate(([True], cs[1:] != cs[:-1]))
        rank = rows[:b] - np.maximum.accumulate(np.where(starts, rows[:b], 0))
        over = srt[rank >= room[cs]]
        m = int(over.min()) if len(over) else b
        out[blk[:m]] = choice[:m]
        room -= np.bincount(choice[:m], minlength=k)
        shut[room == 0] = np.inf
        pos += m
    return out, int(np.count_nonzero(out != first)), steps


def balanced_assign(D: np.ndarray, capacity: int) -> np.ndarray:
    """Greedy capacity-constrained assignment from an (n, k) distance matrix.

    Points are processed in order of confidence (gap between best and
    second-best center, computed in float64; ties by lowest index); each
    takes its nearest center that still has room (ties by lowest index, so a
    +inf column — an empty center — fills only after every finite one of the
    point's row is full).  Guarantees every cluster gets at most
    ``capacity`` points; with n <= k * capacity every point is assigned.
    ``D`` is taken in float32, as ``assign_points`` makes it.
    """
    D = np.asarray(D, np.float32)
    return _balance(D, capacity, _nearest_two(D))[0]


@dataclasses.dataclass(frozen=True)
class Partition:
    """A (near-)balanced partition of n points into k clusters, padded layout.

    ``idx[c]`` holds the original indices of cluster c padded with -1 up to
    ``nc`` slots; ``mask[c]`` marks real entries.  The padded layout lets the
    divide step gather every cluster into a dense (k, nc, d) tensor and solve
    all k subproblems in ONE vmapped CD call (pad slots are excluded via the
    solver's active mask).
    """

    assign: np.ndarray      # (n,) cluster id per original index
    idx: np.ndarray         # (k, nc) original indices, -1 for padding
    mask: np.ndarray        # (k, nc) True for real points
    k: int
    nc: int                 # slots per cluster (k * nc >= n)
    model: KKMeansModel     # routing model (implicit centers)
    redirected: int = 0     # points the balance moved off their nearest center

    @staticmethod
    def build(assign: np.ndarray, k: int, model: KKMeansModel,
              redirected: int = 0) -> "Partition":
        n = assign.shape[0]
        counts = np.bincount(assign, minlength=k)
        nc = int(counts.max())
        idx = np.full((k, nc), -1, dtype=np.int64)
        mask = np.zeros((k, nc), dtype=bool)
        for c in range(k):
            members = np.nonzero(assign == c)[0]
            idx[c, : len(members)] = members
            mask[c, : len(members)] = True
        return Partition(assign=assign, idx=idx, mask=mask, k=k, nc=nc,
                         model=model, redirected=redirected)

    def gather(self, A: Array) -> Array:
        """Gather per-cluster values: (n, ...) -> (k, nc, ...); pads read row 0."""
        return jnp.asarray(A)[np.maximum(self.idx, 0)]

    def scatter(self, Ac: Array, n: int, fill: float = 0.0) -> Array:
        """Scatter (k, nc, ...) back to (n, ...). Pad slots are dropped."""
        flat_idx = jnp.asarray(np.where(self.mask, self.idx, n).reshape(-1))
        flat_val = jnp.asarray(Ac).reshape((self.k * self.nc,) + Ac.shape[2:])
        out = jnp.full((n + 1,) + flat_val.shape[1:], fill, flat_val.dtype)
        out = out.at[flat_idx].set(flat_val)
        return out[:n]


def two_step_kernel_kmeans(
    kernel: Kernel,
    X: Array,
    k: int,
    key: Array,
    m: int = 1000,
    iters: int = 20,
    sample_idx: Optional[Array] = None,
    balanced: bool = True,
    use_pallas: bool = False,
    *,
    span_prefix: str,
) -> Partition:
    """The paper's clustering step. ``sample_idx`` overrides the random sample
    (adaptive clustering passes the current support-vector set here).

    The host part is named by spans ``<span_prefix>/fetch`` (the wait for
    the device and the copy of the distances or the assignment),
    ``<span_prefix>/balance`` (``balanced_assign``; identifiers
    ``redirected`` and ``steps``, the greedy's counters) and
    ``<span_prefix>/partition`` (``Partition.build``); a fit passes
    ``divide/level<l>``."""
    n = X.shape[0]
    m = min(m, n)
    # independent streams for the m-point sample and the kmeans init: reusing
    # ``key`` for both correlates the sample with the init permutation
    key_sample, key_init = jax.random.split(key)
    if sample_idx is None:
        sample_idx = jax.random.choice(key_sample, n, shape=(m,), replace=False)
    else:
        sample_idx = jnp.asarray(sample_idx)
        m = sample_idx.shape[0]
    Xm = X[sample_idx]
    Kmm = gram(kernel, Xm, Xm, use_pallas=use_pallas)
    _, W, s = kernel_kmeans(Kmm, k, key_init, iters=iters)
    model = KKMeansModel(Xm=Xm, W=W, s=s)
    assign, D = assign_points(kernel, model, X, use_pallas=use_pallas)
    redirected = 0
    if balanced:
        capacity = -(-n // k)  # ceil
        nearest = _nearest_two(D)
        with span(f"{span_prefix}/fetch"):
            D = np.asarray(D)
        with span(f"{span_prefix}/balance") as tag:
            assign, redirected, steps = _balance(D, capacity, nearest)
            tag(redirected=redirected, steps=steps)
    else:
        with span(f"{span_prefix}/fetch"):
            assign = np.asarray(assign)
    with span(f"{span_prefix}/partition"):
        return Partition.build(np.asarray(assign, np.int32), k, model,
                               redirected=redirected)
