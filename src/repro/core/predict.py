"""Prediction strategies for DC-SVM models (paper Sec. 4, Table 1).

All strategies are task-uniform: they score with the collapsed decision
coefficients ``beta`` (``model.weights``) over the base points —
``beta = y ∘ alpha`` for classification, ``beta = alpha - alpha*`` for
epsilon-SVR — so one code path serves C-SVC, weighted C-SVC, and
regression.  ``predict_*`` applies ``sign`` for classification and returns
the raw decision value for regression tasks.

* ``decision_exact``  — f(x) = sum_i beta_i K(x, x_i); used with the
  final alpha (exact model) or with a level-l alpha (paper eq. 10, the
  "naive" early strategy).
* ``decision_early``  — paper eq. 11: route x to its nearest kernel-kmeans
  cluster and score with ONLY that cluster's local model.  This is exactly
  prediction under the block-diagonal kernel K-bar of Lemma 1, and is the
  paper's recommended early strategy (O(|S| d / k) per query).
* ``decision_bcm``    — Bayesian Committee Machine combination [Tresp, 2000]
  of the k local models, the paper's Table-1 baseline: precision-weighted
  average of local decisions with a GP-style predictive variance per cluster.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.dcsvm import DCSVMModel
from repro.core.kernels import (Kernel, f32_matmul, gram,
                                resolve_use_pallas)
from repro.core.kkmeans import KKMeansModel, assign_points

Array = jax.Array


# ---------------------------------------------------------------------------
# Bucketed per-cluster scoring (shared by early prediction, its OVA variant,
# and the serving engine)
# ---------------------------------------------------------------------------

def bucketed_cluster_scores(kern: Kernel, Xq: Array, cid: Array,
                            Xblocks: Array, Wblocks: Array, cap: int,
                            use_pallas: bool = False,
                            offsets: Optional[Array] = None,
                            compute_dtype: Optional[str] = None) -> Array:
    """Score every query against ONLY its assigned cluster's block.

    ``Xblocks``: (k, nc, d) per-cluster member coordinates, ``Wblocks``:
    (k, nc, C) per-member weights (zero on padding slots).  Returns (nq, C).
    ``offsets`` (k, C), when given, is subtracted from each query's score
    according to its assigned cluster — the per-cluster decision offsets
    rho_c of early-stopped equality-constrained models (one-class SVM).

    Queries are bucketed into a (k, cap, d) buffer and all clusters are
    scored in one vmapped kernel matvec.  Clusters holding more than ``cap``
    queries are handled by additional rounds of the SAME fused program
    inside an on-device ``lax.while_loop`` — the common no-overflow case
    runs exactly one round, and no path ever forces a host sync.  Queries
    outside the current round target a dropped out-of-bounds buffer slot,
    so they can never collide with (and overwrite) a real query's slot.
    """
    nq, d = Xq.shape
    k = Xblocks.shape[0]
    n_out = Wblocks.shape[-1]
    if nq == 0:
        return jnp.zeros((0, n_out), Xq.dtype)
    acc = jnp.promote_types(Xq.dtype, jnp.float32)

    order = jnp.argsort(cid)
    sc = cid[order]
    seg_start = jnp.searchsorted(sc, jnp.arange(k), side="left")
    pos = jnp.arange(nq) - seg_start[sc]        # rank of each query in its cluster
    pos_max = jnp.max(pos)

    if use_pallas and n_out == 1:
        from repro.kernels import ops as kops

        def one(qc, Xc, wc):
            return kops.kernel_matvec(qc, Xc, wc[:, 0], kern,
                                      compute_dtype=compute_dtype)[:, None]
    elif use_pallas:
        from repro.kernels import ops as kops

        def one(qc, Xc, wc):
            return f32_matmul(kops.kernel_matrix(qc, Xc, kern,
                                         compute_dtype=compute_dtype), wc)
    else:
        def one(qc, Xc, wc):
            return f32_matmul(
                kern.pairwise(qc, Xc, compute_dtype=compute_dtype), wc)

    def body(carry):
        out, r = carry
        base = r * cap
        in_r = (pos >= base) & (pos < base + cap)
        row = jnp.where(in_r, sc, k)                             # k = dropped
        col = jnp.where(in_r, pos - base, 0)
        qbuf = jnp.zeros((k, cap, d), Xq.dtype).at[row, col].set(
            Xq[order], mode="drop")
        scores = jax.vmap(one)(qbuf, Xblocks, Wblocks)           # (k, cap, C)
        vals = jnp.where(in_r[:, None],
                         scores[jnp.where(in_r, sc, 0), col], 0.0)
        return out.at[order].add(vals.astype(acc)), r + 1

    def cond(carry):
        _, r = carry
        return r * cap <= pos_max

    out0 = jnp.zeros((nq, n_out), acc)
    out, _ = jax.lax.while_loop(cond, body, (out0, jnp.zeros((), jnp.int32)))
    if offsets is not None:
        out = out - offsets[cid]
    return out.astype(Xq.dtype)


@partial(jax.jit, static_argnames=("kern", "cap", "use_pallas", "compute_dtype"))
def _early_program(kern: Kernel, Xq: Array, route_model: KKMeansModel,
                   Xblocks: Array, Wblocks: Array, cap: int,
                   use_pallas: bool = False,
                   offsets: Optional[Array] = None,
                   compute_dtype: Optional[str] = None) -> Array:
    """Route + bucketed local scoring as ONE compiled program."""
    cid, _ = assign_points(kern, route_model, Xq, use_pallas=use_pallas)
    return bucketed_cluster_scores(kern, Xq, cid, Xblocks, Wblocks, cap,
                                   use_pallas=use_pallas, offsets=offsets,
                                   compute_dtype=compute_dtype)


@partial(jax.jit, static_argnames=("kern", "chunk", "use_pallas",
                                   "compute_dtype"))
def _decision_scan(kern: Kernel, Xq: Array, Xs: Array, W: Array,
                   chunk: int, use_pallas: bool = False,
                   compute_dtype: Optional[str] = None) -> Array:
    """K(Xq, Xs) @ W as ONE compiled scan over SV chunks (no per-chunk
    Python dispatch, and never more than an (nq, chunk) kernel block live).
    W is (ns, C) — one weight column per output (C = 1 binary,
    C = n_classes one-vs-all).  Zero-padded SV rows carry zero weights."""
    ns, d = Xs.shape
    chunk = min(chunk, ns)
    pad = (-ns) % chunk
    Xsp = jnp.pad(Xs, ((0, pad), (0, 0)))
    Wp = jnp.pad(W, ((0, pad), (0, 0)))
    if use_pallas:
        from repro.kernels import ops as kops

    def step(acc, xw):
        Xc, wc = xw
        Kc = (kops.kernel_matrix(Xq, Xc, kern, compute_dtype=compute_dtype)
              if use_pallas
              else kern.pairwise(Xq, Xc, compute_dtype=compute_dtype))
        return acc + f32_matmul(Kc, wc), None

    out, _ = jax.lax.scan(
        step, jnp.zeros((Xq.shape[0], W.shape[1]), Xq.dtype),
        (Xsp.reshape(-1, chunk, d), Wp.reshape(-1, chunk, W.shape[1])))
    return out


def _is_regression(model) -> bool:
    task = getattr(model, "task", None)
    return bool(task is not None and task.is_regression)


def _offset(model) -> float:
    """Decision offset rho of equality-constrained tasks (one-class SVM:
    f(x) = sum_i beta_i K(x_i, x) - rho); 0 for every box-family task."""
    rho = getattr(model, "rho", None)
    return 0.0 if rho is None else float(rho)


def _labels(model, d: Array) -> Array:
    """Decision values -> predictions: raw values for regression, +/-1 for
    classification.  One-class models threshold with ``d >= 0 -> +1``
    (inlier), matching ``serve_batch``'s ocsvm path exactly — ``jnp.sign``
    would emit 0 for boundary points (f(x) == rho) and the two sides of the
    serving round trip would disagree on them."""
    if _is_regression(model):
        return d
    task = getattr(model, "task", None)
    if task is not None and getattr(task, "has_rho_offset", False):
        return jnp.where(d >= 0, 1.0, -1.0).astype(d.dtype)
    return jnp.sign(d)


def decision_exact(model: DCSVMModel, Xq: Array, chunk: int = 4096,
                   use_pallas: Optional[bool] = None) -> Array:
    """f(x) = sum_i beta_i K(x_i, x) over all support vectors (eq. 10 when
    alpha is a level-l solution); task-uniform through ``model.weights``.
    Pallas path: one streaming ``kernel_matvec`` call — the (nq, |S|)
    kernel block never hits HBM; otherwise a single fused scan over SV
    chunks."""
    sv = model.sv_index
    off = _offset(model)
    if len(sv) == 0:
        return jnp.zeros(Xq.shape[0], Xq.dtype) - off
    if use_pallas is None:
        use_pallas = model.config.use_pallas
    Xs = model.X[jnp.asarray(sv)]
    w = model.weights[jnp.asarray(sv)]
    kern = model.config.kernel
    cd = getattr(model.config, "compute_dtype", None)
    if resolve_use_pallas(use_pallas):
        from repro.kernels import ops as kops

        return kops.kernel_matvec(Xq, Xs, w, kern,
                                  compute_dtype=cd).astype(Xq.dtype) - off
    return _decision_scan(kern, Xq, Xs, w[:, None], chunk,
                          compute_dtype=cd)[:, 0] - off


def predict_exact(model: DCSVMModel, Xq: Array) -> Array:
    """Class labels for classification tasks; raw regression values for
    epsilon-SVR (the decision function IS the prediction)."""
    return _labels(model, decision_exact(model, Xq))


def _early_blocks(model, w: Array):
    """Per-cluster member blocks (k, nc, d) and weights (k, nc, C) for a
    partitioned model; ``w`` is (n,) or (n, C)."""
    part = model.partition
    members = jnp.asarray(np.maximum(part.idx, 0))           # (k, nc)
    mmask = jnp.asarray(part.mask)
    Xm = model.X[members]                                    # (k, nc, d)
    if w.ndim == 1:
        w = w[:, None]
    wm = jnp.where(mmask[..., None], w[members], 0.0)        # (k, nc, C)
    return Xm, wm


def early_capacity(nq: int, k: int) -> int:
    """Query-buffer slots per cluster: 2x the balanced load.  Overflow past
    this capacity is handled by extra on-device rounds, never dropped.

    ``cap`` is a STATIC argument of the fused early program — every distinct
    value is a fresh jit signature and a fresh compile.  Serving paths must
    therefore derive it from a padded bucket size (``bucket_size``), never
    from the live ragged batch size: feeding raw ``Xq.shape[0]`` here is
    exactly the per-batch-size recompile bug the bucketed serving path
    exists to fix."""
    return int(min(nq, max(8, -(-2 * nq // k))))


def bucket_size(nq: int, lo: int = 8, hi: int = 4096) -> int:
    """Pad bucket for a ragged request batch: the smallest power of two
    >= ``nq``, clamped below by ``lo``; batches past ``hi`` round up to a
    multiple of ``hi``.  Ragged arrival sizes collapse onto O(log hi)
    distinct (batch, cap) jit signatures, so the serving caches stay warm
    forever once each bucket has compiled."""
    if nq <= 0:
        return lo
    if nq > hi:
        return -(-nq // hi) * hi
    return max(lo, 1 << (nq - 1).bit_length())


def decision_early(model: DCSVMModel, Xq: Array,
                   use_pallas: Optional[bool] = None) -> Array:
    """Paper eq. 11: nearest-cluster routing + local-model scoring.

    Vectorized MoE-style dispatch (the same compute shape as our MoE layer):
    route every query to its cluster, sort queries by cluster id, batch each
    cluster's queries against ONLY that cluster's members — one vmapped
    kernel matvec, total work O(nq * (n/k) * d) = the paper's 1/k serving
    win.  On the Pallas path each cluster's scoring streams through the
    fused ``kernel_matvec`` kernel (vmapped over clusters).

    Routing and scoring run as ONE compiled program; queries overflowing a
    cluster's buffer capacity are handled by extra rounds of the same
    program inside the device-side loop (see ``bucketed_cluster_scores``) —
    no host sync on any path.
    """
    part = model.partition
    assert part is not None, "early prediction requires a partitioned model"
    kern = model.config.kernel
    if use_pallas is None:
        use_pallas = model.config.use_pallas
    use_pallas = resolve_use_pallas(use_pallas)
    Xm, wm = _early_blocks(model, model.weights)
    cap = early_capacity(Xq.shape[0], part.k)
    # early-stopped equality models: each cluster's local sub-QP carries its
    # own multiplier, so the offset is per assigned cluster, not global
    rho_c = getattr(model, "rho_clusters", None)
    offsets = None if rho_c is None else jnp.asarray(rho_c)[:, None]
    off = 0.0 if offsets is not None else _offset(model)
    return _early_program(kern, Xq, part.model, Xm, wm, cap,
                          use_pallas=use_pallas, offsets=offsets,
                          compute_dtype=getattr(model.config, "compute_dtype",
                                                None))[:, 0] - off


def predict_early(model: DCSVMModel, Xq: Array) -> Array:
    return _labels(model, decision_early(model, Xq))


def decision_bcm(model: DCSVMModel, Xq: Array, noise: float = 1e-2,
                 max_sv_per_cluster: int = 512) -> Array:
    """BCM combination of the k local models (paper's Table-1 baseline).

    Each cluster contributes its local decision f_c(x) weighted by the
    inverse GP predictive variance sigma_c^2(x) = K(x,x) - k_c' (K_cc +
    noise I)^-1 k_c computed on (a subsample of) the cluster's support
    vectors.  Precision-weighted averaging follows Tresp (2000); we use the
    common precision-normalized form (the (k-1)/K(x,x) prior correction is
    absorbed into the normalization, which only rescales decisions and does
    not change the sign/accuracy).

    Equality-family offsets are applied PER COMMITTEE MEMBER before the
    combination: an early-stopped one-class model's clusters carry their
    own multipliers rho_c, so member c contributes f_c(x) - rho_c (a
    globally trained model's members share the one global rho).
    """
    W = model.weights[:, None]
    active = np.asarray(model.weights) != 0
    rho_c = getattr(model, "rho_clusters", None)
    if rho_c is not None:
        offsets = np.asarray(rho_c, np.float64)
    else:
        offsets = np.full(model.partition.k, _offset(model))
    scores = _bcm_scores(model, Xq, W, active, noise, max_sv_per_cluster,
                         offsets=offsets)
    return scores[:, 0]


def _bcm_scores(model, Xq: Array, W: Array, active: np.ndarray, noise: float,
                max_sv_per_cluster: int,
                offsets: Optional[np.ndarray] = None) -> Array:
    """Shared BCM combination: W is (n, C) decision weights, ``active`` marks
    the support vectors eligible per cluster.  The GP predictive variance is
    label-independent, so one variance per cluster weights all C outputs.
    ``offsets`` (k,) is subtracted from cluster c's local decision before
    the precision weighting (equality-family rho_c; None = no offsets)."""
    part = model.partition
    assert part is not None
    kern = model.config.kernel
    nq = Xq.shape[0]
    num = np.zeros((nq, W.shape[1]), np.float64)
    den = np.zeros((nq, 1), np.float64) + 1e-12
    W_np = np.asarray(W)
    for c in range(part.k):
        members = part.idx[c][part.mask[c]]
        sv = members[active[members]]
        if len(sv) == 0:
            continue
        if len(sv) > max_sv_per_cluster:
            sv = sv[:: len(sv) // max_sv_per_cluster + 1]
        Xs = model.X[jnp.asarray(sv)]
        Kss = np.asarray(gram(kern, Xs, Xs)) + noise * np.eye(len(sv))
        Kqs = np.asarray(gram(kern, Xq, Xs))
        f_c = Kqs @ W_np[sv]                                  # (nq, C)
        if offsets is not None:
            f_c = f_c - offsets[c]
        sol = np.linalg.solve(Kss, Kqs.T)                     # (s, nq)
        var = np.asarray(kern.diag(Xq)) - np.einsum("qs,sq->q", Kqs, sol)
        var = np.maximum(var, noise)[:, None]
        num += f_c / var
        den += 1.0 / var
    return jnp.asarray((num / den).astype(np.float32))


def predict_bcm(model: DCSVMModel, Xq: Array) -> Array:
    return _labels(model, decision_bcm(model, Xq))


def accuracy(y_true: Array, y_pred: Array) -> float:
    return float(jnp.mean((jnp.sign(y_true) == jnp.sign(y_pred)).astype(jnp.float32)))


def mse(y_true: Array, y_pred: Array) -> float:
    """Mean squared error (regression tasks)."""
    return float(jnp.mean((jnp.asarray(y_true) - jnp.asarray(y_pred)) ** 2))


def mae(y_true: Array, y_pred: Array) -> float:
    """Mean absolute error (regression tasks)."""
    return float(jnp.mean(jnp.abs(jnp.asarray(y_true) - jnp.asarray(y_pred))))


def recall(y_true: Array, y_pred: Array, label: float = 1.0) -> float:
    """Recall of one class (minority-class metric for weighted C-SVC)."""
    t = np.asarray(y_true) == label
    if not t.any():
        return float("nan")
    return float(np.mean(np.asarray(y_pred)[t] == label))


def precision(y_true: Array, y_pred: Array, label: float = 1.0) -> float:
    """Precision of one class (anomaly metric: label=-1 for outliers)."""
    p = np.asarray(y_pred) == label
    if not p.any():
        return float("nan")
    return float(np.mean(np.asarray(y_true)[p] == label))


def f1(y_true: Array, y_pred: Array, label: float = 1.0) -> float:
    """F1 of one class — the anomaly-detection headline metric for
    one-class SVM (label=-1 marks outliers)."""
    t = np.asarray(y_true) == label
    p = np.asarray(y_pred) == label
    tp = float(np.sum(t & p))
    denom = 2.0 * tp + float(np.sum(~t & p)) + float(np.sum(t & ~p))
    return 0.0 if denom == 0 else 2.0 * tp / denom


# ---------------------------------------------------------------------------
# One-vs-all (multiclass) variants: per-class decision values + argmax.
# ``model`` is a core.multiclass.MulticlassModel (duck-typed: needs config,
# X, Y (n_classes, n), alpha (n_classes, n), classes, partition, sv_union).
# ---------------------------------------------------------------------------

def _ova_weights(model) -> Array:
    """(n, n_classes) decision weights: column c is alpha_c * y_c."""
    return (model.alpha * model.Y).T


def decision_exact_ova(model, Xq: Array, chunk: int = 4096,
                       use_pallas: Optional[bool] = None) -> Array:
    """(nq, n_classes) exact decision values over the SV union — one shared
    kernel evaluation per (query, SV) pair serves every class (the class
    axis is a plain matmul against the stacked weight columns)."""
    sv = model.sv_union
    n_cls = model.Y.shape[0]
    if len(sv) == 0:
        return jnp.zeros((Xq.shape[0], n_cls), Xq.dtype)
    if use_pallas is None:
        use_pallas = model.config.use_pallas
    Xs = model.X[jnp.asarray(sv)]
    Ws = _ova_weights(model)[jnp.asarray(sv)]                # (ns, n_classes)
    kern = model.config.kernel
    return _decision_scan(kern, Xq, Xs, Ws, chunk,
                          use_pallas=resolve_use_pallas(use_pallas),
                          compute_dtype=getattr(model.config, "compute_dtype",
                                                None))


def decision_early_ova(model, Xq: Array,
                       use_pallas: Optional[bool] = None) -> Array:
    """Eq.-11 early prediction for one-vs-all: each query is routed ONCE and
    all n_classes local machines score it against the same gathered cluster
    block (the kernel rows are shared; only the weight columns differ)."""
    part = model.partition
    assert part is not None, "early prediction requires a partitioned model"
    if use_pallas is None:
        use_pallas = model.config.use_pallas
    use_pallas = resolve_use_pallas(use_pallas)
    Xm, wm = _early_blocks(model, _ova_weights(model))
    cap = early_capacity(Xq.shape[0], part.k)
    return _early_program(model.config.kernel, Xq, part.model, Xm, wm, cap,
                          use_pallas=use_pallas,
                          compute_dtype=getattr(model.config, "compute_dtype",
                                                None))


def decision_bcm_ova(model, Xq: Array, noise: float = 1e-2,
                     max_sv_per_cluster: int = 512) -> Array:
    """BCM combination for one-vs-all — the per-cluster GP variance is
    label-independent, so one variance weighting serves all classes."""
    active = np.any(np.asarray(model.alpha) > 0, axis=0)
    return _bcm_scores(model, Xq, _ova_weights(model), active, noise,
                       max_sv_per_cluster)


def _argmax_classes(model, scores: Array) -> Array:
    return jnp.asarray(model.classes)[jnp.argmax(scores, axis=1)]


def predict_exact_ova(model, Xq: Array) -> Array:
    return _argmax_classes(model, decision_exact_ova(model, Xq))


def predict_early_ova(model, Xq: Array) -> Array:
    return _argmax_classes(model, decision_early_ova(model, Xq))


def predict_bcm_ova(model, Xq: Array) -> Array:
    return _argmax_classes(model, decision_bcm_ova(model, Xq))


def accuracy_multiclass(y_true, y_pred) -> float:
    return float(np.mean(np.asarray(y_true) == np.asarray(y_pred)))
