"""Fused two-step-kernel-kmeans assignment Pallas kernel.

For each X tile (bm, d): compute the RBF cross-kernel tile K(Xt, Xm) (MXU),
immediately contract with the center weight matrix W (m, kpad) (second MXU
matmul), add the center self-terms s, and reduce to the per-row argmin — all
inside VMEM.  The (n, m) cross-kernel never touches HBM: this fusion removes
the dominant memory term of the O(nmd) assignment step.

VMEM per grid step (bm=256, m<=1024, d<=512, kpad=128, f32):
    Xt 0.5 MiB + Xm 2 MiB + K tile 1 MiB + W 0.5 MiB  << 16 MiB.
Outputs: scores (bm, kpad) distance-to-center, assign (bm, 1) int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import out_struct


def _assign_body(x_ref, xm_ref, w_ref, s_ref, scores_ref, assign_ref, *,
                 gamma: float):
    x = x_ref[...]                                     # (bm, d)
    xm = xm_ref[...]                                   # (m, d)
    g = jax.lax.dot_general(x, xm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)[:, None]
    mm = jnp.sum(xm.astype(jnp.float32) ** 2, axis=-1)[None, :]
    k = jnp.exp(-gamma * jnp.maximum(xx + mm - 2.0 * g, 0.0))   # (bm, m)
    w = w_ref[...]                                     # (m, kpad)
    scores = -2.0 * jnp.dot(k, w, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    scores = scores + s_ref[...]                       # (bm, kpad); pads = +inf
    scores_ref[...] = scores
    assign_ref[...] = jnp.argmin(scores, axis=-1, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("gamma", "bm", "interpret"))
def kmeans_assign(
    X: jax.Array,
    Xm: jax.Array,
    W: jax.Array,
    s: jax.Array,
    *,
    gamma: float = 1.0,
    bm: int = 256,
    interpret: bool = False,
):
    """Returns (assign (n,), scores (n, kpad)).  RBF kernel only (the paper's
    clustering kernel); K(x,x)=1 is constant per row and dropped (argmin
    invariant).  s must be padded with +inf beyond the real k centers."""
    n, d = X.shape
    m, _ = Xm.shape
    kpad = W.shape[1]
    assert n % bm == 0 and s.shape == (1, kpad)
    grid = (n // bm,)
    body = functools.partial(_assign_body, gamma=gamma)
    scores, assign = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            pl.BlockSpec((m, kpad), lambda i: (0, 0)),
            pl.BlockSpec((1, kpad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, kpad), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            out_struct((n, kpad), jnp.float32, X, Xm, W, s),
            out_struct((n, 1), jnp.int32, X, Xm, W, s),
        ],
        interpret=interpret,
    )(X, Xm, W, s)
    return assign[:, 0], scores
