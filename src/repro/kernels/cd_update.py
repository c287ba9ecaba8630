"""Fused kernel-column block gradient update Pallas kernel.

The conquer-step block CD updates g += Q[:, idx] @ delta with Q columns
recomputed on the fly.  This kernel fuses, per X tile:

    K_tile = rbf(Xt, Xb)            (bm, B)  MXU + VPU exp
    g_out  = y_t * (K_tile @ w)     (bm, 1)  skinny MXU matmul

where w = y_b * delta and y is the generalized dual's sign vector s
(labels for C-SVC, mixed +1/-1 mirror signs for the epsilon-SVR stacked
dual — signs are data, not structure, so one kernel serves every task).
The (n, B) column block never hits HBM — only the
(n,) gradient delta does.  This is the recompute-in-VMEM replacement for
LIBSVM's kernel cache; the optional device-resident column cache that
serves fully-resident blocks without any recompute lives in
``repro.core.colcache`` (see DESIGN.md §2 for the tradeoff).

VMEM per grid step (bm=512, B<=256, d<=512): well under 4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import out_struct, precision


def _cd_body(x_ref, y_ref, xb_ref, w_ref, o_ref, *, kind: str, gamma: float,
             degree: int, coef0: float, compute_dtype=None):
    x = x_ref[...]                                      # (bm, d)
    xb = xb_ref[...]                                    # (B, d)
    if compute_dtype is not None:
        # precision policy: quantize the Gram operands only — y, w and the
        # skinny contraction stay f32 (flash_attention idiom)
        x = x.astype(compute_dtype)
        xb = xb.astype(compute_dtype)
    g = jax.lax.dot_general(x, xb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=precision(compute_dtype))
    if kind == "linear":
        k = g
    elif kind == "poly":
        k = (gamma * g + coef0) ** degree
    else:
        xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)[:, None]
        bb = jnp.sum(xb.astype(jnp.float32) ** 2, axis=-1)[None, :]
        k = jnp.exp(-gamma * jnp.maximum(xx + bb - 2.0 * g, 0.0))
    w = w_ref[...]                                      # (B, 1)
    o = y_ref[...] * jnp.dot(k, w, preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    o_ref[...] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "bm", "interpret",
                     "compute_dtype"),
)
def cd_column_update(
    X: jax.Array,
    y: jax.Array,
    Xb: jax.Array,
    w: jax.Array,
    *,
    kind: str = "rbf",
    gamma: float = 1.0,
    degree: int = 3,
    coef0: float = 0.0,
    bm: int = 512,
    interpret: bool = False,
    compute_dtype=None,
) -> jax.Array:
    """Returns dg (n,) = y * (K(X, Xb) @ w).  y: (n,), w: (B,)."""
    n, d = X.shape
    B, _ = Xb.shape
    assert n % bm == 0
    body = functools.partial(_cd_body, kind=kind, gamma=gamma, degree=degree,
                             coef0=coef0, compute_dtype=compute_dtype)
    out = pl.pallas_call(
        body,
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((B, d), lambda i: (0, 0)),
            pl.BlockSpec((B, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=out_struct((n, 1), jnp.float32, X, y, Xb, w),
        interpret=interpret,
    )(X, y[:, None], Xb, w[:, None])
    return out[:, 0]
