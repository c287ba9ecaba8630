"""Tiled kernel-matrix Pallas kernel.

K(X, Y) for X:(n,d), Y:(m,d) computed in (bm, bn) output tiles.  Each grid
step loads an (bm, d) X-tile and (bn, d) Y-tile into VMEM, runs the Gram
matmul on the MXU (f32 accumulation via preferred_element_type) and fuses the
kernel transform (exp / polynomial) on the VPU before writing the tile back —
the TPU adaptation of LIBSVM's kernel-row computation: recompute beats cache
at 197 TFLOP/s.

VMEM budget per grid step (bm=bn=256, d<=3072, f32):
    X tile 256*3072*4 = 3.0 MiB, Y tile 3.0 MiB, out 0.25 MiB  << 16 MiB.
MXU alignment: bm, bn multiples of 128; d padded to a multiple of 8 by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import out_struct, precision


def _kermat_body(x_ref, y_ref, o_ref, *, kind: str, gamma: float, degree: int,
                 coef0: float, compute_dtype=None):
    x = x_ref[...]
    y = y_ref[...]
    if compute_dtype is not None:
        # precision policy (flash_attention idiom): low-precision operand
        # tiles feed the MXU, accumulation stays f32 via
        # preferred_element_type; the rbf norms below square the *quantized*
        # tiles in f32 so the sqdist expansion cancels consistently
        x = x.astype(compute_dtype)
        y = y.astype(compute_dtype)
    g = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=precision(compute_dtype),
    )                                                        # (bm, bn) MXU
    if kind == "linear":
        o = g
    elif kind == "poly":
        o = (gamma * g + coef0) ** degree
    else:  # rbf
        xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)[:, None]
        yy = jnp.sum(y.astype(jnp.float32) ** 2, axis=-1)[None, :]
        sq = jnp.maximum(xx + yy - 2.0 * g, 0.0)
        o = jnp.exp(-gamma * sq)
    o_ref[...] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "bm", "bn",
                     "interpret", "compute_dtype"),
)
def kermat(
    X: jax.Array,
    Y: jax.Array,
    *,
    kind: str = "rbf",
    gamma: float = 1.0,
    degree: int = 3,
    coef0: float = 0.0,
    bm: int = 256,
    bn: int = 256,
    interpret: bool = False,
    compute_dtype=None,
) -> jax.Array:
    """K(X, Y) -> (n, m). n % bm == 0, m % bn == 0 (ops.py pads)."""
    n, d = X.shape
    m, _ = Y.shape
    assert n % bm == 0 and m % bn == 0, (n, m, bm, bn)
    grid = (n // bm, m // bn)
    body = functools.partial(_kermat_body, kind=kind, gamma=gamma,
                             degree=degree, coef0=coef0,
                             compute_dtype=compute_dtype)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=out_struct((n, m), jnp.float32, X, Y),
        interpret=interpret,
    )(X, Y)
