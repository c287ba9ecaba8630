"""Fused tiled kernel-matrix x vector Pallas kernel.

out = K(X, Z) @ v without ever materializing K: each grid step computes one
(bm, bn) kernel tile in VMEM (MXU Gram matmul + VPU transform) and
immediately contracts it against the matching v tile, accumulating into the
(bm, 1) output block in f32 across the inner grid axis.  HBM traffic is
O(n d + m d + n) instead of the O(n m) a materialize-then-matvec pays —
this is the streaming-conquer replacement for the chunked ``lax.map`` in
``core.kernels.gram_matvec`` (DESIGN.md §3).

Grid order is (i, j) with j innermost: for a fixed output tile i all the
column tiles j run consecutively, so the output block stays resident in
VMEM across the accumulation (initialized at j == 0 via ``pl.when``).

VMEM per grid step (bm=bn=256, d<=3072, f32): X tile 3.0 MiB + Z tile
3.0 MiB + v/out slivers << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import out_struct, precision


def _kmv_body(x_ref, z_ref, v_ref, o_ref, *, kind: str, gamma: float,
              degree: int, coef0: float, compute_dtype=None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                       # (bm, d)
    z = z_ref[...]                                       # (bn, d)
    if compute_dtype is not None:
        # precision policy: quantize the Gram operands only — v and the
        # output block stay f32 (flash_attention idiom)
        x = x.astype(compute_dtype)
        z = z.astype(compute_dtype)
    g = jax.lax.dot_general(x, z, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=precision(compute_dtype))
    if kind == "linear":
        k = g
    elif kind == "poly":
        k = (gamma * g + coef0) ** degree
    else:  # rbf
        xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)[:, None]
        zz = jnp.sum(z.astype(jnp.float32) ** 2, axis=-1)[None, :]
        k = jnp.exp(-gamma * jnp.maximum(xx + zz - 2.0 * g, 0.0))
    o_ref[...] += jnp.dot(k, v_ref[...], preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "bm", "bn",
                     "interpret", "compute_dtype"),
)
def kernel_matvec(
    X: jax.Array,
    Z: jax.Array,
    v: jax.Array,
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
    """out (n,) = K(X, Z) @ v.  n % bm == 0, m % bn == 0 (ops.py pads)."""
    n, d = X.shape
    m, _ = Z.shape
    assert n % bm == 0 and m % bn == 0, (n, m, bm, bn)
    body = functools.partial(_kmv_body, kind=kind, gamma=gamma, degree=degree,
                             coef0=coef0, compute_dtype=compute_dtype)
    out = pl.pallas_call(
        body,
        grid=(n // bm, m // bn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=out_struct((n, 1), jnp.float32, X, Z, v),
        interpret=interpret,
    )(X, Z, v[:, None])
    return out[:, 0]
