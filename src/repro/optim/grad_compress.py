"""Error-feedback gradient compression for cross-replica reduction.

For the multi-pod mesh, the "pod" axis rides the (slow) DCN: compressing the
cross-pod gradient exchange is the classic distributed-optimization trick.
``compressed_psum`` implements an int8 + per-block-scale quantized all-reduce
under shard_map: quantize locally -> all_gather int8 payloads (+f32 scales)
-> dequantize-sum locally.  Bytes on the wire drop ~4x vs f32 psum (~2x vs
bf16).  ``compress_ef`` maintains the error-feedback residual that makes
quantized SGD/Adam provably convergent (the residual re-enters the next
step's gradient).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

BLOCK = 256


def _pad_to_block(x: Array) -> Tuple[Array, int]:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def compress(x: Array) -> Tuple[Array, Array]:
    """Blockwise symmetric int8 quantization. Returns (q int8, scales f32)."""
    flat, _ = _pad_to_block(x)
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def decompress(q: Array, scale: Array, shape) -> Array:
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape)


def compress_ef(g: Array, residual: Array) -> Tuple[Array, Array, Array]:
    """Error-feedback compression: quantize (g + residual), carry the error.
    Returns (q, scale, new_residual)."""
    corrected = g + residual
    q, scale = compress(corrected)
    approx = decompress(q, scale, g.shape)
    return q, scale, corrected - approx


def compressed_psum(x_stacked: Array, mesh: Mesh, axis: str) -> Array:
    """Quantized all-reduce over ``axis``: int8 all_gather + local dequant-sum.

    ``x_stacked`` has a leading dim of size mesh.shape[axis] — one gradient
    per axis member (e.g. each pod's locally-reduced gradient).  Returns the
    same shape with every slice holding the (quantized) sum.
    """
    shape = x_stacked.shape[1:]
    n = 1
    for d in shape:
        n *= d

    def local(xl):                                     # xl: (1, ...)
        q, s = compress(xl[0])
        qg = lax.all_gather(q, axis)                   # (P, nblk, BLOCK) int8
        sg = lax.all_gather(s, axis)                   # (P, nblk)
        deq = qg.astype(jnp.float32) * sg[..., None]
        total = jnp.sum(deq, axis=0).reshape(-1)
        return total[:n].reshape(shape)[None]

    fn = jax.shard_map(local, mesh=mesh,
                   in_specs=P(axis, *(None,) * len(shape)),
                   out_specs=P(axis, *(None,) * len(shape)))
    return fn(x_stacked)
