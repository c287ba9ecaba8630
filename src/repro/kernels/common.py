"""Helpers shared by the Pallas kernels."""
from __future__ import annotations

import jax


def precision(compute_dtype):
    """Gram-matmul precision: HIGHEST for f32 operand tiles (the TPU's
    default may be one bf16 pass, under which the RBF expansion
    xx + yy - 2g cancels catastrophically); a low-precision policy keeps
    the MXU's native single pass."""
    return jax.lax.Precision.HIGHEST if compute_dtype is None else None


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """``pallas_call`` output spec that varies over the same manual mesh
    axes as its operands, so the kernels run inside a ``shard_map`` with
    its varying-axes check on (outside one the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
