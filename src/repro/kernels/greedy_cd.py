"""Greedy single-coordinate CD on a dense box QP as one Pallas kernel.

``core.solver.solve_box_qp`` moves one coordinate a step: the projected
gradient over the active set, its arg-max ``i``, the clipped Newton step
of ``alpha[i]``, and the rank-1 gradient update ``g += delta * Q[:, i]``.
As an XLA ``while_loop`` every step is a trip of a handful of tiny ops
plus a strided column read of ``Q``; here the whole solve, up to the
stopping test, runs inside one kernel invocation per problem.

Layout: ``alpha``, ``g``, the box ``c``, the guarded diagonal ``d`` and
the active mask are (R, 128) tiles, R = ceil(n / 128) rounded up to 8,
zero padded (``d`` with ones, the mask with zeros, so padded slots are
never selected and stay 0).  ``QT`` is ``Q.T`` as (n, R, 128): column i of
``Q`` is row i of ``Q.T``, read whole as one (R, 128) tile.  Where ``QT``
fits ``VMEM_QT_BYTES`` one DMA at the start of a problem copies it whole
into a VMEM buffer, from which each step reads its row with a dynamic
index; otherwise it stays in HBM and each step DMAs its one row into a
VMEM buffer, overlapped with the step's scalar work.  Problems of a batch
are grid steps, solved one after another.

Step for step the arithmetic is that of the XLA loop: the arg-max is the
first index of the largest ``|pg|`` (the max, then the least index that
attains it), the scalars of coordinate i are one-hot masked lane sums of
its row of the tile, which are exact, and the updates are the same
elementwise ops in the same order.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import out_struct

LANES = 128
SUBLANES = 8
# Q.T is held in VMEM (one buffer) up to this size, half of a v5e core's
# 128 MiB: a 3,125-point cluster's 51 MB fits, a 6,250-point one's does not
VMEM_QT_BYTES = 64 * 2 ** 20


def tile_rows(n: int) -> int:
    """Rows R of the (R, 128) tile that holds an n-vector."""
    return -(-n // (LANES * SUBLANES)) * SUBLANES


def qt_in_vmem(n: int) -> bool:
    """Whether an (n, n) f32 ``Q.T`` is held in VMEM (else it stays in HBM
    and each step DMAs one row)."""
    return n * tile_rows(n) * LANES * 4 <= VMEM_QT_BYTES


def _body(tol_ref, a_ref, g_ref, c_ref, d_ref, m_ref, qt_ref,
          ao_ref, go_ref, it_ref, pm_ref, buf, sem, *, max_iters: int,
          in_vmem: bool):
    b = pl.program_id(0)
    shape = a_ref.shape
    flat = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    big = jnp.int32(shape[0] * LANES)
    c, active = c_ref[...], m_ref[...] != 0
    if in_vmem:
        whole = pltpu.make_async_copy(qt_ref.at[b], buf, sem)
        whole.start()
    ao_ref[...] = a_ref[...]
    go_ref[...] = g_ref[...]
    if in_vmem:
        whole.wait()

    def select():
        a, g = ao_ref[...], go_ref[...]
        pg = jnp.where(a <= 0.0, jnp.minimum(g, 0.0), g)
        pg = jnp.where(a >= c, jnp.maximum(g, 0.0), pg)
        s = jnp.abs(jnp.where(active, pg, 0.0))
        m = jnp.max(s)
        return m, jnp.min(jnp.where(s == m, flat, big))

    def pick(hit, ref, r):
        return jnp.sum(jnp.where(hit, ref[pl.ds(r, 1), :], 0.0), axis=1,
                       keepdims=True)

    def step(carry):
        it, _ = carry
        m, i = select()
        if not in_vmem:
            col = pltpu.make_async_copy(qt_ref.at[b, i], buf, sem)
            col.start()
        r, hit = i // LANES, lane == i % LANES
        arow = ao_ref[pl.ds(r, 1), :]
        ai = jnp.sum(jnp.where(hit, arow, 0.0), axis=1, keepdims=True)
        new = jnp.clip(ai - pick(hit, go_ref, r) / pick(hit, d_ref, r), 0.0,
                       pick(hit, c_ref, r))
        ao_ref[pl.ds(r, 1), :] = jnp.where(hit, new, arow)
        if in_vmem:
            q = buf[i]
        else:
            col.wait()
            q = buf[...]
        go_ref[...] = go_ref[...] + (new - ai) * q
        return it + 1, m

    def cond(carry):
        it, m = carry
        return (m > tol_ref[b]) & (it < max_iters)

    it, m = jax.lax.while_loop(cond, step, (jnp.int32(0), select()[0]))
    it_ref[b] = it
    pm_ref[b] = m


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def greedy_cd(QT: jax.Array, alpha: jax.Array, g: jax.Array, c: jax.Array,
              d: jax.Array, mask: jax.Array, tol: jax.Array, *,
              max_iters: int, interpret: bool = False):
    """Batched greedy CD: for each problem b, ``min 1/2 a'Qa + p'a`` over
    ``0 <= a <= c`` from ``alpha`` with gradient ``g``, moving only the
    ``mask`` coordinates, until max |pg| (before a step) is at most ``tol``
    or ``max_iters`` steps have run.  ``QT`` is (b, n, n), ``Q.T`` of each
    problem; ``alpha``, ``g``, ``c``, ``d`` (the guarded diagonal of Q) and
    ``mask`` are (b, n); ``tol`` is (b,).  Returns ``(alpha, g, iters,
    pg_max)`` as the XLA loop of ``solve_box_qp`` does."""
    nb, n = alpha.shape
    R = tile_rows(n)
    npad = R * LANES - n
    in_vmem = qt_in_vmem(n)

    def tile(v, fill=0.0):
        return jnp.pad(v, ((0, 0), (0, npad)),
                       constant_values=fill).reshape(nb, R, LANES)

    args = (tol.astype(jnp.float32), tile(alpha), tile(g), tile(c),
            tile(d, 1.0), tile(mask.astype(jnp.int32)),
            jnp.pad(QT, ((0, 0), (0, 0), (0, npad))).reshape(nb, n, R, LANES))
    vec = pl.BlockSpec((None, R, LANES), lambda bi: (bi, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # Q.T whole, or the one row a step reads
    buf = (n, R, LANES) if in_vmem else (R, LANES)
    vec_bytes = R * LANES * 4
    out_a, out_g, iters, pg_max = pl.pallas_call(
        functools.partial(_body, max_iters=max_iters, in_vmem=in_vmem),
        grid=(nb,),
        in_specs=[smem, vec, vec, vec, vec, vec,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[vec, vec, smem, smem],
        out_shape=[out_struct((nb, R, LANES), jnp.float32, *args),
                   out_struct((nb, R, LANES), jnp.float32, *args),
                   out_struct((nb,), jnp.int32, *args),
                   out_struct((nb,), jnp.float32, *args)],
        scratch_shapes=[pltpu.VMEM(buf, jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * math.prod(buf) + 32 * vec_bytes
            + 8 * 2 ** 20),
        interpret=interpret,
        name="greedy_cd",
    )(*args)
    return (out_a.reshape(nb, -1)[:, :n], out_g.reshape(nb, -1)[:, :n],
            iters, pg_max)
