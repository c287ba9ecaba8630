"""The conquer step's B×B sub-QP sweep as one Pallas kernel.

Each outer iteration of the block CD solver (``core.solver``) moves its B
working-set coordinates by ``sweeps`` cyclic coordinate-descent passes over
the (B, B) block ``Qbb``: ``sweeps * B`` dependent scalar steps.  As an XLA
``fori_loop`` each step is a while-loop trip of several tiny ops; here the
whole sweep runs inside one kernel invocation with ``Qbb`` held in VMEM.

Layout: ``a``, ``g``, ``cb`` and ``diag`` are (1, Bp) lane rows, Bp = B
rounded up to 128 with zero padding; ``QbbT`` is (Bp, Bp), so step j's
column of ``Qbb`` is row j of ``QbbT``, read with a dynamic sublane slice.
Coordinate j's scalars are one-hot masked lane sums, which are exact (every
other term is 0).  Padded lanes are never visited and their ``Qbb`` entries
are 0, so they stay 0.  Step for step the arithmetic is that of the XLA
loop (``solver._solve_small_qp``): a true divide, a clip, and the rank-1
gradient update ``g += delta * Qbb[:, j]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import out_struct

LANES = 128


def _sweep_body(qt_ref, g_ref, a_ref, c_ref, d_ref, o_ref, *, B: int,
                sweeps: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, a_ref.shape, 1)
    cb, diag = c_ref[...], d_ref[...]

    def pick(m, v):
        return jnp.sum(jnp.where(m, v, 0.0), axis=1, keepdims=True)

    def step(j, carry):
        a, g = carry
        m = lane == j
        aj = pick(m, a)
        new_aj = jnp.clip(aj - pick(m, g) / pick(m, diag), 0.0, pick(m, cb))
        delta = new_aj - aj
        return (jnp.where(m, new_aj, a),
                g + delta * qt_ref[pl.ds(j, 1), :])

    def sweep(_, carry):
        return jax.lax.fori_loop(0, B, step, carry)

    a, _ = jax.lax.fori_loop(0, sweeps, sweep, (a_ref[...], g_ref[...]))
    o_ref[...] = a


@functools.partial(jax.jit, static_argnames=("sweeps", "interpret"))
def small_qp_sweep(QbbT: jax.Array, gb: jax.Array, ab: jax.Array,
                   cb: jax.Array, diag: jax.Array, *, sweeps: int,
                   interpret: bool = False) -> jax.Array:
    """New a_b (B,) after ``sweeps`` cyclic CD passes on the box QP
    ``min 1/2 a'Qbb a + g'a, 0 <= a <= cb`` from ``ab``, where ``gb`` is the
    gradient at ``ab`` and ``diag`` the guarded diagonal of ``Qbb``.
    ``QbbT`` is ``Qbb.T``; ``cb`` and ``diag`` are (B,) vectors."""
    B = ab.shape[0]
    Bp = -(-B // LANES) * LANES

    def row(v):
        return jnp.pad(v, (0, Bp - B))[None, :]

    args = (jnp.pad(QbbT, ((0, Bp - B), (0, Bp - B))), row(gb), row(ab),
            row(cb), row(diag))
    out = pl.pallas_call(
        functools.partial(_sweep_body, B=B, sweeps=sweeps),
        out_shape=out_struct((1, Bp), ab.dtype, *args),
        interpret=interpret,
        name="small_qp_sweep",
    )(*args)
    return out[0, :B]
