"""Compile-only checks for a described TPU v5e: nothing runs on a chip.

The TPU compiler is installed even where no chip is attached; these tests
lower the main path's Pallas kernels at the covtype (d=54) and webspam
(d=254) widths, the conquer's sub-QP sweep kernel, the cluster solves'
greedy CD kernel in both of its placements, plus one level-1 cluster solve
at the paper deployment's cluster size, on one chip and sharded over the
four of a ``v5e:2x2``.
They catch what interpret mode cannot: a kernel Mosaic refuses (tiling,
VMEM, an unsupported precision), a kernel inside ``shard_map`` without
varying-axes types, an XLA replacement of a kernel, or a program that
outgrows 16 GB of HBM.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import DCSVMConfig, Kernel
from repro.core import dcsvm
from repro.kernels import ops

HBM_BYTES = 16 * 10 ** 9          # one v5e chip
KERN = Kernel("rbf", gamma=1.0)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """``ops`` picks interpret mode from ``jax.default_backend()``, which is
    the CPU here; compile the real kernels instead."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *shapes):
    c = jax.jit(fn).lower(*shapes).compile()
    ma = c.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    return c.as_text(), total


KERNELS = {
    "kernel_matrix": (lambda X, Y: ops.kernel_matrix(X, Y, KERN),
                      lambda d: [(4096, d), (2000, d)]),
    "kernel_matvec": (lambda X, Z, v: ops.kernel_matvec(X, Z, v, KERN),
                      lambda d: [(4096, d), (2000, d), (2000,)]),
    "cd_column_update": (
        lambda X, y, Xb, w: ops.cd_column_update(X, y, Xb, w, KERN),
        lambda d: [(100_000, d), (100_000,), (64, d), (64,)]),
    "kmeans_assign": (lambda X, Xm, W, s: ops.kmeans_assign(X, Xm, W, s, 1.0),
                      lambda d: [(4096, d), (1000, d), (1000, 4), (4,)]),
}


@pytest.mark.parametrize("d", [54, 254])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, compiled_kernels, name, d):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes(d)]
    hlo, total = _compile(fn, *args)
    assert "tpu_custom_call" in hlo
    assert total < HBM_BYTES


@pytest.mark.parametrize("B", [64, 256])
def test_small_qp_sweep_compiles_for_v5e(one_chip, compiled_kernels, B):
    """The conquer's B×B sub-QP sweep kernel at the solver's block (64) and
    at two lane tiles: its dynamic row reads and lane reductions must pass
    Mosaic, which interpret mode does not check."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in [(B, B), (B,), (B,), (B,), (B,)]]
    hlo, total = _compile(
        lambda QT, g, a, c, d: ops.small_qp_sweep(QT, g, a, c, d, sweeps=4),
        *args)
    assert "tpu_custom_call" in hlo
    assert total < HBM_BYTES


@pytest.mark.parametrize("b,n", [(1, 12_500), (1, 3_125), (16, 1_563)])
def test_greedy_cd_compiles_for_v5e(one_chip, compiled_kernels, b, n):
    """The cluster solves' greedy CD kernel at the covtype cell's level-1
    cluster (Q.T in HBM, one row DMA a step), its level-2 cluster (the
    largest Q.T held in VMEM, 51 MB) and a batch of webspam's level-2
    clusters (Q.T in VMEM): its scalar reductions, dynamic row reads, DMAs
    and VMEM limit must pass Mosaic, which interpret mode does not check."""
    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo, total = _compile(
        lambda QT, a, g, c, d, m, tol: ops.greedy_cd(QT, a, g, c, d, m, tol,
                                                     max_iters=30_000),
        spec(b, n, n), spec(b, n), spec(b, n), spec(b, n), spec(b, n),
        spec(b, n, dtype=jnp.bool_), spec(b))
    assert "tpu_custom_call" in hlo
    assert total < HBM_BYTES


def test_level1_cluster_solve_fits_v5e(one_chip, compiled_kernels):
    """One level-1 solve of the n_train=100,000 deployment: 4 clusters of
    25,000 points, each a dense Gram (Pallas) plus the CD solve, swept
    sequentially by ``lax.map``."""
    cfg = DCSVMConfig(kernel=KERN, C=8.0, k=4, levels=4, m=1000, tol=1e-3)
    k, nc, d = 4, 25_000, 54

    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo, total = _compile(
        lambda Xc, sc, pc, cc, ac, mask: dcsvm._solve_clusters(
            cfg, Xc, sc, pc, cc, ac, mask, use_pallas=True),
        spec(k, nc, d), spec(k, 1, nc), spec(k, 1, nc), spec(k, 1, nc),
        spec(k, 1, nc), spec(k, nc, dtype=jnp.bool_))
    assert "tpu_custom_call" in hlo
    assert total < HBM_BYTES


def test_sharded_level1_solve_compiles_for_v5e_2x2(topo, compiled_kernels):
    """The same level-1 solve sharded over the four chips by
    ``distributed.divide_step``: the Pallas Gram runs inside ``shard_map``
    with its varying-axes check on, one 25,000-point cluster per chip."""
    from repro.core.distributed import divide_step

    mesh = Mesh(np.array(topo.devices), ("i",),
                axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, PartitionSpec("i"))
    cfg = DCSVMConfig(kernel=KERN, C=8.0, k=4, levels=4, m=1000, tol=1e-3,
                      use_pallas=True)
    k, nc, d = 4, 25_000, 54

    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rows)

    hlo, total = _compile(
        lambda Xc, sc, pc, cc, ac, mask: divide_step(
            mesh, "i", cfg, Xc, sc, pc, cc, ac, mask),
        spec(k, nc, d), spec(k, nc), spec(k, nc), spec(k, nc), spec(k, nc),
        spec(k, nc, dtype=jnp.bool_))
    assert "tpu_custom_call" in hlo
    assert total < HBM_BYTES
