# Pallas TPU kernels for DC-SVM's compute hot-spots:
#   kermat.py        tiled kernel-matrix (Gram) computation  — O(n m d), the
#                    dominant FLOP sink of both clustering and training
#   kermatvec.py     streaming K(X, Z) @ v — the conquer-step gradient init,
#                    objective, and exact-serving matvec without materializing K
#   kmeans_assign.py fused two-step-kmeans assignment (K tile -> scores -> argmin)
#   cd_update.py     fused on-the-fly kernel-column block gradient update for
#                    the conquer-step block CD (recompute-in-VMEM; the optional
#                    device-resident column cache lives in core.colcache)
#   small_qp.py      the conquer step's B×B sub-QP coordinate-descent sweep,
#                    all sweeps * B steps in one kernel with Qbb in VMEM
#   greedy_cd.py     the cluster solves' greedy single-coordinate CD, a whole
#                    solve in one kernel (Q.T in VMEM or one row DMA a step)
# ops.py exposes jit'd wrappers (interpret mode on CPU, compiled on TPU);
# ref.py holds the pure-jnp oracles the tests compare against.
from repro.kernels import ops, ref
