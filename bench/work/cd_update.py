"""Least work of one ``cd_column_update`` call (``kernels/cd_update.py``):
dg = s * (K(X, Xb) @ w) for X (n, d), Xb (B, d), RBF kernel.

Operations: the (n, B) Gram tile's dot products, 2 n B d, and its contraction
with w, 2 n B (the exp and the norms are not counted).  Bytes: X, s and the
output once each, plus Xb and w, all f32, at the unpadded shapes.
"""


def work(shapes):
    n, d, B = int(shapes["n"]), int(shapes["d"]), int(shapes["B"])
    flops = 2 * n * B * d + 2 * n * B
    nbytes = 4 * (n * d + n + B * d + B + n)
    return flops, nbytes
