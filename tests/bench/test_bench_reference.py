"""The plain references against float64 on the host at small n."""
import numpy as np

import tinyroot  # noqa: F401

from bench import reference as R


def _data(n=600, d=54, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.random((n, d)).astype(np.float32)
    y = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    return X, y


def test_matvec_highest_agrees_with_float64():
    X, _ = _data()
    v = np.random.default_rng(1).standard_normal(300)
    ref = R.rbf64(X, X[:300], 1.0) @ v
    got = R.rbf_matvec(1.0, X, X[:300], v, rows=128)
    assert np.abs(got - ref).max() <= 1e-4


def test_matvec_bf16_3x_is_the_lower_precision():
    X, _ = _data(2000)
    v = 3.0 * np.random.default_rng(2).standard_normal(1000)
    ref = R.rbf64(X, X[:1000], 1.0) @ v
    hi = np.abs(R.rbf_matvec(1.0, X, X[:1000], v) - ref).max()
    lo = np.abs(R.rbf_matvec(1.0, X, X[:1000], v, precision=R.BF16_3X)
                - ref).max()
    assert lo > 5 * hi


def test_box_kkt_agrees_with_float64():
    X, y = _data(400)
    rng = np.random.default_rng(3)
    C = 8.0
    a = np.clip(rng.random(400) * 10 - 1, 0.0, C).astype(np.float32)
    Q = (y[:, None] * y[None, :]) * R.rbf64(X, X, 1.0)
    g = Q @ a.astype(np.float64) - 1.0
    pg = np.where(a <= 0, np.minimum(g, 0), np.where(a >= C,
                                                    np.maximum(g, 0), g))
    r = R.box_kkt(1.0, C, X, y, a)
    assert abs(r["kkt"] - np.abs(pg).max()) <= 1e-4 * max(1.0, np.abs(pg).max())
    obj = 0.5 * a @ Q @ a - a.sum()
    assert abs(r["objective"] - obj) <= 1e-5 * abs(obj)
    assert r["box"] == 0.0
    bad = a.copy()
    bad[0] = C + 0.5
    assert R.box_kkt(1.0, C, X, y, bad)["box"] == 0.5


def test_cluster_kkt_is_the_worst_cluster():
    X, y = _data(300)
    a = np.full(300, 0.5, np.float32)
    assign = np.arange(300) % 3
    per = [R.box_kkt(1.0, 8.0, X[assign == c], y[assign == c],
                     a[assign == c])["kkt"] for c in range(3)]
    assert R.cluster_kkt(1.0, 8.0, X, y, a, assign)["kkt"] == max(per)


def test_decision_matches_float64():
    X, _ = _data(500)
    w = np.random.default_rng(4).standard_normal(200)
    ref = R.rbf64(X, X[:200], 0.5) @ w
    assert np.abs(R.decision(0.5, X[:200], w, X) - ref).max() <= 1e-4


def test_balanced_assign_by_hand():
    # confidence order: point 2 (gap 0.9), 0 (0.5), 1 (0.3), 3 (0.1);
    # capacity 2: points 2 and 0 fill centre 0, so 1 and 3 go to centre 1
    D = np.array([[0.1, 0.6], [0.2, 0.5], [0.0, 0.9], [0.4, 0.5]])
    assert R.balanced_assign(D, 2).tolist() == [0, 1, 0, 1]
    assert R.balanced_assign(D, 4).tolist() == [0, 0, 0, 0]


def test_partition_assign_is_nearest_sampled_centre():
    # each centre one sampled point, far apart: every point goes to the
    # centre it was drawn around; an empty centre is never chosen
    rng = np.random.default_rng(5)
    Xm = np.eye(3, 8, dtype=np.float32) * 3.0
    lab = np.repeat(np.arange(3), 10)
    X = (Xm[lab] + 0.05 * rng.standard_normal((30, 8))).astype(np.float32)
    got = R.partition_assign(0.5, X, Xm, np.eye(3))
    assert got.tolist() == lab.tolist()
    assert (R.partition_assign(0.5, X, Xm, np.eye(3), bf16=True) == lab).all()
    # a fourth, empty centre is the farthest from every point: capacity
    # ceil(30/4) = 8 leaves it only the six points no other centre has room for
    got = R.partition_assign(0.5, X, Xm, np.eye(3, 4))
    assert np.bincount(got, minlength=4).tolist() == [8, 8, 8, 6]
