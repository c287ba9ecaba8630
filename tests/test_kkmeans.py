"""Tests for two-step kernel kmeans + balanced partitioning."""
import zlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    Kernel,
    Partition,
    assign_points,
    balanced_assign,
    gram,
    kernel_kmeans,
    two_step_kernel_kmeans,
)
from repro.core.bounds import d_pi
from repro.data import gaussian_mixture


def test_kernel_kmeans_recovers_separated_blobs():
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    centers = jnp.array([[0.0, 0.0], [5.0, 5.0], [0.0, 5.0], [5.0, 0.0]])
    lab = jax.random.randint(k1, (400,), 0, 4)
    X = centers[lab] + 0.1 * jax.random.normal(k2, (400, 2))
    kern = Kernel("rbf", gamma=1.0)
    K = gram(kern, X, X)
    assign, W, s = kernel_kmeans(K, 4, jax.random.PRNGKey(1), iters=30)
    # perfect clustering up to label permutation: each true blob maps to one cluster
    assign = np.asarray(assign)
    lab = np.asarray(lab)
    for b in range(4):
        vals = assign[lab == b]
        assert (vals == vals[0]).all()


def test_two_step_assignment_matches_full_on_sample():
    X, y = gaussian_mixture(jax.random.PRNGKey(2), 600, d=6, modes_per_class=3)
    kern = Kernel("rbf", gamma=4.0)
    part = two_step_kernel_kmeans(kern, X, k=6, key=jax.random.PRNGKey(3), m=200,
                                  balanced=False, span_prefix="divide/level1")
    # routing model assigns consistently with the stored partition
    a2, _ = assign_points(kern, part.model, X)
    assert (np.asarray(a2) == part.assign).mean() > 0.999


def test_balanced_assign_exact_capacity():
    rng = np.random.default_rng(0)
    D = rng.random((128, 4))
    out = balanced_assign(D, capacity=32)
    counts = np.bincount(out, minlength=4)
    assert (counts == 32).all()


def test_balanced_assign_prefers_near_centers():
    # two tight groups, two centers: balanced assignment should match argmin
    D = np.array([[0.1, 5.0]] * 8 + [[5.0, 0.1]] * 8)
    out = balanced_assign(D, capacity=8)
    assert (out[:8] == 0).all() and (out[8:] == 1).all()


def _one_at_a_time(D, capacity):
    """The balance rule one point at a time, with stable sorts: points by
    confidence (largest first, ties by index), each to its nearest centre
    with room (ties by index)."""
    D = np.asarray(D, dtype=np.float64)
    n, k = D.shape
    pref = np.argsort(D, axis=1, kind="stable")
    if k > 1:
        part = np.partition(D, 1, axis=1)
        confidence = part[:, 1] - part[:, 0]
    else:
        confidence = np.zeros(n)
    remaining = np.full(k, capacity, dtype=np.int64)
    out = np.full(n, -1, dtype=np.int32)
    for i in np.argsort(-confidence, kind="stable"):
        for c in pref[i]:
            if remaining[c] > 0:
                out[i] = c
                remaining[c] -= 1
                break
    return out


def _kmeans_distances(k, n=600, m=120, seed=0):
    """(n, k) float32 distances of a small two-step kernel k-means model;
    a centre no sampled point reached has a +inf column."""
    X, _ = gaussian_mixture(jax.random.PRNGKey(seed), n, d=6, modes_per_class=3)
    kern = Kernel("rbf", gamma=4.0)
    part = two_step_kernel_kmeans(kern, X, k=k, key=jax.random.PRNGKey(seed + 1),
                                  m=m, balanced=False,
                                  span_prefix="divide/level1")
    return np.asarray(assign_points(kern, part.model, X)[1])


def _balance_case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    rand = lambda n, k: rng.random((n, k), dtype=np.float32)
    if name == "k1":
        return rand(37, 1), 37
    if name == "k2_ragged":
        return rand(101, 2), 51
    if name == "k4_full":
        return rand(128, 4), 32
    if name == "k64_ragged":
        return rand(1000, 64), 16
    if name == "k64_full":
        return rand(64 * 12, 64), 12
    if name == "k64_column_major":
        # the layout a TPU hands narrow (n, k) arrays over in
        return np.asfortranarray(rand(1000, 64)), 16
    if name == "k256_ragged":
        return rand(3001, 256), 12
    if name == "k256_full":
        return rand(256 * 8, 256), 8
    if name == "ties":
        # few distinct values: tied confidences and tied row minima
        return (rng.integers(0, 4, (500, 16)) / 4).astype(np.float32), 32
    if name == "duplicate_minima":
        D = rand(400, 8)
        D[::3, 5] = D[::3].min(axis=1)      # a second centre at the minimum
        D[::7, 0] = D[::7].min(axis=1)
        return D, 50
    if name == "inf_columns":
        # empty centres: every row +inf there; the capacity is tight, so
        # they must still be filled, in index order, after the finite ones
        D = rand(64 * 10, 64)
        D[:, [3, 17, 40, 63]] = np.inf
        return D, 10
    if name == "kmeans_k16":
        D = _kmeans_distances(16)
        return D, -(-D.shape[0] // 16)
    if name == "kmeans_k64":
        D = _kmeans_distances(64)
        return D, -(-D.shape[0] // 64)
    if name == "kmeans_k64_empty":
        # more centres than sampled points: 24 empty centres, +inf columns
        D = _kmeans_distances(64, m=40)
        return D, -(-D.shape[0] // 64)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "k1", "k2_ragged", "k4_full", "k64_ragged", "k64_full",
    "k64_column_major", "k256_ragged",
    "k256_full", "ties", "duplicate_minima", "inf_columns", "kmeans_k16",
    "kmeans_k64", "kmeans_k64_empty"])
def test_balanced_assign_matches_one_at_a_time_rule(name):
    """The blocked greedy gives exactly the one-point-at-a-time rule with
    stable tie-breaking, and counts the points it moved off their nearest
    centre and its block steps: a full block of B = ceil(n / k) points, the
    last partial one, or one that stops early and shuts a centre."""
    from repro.core.kkmeans import _balance, _nearest_two

    D, cap = _balance_case(name)
    n, k = D.shape
    want = _one_at_a_time(D, cap)
    out, redirected, steps = _balance(D, cap, _nearest_two(D))
    np.testing.assert_array_equal(balanced_assign(D, cap), want)
    np.testing.assert_array_equal(out, want)
    assert out.dtype == np.int32
    assert np.bincount(out, minlength=k).max() <= cap
    assert redirected == int(np.count_nonzero(
        want != np.argsort(D, axis=1, kind="stable")[:, 0]))
    assert 1 <= steps <= n // -(-n // k) + k + 1
    if name in ("inf_columns", "kmeans_k64_empty"):
        # the finite centres fill first, then the empty ones in index order
        counts = np.bincount(out, minlength=k)
        empty = np.isinf(D).all(axis=0)
        assert empty.sum() >= 4 and (counts[~empty] == cap).all()
        assert counts[empty][0] > 0 and (np.diff(counts[empty]) <= 0).all()
    if name.startswith("kmeans"):
        assert redirected > 0


def test_balanced_assign_rejects_too_little_capacity():
    with pytest.raises(ValueError, match="capacity"):
        balanced_assign(np.zeros((33, 4), np.float32), 8)


def test_partition_gather_scatter_roundtrip():
    X, _ = gaussian_mixture(jax.random.PRNGKey(4), 300, d=4)
    kern = Kernel("rbf", gamma=2.0)
    part = two_step_kernel_kmeans(kern, X, k=5, key=jax.random.PRNGKey(5), m=100,
                                  span_prefix="divide/level1")
    v = jnp.arange(300, dtype=jnp.float32)
    vc = jnp.where(jnp.asarray(part.mask), part.gather(v), 0.0)
    back = part.scatter(vc, 300)
    assert np.allclose(np.asarray(back), np.asarray(v))


def test_kkmeans_partition_beats_random_on_dpi():
    """The reason kernel kmeans is the right divide step (paper Fig. 1):
    D(pi) from kernel kmeans is far below D(pi) of a random partition."""
    X, _ = gaussian_mixture(jax.random.PRNGKey(6), 800, d=8, modes_per_class=4,
                            spread=0.08)
    kern = Kernel("rbf", gamma=16.0)
    part = two_step_kernel_kmeans(kern, X, k=8, key=jax.random.PRNGKey(7), m=300,
                                  span_prefix="divide/level1")
    d_kk = float(d_pi(kern, X, jnp.asarray(part.assign)))
    rng = np.random.default_rng(0)
    rand_assign = rng.integers(0, 8, size=800)
    d_rand = float(d_pi(kern, X, jnp.asarray(rand_assign)))
    assert d_kk < 0.5 * d_rand


def test_empty_cluster_reseeding():
    # k larger than natural cluster count still yields k populated clusters
    X = jnp.concatenate([jnp.zeros((50, 2)), jnp.ones((50, 2))], 0)
    X = X + 0.01 * jax.random.normal(jax.random.PRNGKey(8), X.shape)
    kern = Kernel("rbf", gamma=1.0)
    part = two_step_kernel_kmeans(kern, X, k=4, key=jax.random.PRNGKey(9), m=100,
                                  span_prefix="divide/level1")
    counts = np.bincount(part.assign, minlength=4)
    assert (counts > 0).all()


def test_reseed_all_empties_in_one_iteration():
    """Regression: when argmin collapses many clusters at once, reseeding one
    empty per iteration leaves phantom centers whenever iters < #empties.
    With a constant kernel matrix every point collapses into cluster 0 each
    iteration, so only reseed-ALL keeps k clusters populated within 2 iters."""
    Kmm = jnp.ones((12, 12))
    assign, W, s = kernel_kmeans(Kmm, 4, jax.random.PRNGKey(0), iters=2)
    counts = np.bincount(np.asarray(assign), minlength=4)
    assert (counts > 0).all(), f"phantom empty clusters: counts={counts}"


def test_reseed_handles_more_clusters_than_points():
    """k > m degenerate case: reseeding must not crash, and with an identity
    kernel (all points mutually orthogonal) every point keeps its own
    singleton cluster — m of the k clusters populated, one point each."""
    assign, W, s = kernel_kmeans(jnp.eye(8), 12, jax.random.PRNGKey(0), iters=3)
    counts = np.bincount(np.asarray(assign), minlength=12)
    assert counts.max() == 1
    assert (counts > 0).sum() == 8


def test_assign_points_masks_empty_centers():
    """Regression: an empty center has W[:, c] = 0 and s[c] = 0, so its
    distance column degenerates to K(x, x) = 1 (RBF) and can win argmin for
    far-away queries.  Routing must never send a query to an empty center."""
    from repro.core import KKMeansModel

    Xm = jnp.asarray([[0.0, 0.0], [0.0, 0.1]])
    W = jnp.asarray([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]])   # center 2 is empty
    kern = Kernel("rbf", gamma=1.0)
    Kmm = gram(kern, Xm, Xm)
    s = jnp.asarray([1.0, 1.0, 0.0])
    model = KKMeansModel(Xm=Xm, W=W, s=s)
    # far query: distance to the real centers ~2, to the phantom center 1
    Xq = jnp.asarray([[10.0, 10.0], [0.0, 0.0]])
    assign, D = assign_points(kern, model, Xq)
    assert np.asarray(D)[0, 2] == np.inf
    assert int(assign[0]) in (0, 1)
    assert int(assign[1]) == 0   # near queries still route normally


def test_two_step_splits_sample_and_init_keys():
    """Regression: the m-point sample and the kmeans init permutation must be
    INDEPENDENT streams split from the caller's key, not two consumers of the
    same key (correlated sample/init defeats the two-step scheme's
    randomization).  Pins the documented contract: sample stream =
    split(key)[0]."""
    from repro.data import gaussian_mixture

    X, _ = gaussian_mixture(jax.random.PRNGKey(30), 300, d=4)
    key = jax.random.PRNGKey(42)
    part = two_step_kernel_kmeans(Kernel("rbf", gamma=2.0), X, k=3, key=key,
                                  m=64, iters=2, balanced=False,
                                  span_prefix="divide/level1")
    key_sample, _ = jax.random.split(key)
    expected = X[jax.random.choice(key_sample, 300, shape=(64,), replace=False)]
    np.testing.assert_array_equal(np.asarray(part.model.Xm),
                                  np.asarray(expected))
