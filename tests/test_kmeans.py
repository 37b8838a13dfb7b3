"""k-means in row blocks against the unblocked Lloyd iteration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kmeans


def _unblocked(x, k, iters, seed):
    """The Lloyd iteration over all rows at once: one [n, k] distance
    matrix and one [n, k] one-hot per step."""
    key = jax.random.PRNGKey(seed)
    perm = jax.random.permutation(key, x.shape[0])[:min(x.shape[0], 4096)]
    centers = kmeans._init_centers(x[perm], k, key)
    for _ in range(iters):
        d = kmeans._pairwise_sq_l2(x, centers)
        one_hot = jax.nn.one_hot(jnp.argmin(d, axis=1), k, dtype=x.dtype)
        counts = one_hot.sum(0)
        new = (one_hot.T @ x) / jnp.maximum(counts, 1.0)[:, None]
        far = jnp.argmax(jnp.min(d, axis=1))
        centers = jnp.where((counts > 0)[:, None], new, x[far][None, :])
    return centers, jnp.argmin(kmeans._pairwise_sq_l2(x, centers), axis=1)


def _separated(n, k, dim, seed):
    """Rows in ``k`` tight, far-apart groups: no row lies near a tie
    between two centroids, so any summation order assigns it alike."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((k, dim)).astype(np.float32) * 20.0
    rows = centers[rng.integers(0, k, n)]
    return rows + rng.standard_normal((n, dim)).astype(np.float32)


@pytest.mark.parametrize("n,k,block_bytes", [(1000, 8, 4 * 8 * 96),
                                             (777, 16, 4 * 16 * 50)],
                         ids=["1000rows-block96", "777rows-block50"])
def test_blocked_fit_equals_unblocked(monkeypatch, n, k, block_bytes):
    monkeypatch.setattr(kmeans, "BLOCK_BYTES", block_bytes)
    rows = block_bytes // (4 * k)
    assert n % rows and kmeans.n_blocks(n, k) == -(-n // rows) > 1
    x = _separated(n, k, 24, seed=n)
    c_blk, a_blk = kmeans.kmeans_fit(jnp.asarray(x), k, iters=5, seed=3)
    c_ref, a_ref = _unblocked(jnp.asarray(x), k, iters=5, seed=3)
    np.testing.assert_array_equal(np.asarray(a_blk), np.asarray(a_ref))
    # the centroid sums add the same float32 terms in another order (block
    # by block, then across blocks): each sum may differ by a few ulps of
    # the largest partial sum, |x| * rows of a cluster * 2^-24 per add
    np.testing.assert_allclose(np.asarray(c_blk), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-4)


def test_block_is_sized_from_rows_and_lists():
    assert kmeans.block_rows(10, 1024) == 10
    assert kmeans.block_rows(1_000_000, 1024) == kmeans.BLOCK_BYTES // 4096
    assert kmeans.n_blocks(1_000_000, 1024) == 16
