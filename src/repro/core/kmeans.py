"""Pure-JAX Lloyd k-means for IVF index training.

Matches Faiss's `train` stage (paper Fig. 10 "Train"): k-means over a
training sample, k-means++-style seeding (greedy farthest-point on a
sample for determinism), fixed iteration count, empty-cluster re-seeding.

Row blocks: each Lloyd step, and the final assignment, walks the rows in
blocks of :func:`block_rows` — as many rows as keep one [rows, k] float32
distance block under ``BLOCK_BYTES`` — taking each block's argmin and
adding its cluster sums and counts. Peak memory is then the corpus plus
O(block × k) whether or not the compiler fuses the [n, k] distance
matrix and one-hot away: XLA's TPU backend does, its CPU backend
materialises them (846 MB of temporaries at 200K rows and k = 1024,
against 336 MB in blocks). The iteration is the same over every row;
only the order of the float32 summation of the centroid sums differs.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# bytes of the [rows, k] float32 distance block a step holds at once (its
# one-hot is as large)
BLOCK_BYTES = 1 << 28


def block_rows(n: int, k: int) -> int:
    """Rows per block for ``n`` rows and ``k`` clusters."""
    return max(1, min(n, BLOCK_BYTES // (4 * k)))


def n_blocks(n: int, k: int) -> int:
    """Blocks one pass over ``n`` rows takes (the last may be shorter)."""
    return -(-n // block_rows(n, k))


def _pairwise_sq_l2(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """[n, d] x [m, d] -> [n, m] squared L2 distances."""
    an = jnp.sum(a * a, axis=1)[:, None]
    bn = jnp.sum(b * b, axis=1)[None, :]
    return an - 2.0 * (a @ b.T) + bn


def _init_centers(x: jnp.ndarray, k: int, key: jax.Array) -> jnp.ndarray:
    """Greedy farthest-point init on a subsample (deterministic given key)."""
    n = x.shape[0]
    idx0 = jax.random.randint(key, (), 0, n)
    first = x[idx0]

    def body(carry, _):
        centers, count = carry
        d = _pairwise_sq_l2(x, centers)          # [n, k]
        # only the first `count` centers are valid
        valid = jnp.arange(centers.shape[0]) < count
        d = jnp.where(valid[None, :], d, jnp.inf)
        mind = jnp.min(d, axis=1)                # [n]
        nxt = jnp.argmax(mind)
        centers = centers.at[count].set(x[nxt])
        return (centers, count + 1), None

    centers0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(first)
    (centers, _), _ = jax.lax.scan(body, (centers0, 1), None, length=k - 1)
    return centers


def _fold_blocks(x: jnp.ndarray, block: int, fn, carry):
    """``carry = fn(carry, rows, lo)`` over the row blocks of ``x`` in
    order: a loop over the full blocks, then the shorter last one."""
    n, d = x.shape
    full = n // block

    def body(i, c):
        lo = i * block
        return fn(c, jax.lax.dynamic_slice(x, (lo, 0), (block, d)), lo)

    carry = jax.lax.fori_loop(0, full, body, carry)
    if n > full * block:
        carry = fn(carry, x[full * block:], full * block)
    return carry


@partial(jax.jit, static_argnames=("k", "iters", "block"))
def _fit(x: jnp.ndarray, k: int, iters: int, seed, block: int):
    key = jax.random.PRNGKey(seed)
    # subsample for init to bound the O(n·k) greedy pass
    n = x.shape[0]
    sub = min(n, 4096)
    perm = jax.random.permutation(key, n)[:sub]
    centers = _init_centers(x[perm], k, key)

    def step(centers, _):
        def accumulate(c, xb, lo):
            sums, counts, far_d, far_i = c
            d = _pairwise_sq_l2(xb, centers)           # [block, k]
            one_hot = jax.nn.one_hot(jnp.argmin(d, axis=1), k, dtype=x.dtype)
            mind = jnp.min(d, axis=1)
            i = jnp.argmax(mind)
            # the first row farthest from its center, as an argmax over
            # all rows would pick it
            further = mind[i] > far_d
            return (sums + one_hot.T @ xb, counts + jnp.sum(one_hot, axis=0),
                    jnp.where(further, mind[i], far_d),
                    jnp.where(further, lo + i, far_i))

        init = (jnp.zeros((k, x.shape[1]), x.dtype), jnp.zeros((k,), x.dtype),
                jnp.array(-jnp.inf, x.dtype), jnp.array(0, jnp.int32))
        sums, counts, _, far = _fold_blocks(x, block, accumulate, init)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # re-seed empties at the farthest point from its center
        new = jnp.where((counts > 0)[:, None], new, x[far][None, :])
        return new, None

    centers, _ = jax.lax.scan(step, centers, None, length=iters)

    def assign(out, xb, lo):
        a = jnp.argmin(_pairwise_sq_l2(xb, centers), axis=1).astype(jnp.int32)
        return jax.lax.dynamic_update_slice(out, a, (lo,))

    return centers, _fold_blocks(x, block, assign, jnp.zeros((n,), jnp.int32))


def kmeans_fit(
    x: jnp.ndarray, k: int, iters: int = 12, seed: int = 0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (centers [k, d], assignment [n]), in row blocks of
    :func:`block_rows`."""
    return _fit(x, k, iters, seed, block_rows(x.shape[0], k))


def kmeans_fit_np(x: np.ndarray, k: int, iters: int = 12, seed: int = 0):
    c, a = kmeans_fit(jnp.asarray(x, jnp.float32), k, iters, seed)
    return np.asarray(c), np.asarray(a)
