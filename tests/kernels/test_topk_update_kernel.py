"""Running-top-K Pallas kernel vs the sort-based oracle: shape sweep,
duplicate/invalid handling, and at served shapes the per-tile pass counts
and bit-for-bit equality, ties included. Interpret mode on CPU."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.ref import running_topk_ref
from repro.kernels.topk_update import running_topk_update, topk_pass_counts


def _mk(m, c, k, seed=0, frac_invalid=0.2, run_filled=True):
    rng = np.random.default_rng(seed)
    scores = rng.uniform(0, 100, size=(m, c)).astype(np.float32)
    scores[rng.random((m, c)) < frac_invalid] = np.inf
    ids = rng.integers(0, 10_000, size=(m, c)).astype(np.int32)
    if run_filled:
        run_s = np.sort(rng.uniform(0, 100, size=(m, k)).astype(np.float32), axis=1)
        run_i = rng.integers(10_000, 20_000, size=(m, k)).astype(np.int32)
    else:
        run_s = np.full((m, k), np.inf, np.float32)
        run_i = np.full((m, k), -1, np.int32)
    return map(jnp.asarray, (scores, ids, run_s, run_i))


@pytest.mark.parametrize("m,c,k", [(1, 8, 4), (8, 64, 10), (13, 100, 5), (4, 16, 16)])
@pytest.mark.parametrize("run_filled", [True, False])
def test_matches_oracle(m, c, k, run_filled):
    scores, ids, run_s, run_i = _mk(m, c, k, seed=m * c + k,
                                    run_filled=run_filled)
    got_s, got_i = running_topk_update(scores, ids, run_s, run_i, k=k,
                                       tile_m=4, interpret=True)
    want_s, want_i = running_topk_ref(scores, ids, run_s, run_i, k)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=1e-6)
    # ids must match except across exact score ties
    gs, ws = np.asarray(got_s), np.asarray(want_s)
    gi, wi = np.asarray(got_i), np.asarray(want_i)
    diff = gi != wi
    if diff.any():
        r, c_ = np.nonzero(diff)
        assert np.allclose(gs[r, c_], ws[r, c_]), "id mismatch beyond ties"


def test_all_invalid_chunk_keeps_running():
    scores = jnp.full((3, 10), jnp.inf, jnp.float32)
    ids = jnp.full((3, 10), -1, jnp.int32)
    run_s = jnp.asarray(np.sort(np.random.default_rng(0).uniform(0, 1, (3, 5)), axis=1),
                        jnp.float32)
    run_i = jnp.arange(15, dtype=jnp.int32).reshape(3, 5)
    got_s, got_i = running_topk_update(scores, ids, run_s, run_i, k=5,
                                       tile_m=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(run_s))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(run_i))


# ---------------------------------------------------------------- pass counts
# Served shapes: 256-row chunks, 8-query tiles, K = 10 (fp32) and K' = 40
# (int8 stage 1). Two tiles of 8 rows each.
C_SERVED, TILE_SERVED, M_SERVED = 256, 8, 16


def _served_case(case, k, seed):
    """(scores, ids, run_s, run_i) with a known number of candidates below
    each row's K-th running score."""
    rng = np.random.default_rng(seed)
    m, c = M_SERVED, C_SERVED
    run_s = np.sort(rng.uniform(0, 50, size=(m, k)).astype(np.float32), axis=1)
    kth = run_s[:, -1:]
    above = rng.uniform(1, 50, size=(m, c)).astype(np.float32) + kth
    scores = np.where(rng.random((m, c)) < 0.5, above, np.inf).astype(np.float32)
    below = lambda n: rng.uniform(0, 1, size=(m, n)).astype(np.float32) * kth
    if case == "exactly_k":
        scores[:, 7:7 + k] = below(k)
    elif case == "more_than_k":
        scores[:, 3:3 + 3 * k] = below(3 * k)
    elif case == "ties":
        # integer scores: ties among candidates, with running entries and
        # at the K-th score itself
        run_s = np.sort(rng.integers(0, 8, size=(m, k)), axis=1).astype(np.float32)
        scores = rng.integers(0, 10, size=(m, c)).astype(np.float32)
        scores[rng.random((m, c)) < 0.5] = np.inf
        scores[:, 0] = run_s[:, -1]
    elif case == "one_row_needs_k":
        scores = np.full((m, c), np.inf, np.float32)
        scores[0, 100:100 + 2 * k] = below(2 * k)[0]
    elif case == "empty_run":
        run_s = np.full((m, k), np.inf, np.float32)
        scores[rng.random((m, c)) < 0.9] = np.inf
    else:
        assert case == "none_below"
    run_i = rng.integers(1 << 20, 1 << 21, size=(m, k)).astype(np.int32)
    run_i[~np.isfinite(run_s)] = -1
    ids = rng.integers(0, 1 << 20, size=(m, c)).astype(np.int32)
    return scores, ids, run_s, run_i


def _recount(scores, run_s, k, tile_m):
    below = np.minimum((scores < run_s[:, -1:]).sum(axis=1), k)
    return below.reshape(-1, tile_m).max(axis=1)


EXPECTED_PASSES = {
    "none_below": lambda k: [0, 0],
    "exactly_k": lambda k: [k, k],
    "more_than_k": lambda k: [k, k],
    "one_row_needs_k": lambda k: [k, 0],
}


@pytest.mark.parametrize("k", [10, 40])
@pytest.mark.parametrize("case", ["none_below", "exactly_k", "more_than_k",
                                  "ties", "one_row_needs_k", "empty_run"])
def test_served_shapes_match_oracle_exactly(case, k):
    """Scores, ids and tie order equal the stable sort oracle bit for bit,
    and the per-tile pass counts equal a NumPy recount."""
    scores, ids, run_s, run_i = _served_case(case, k, seed=k)
    args = [jnp.asarray(a) for a in (scores, ids, run_s, run_i)]
    passes = np.asarray(topk_pass_counts(args[0], args[2], k=k,
                                         tile_m=TILE_SERVED))
    np.testing.assert_array_equal(passes,
                                  _recount(scores, run_s, k, TILE_SERVED))
    if case in EXPECTED_PASSES:
        np.testing.assert_array_equal(passes, EXPECTED_PASSES[case](k))
    got_s, got_i = running_topk_update(*args, k=k, tile_m=TILE_SERVED,
                                       interpret=True)
    want_s, want_i = running_topk_ref(*args, k)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_ties_follow_the_stable_merge():
    """A running entry wins a tie with a candidate, the lower column wins
    among equal candidates, and a candidate equal to the K-th score stays
    out."""
    k = 4
    run_s = np.array([[1, 2, 2, 5]] * 8, np.float32)
    run_i = np.array([[10, 20, 21, 50]] * 8, np.int32)
    scores = np.full((8, 8), np.inf, np.float32)
    scores[:, [6, 1, 3, 5]] = [2, 2, 5, 0]
    ids = np.tile(np.arange(100, 108, dtype=np.int32), (8, 1))
    args = [jnp.asarray(a) for a in (scores, ids, run_s, run_i)]
    got_s, got_i = running_topk_update(*args, k=k, tile_m=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s)[0], [0, 1, 2, 2])
    np.testing.assert_array_equal(np.asarray(got_i)[0], [105, 10, 20, 21])
    run_s[:, 3] = 3                                # now 2, 2 from columns 1, 6
    args[2] = jnp.asarray(run_s)
    scores[:, 5] = np.inf
    args[0] = jnp.asarray(scores)
    got_s, got_i = running_topk_update(*args, k=k, tile_m=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s)[0], [1, 2, 2, 2])
    np.testing.assert_array_equal(np.asarray(got_i)[0], [10, 20, 21, 101])
    scores[:] = np.inf                             # only the K-th score itself
    scores[:, 2] = 3
    args[0] = jnp.asarray(scores)
    got_s, got_i = running_topk_update(*args, k=k, tile_m=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s), run_s)
    np.testing.assert_array_equal(np.asarray(got_i), run_i)
