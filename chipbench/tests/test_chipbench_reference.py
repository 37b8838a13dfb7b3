"""The plain reference against brute force, and the answer gap."""

import numpy as np
import pytest

from chipbench import reference as R


def _deployment(n=600, dim=8, n_lists=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim)).astype(np.float32)
    centroids = x[rng.choice(n, n_lists, replace=False)]
    d = ((x[:, None, :] - centroids[None]) ** 2).sum(-1)
    return x, centroids, d.argmin(1)


def _brute(x, q, k):
    d = ((x[None].astype(np.float64) - q[:, None].astype(np.float64)) ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_full_coverage_equals_brute_force(precision):
    x, c, lists = _deployment()
    q = np.random.default_rng(1).standard_normal((20, 8)).astype(np.float32)
    cfg = {"k": 5, "nprobe": 6, "precision": precision, "rerank_factor": 120}
    ref = R.Reference(x, c, lists, cfg)
    ids, d2 = ref.answers(q)
    want_ids, want_d2 = _brute(x, q, 5)
    assert np.array_equal(ids, want_ids) and np.allclose(d2, want_d2)
    rank, score = ref.gaps(q, want_ids, want_d2.astype(np.float32))
    assert rank.max() < 1e-6 and score.max() < 1e-5


def test_gap_flags_a_wrong_row_a_wrong_score_and_a_missing_answer():
    x, c, lists = _deployment()
    q = np.random.default_rng(2).standard_normal((4, 8)).astype(np.float32)
    ref = R.Reference(x, c, lists, {"k": 5, "nprobe": 2, "precision": "fp32"})
    ids, d2 = ref.answers(q)
    sc = d2.astype(np.float32)
    assert max(g.max() for g in ref.gaps(q, ids, sc)) < 1e-5
    wrong = ids.copy()
    wrong[0, 4] = ref.answers(q + 0.5)[0][0, 0] if ids[0, 0] != 0 else 1
    wrong_sc = sc.copy()
    wrong_sc[1, 2] *= 1.01
    missing = ids.copy()
    missing[2, 3] = -1
    rank, _ = ref.gaps(q, wrong, sc)
    assert rank[0] > 1e-3 and rank[1:].max() < 1e-6
    rank, score = ref.gaps(q, ids, wrong_sc)
    assert score[1] > 1e-3 and rank.max() < 1e-6
    assert ref.gaps(q, missing, sc)[0][2] == R.INVALID


def test_tied_probe_lists_allow_either_probe_set():
    c = np.array([[0.0, 0.0], [1.0, 0.0], [-1.0, 0.0], [0.0, 5.0]])
    lists = R.Lists(c, np.array([0, 1, 2, 3]))
    (sets,) = R.probe_sets(lists, np.array([[0.0, 0.0]]), 2)
    assert sorted(map(sorted, (s.tolist() for s in sets))) == [[0, 1], [0, 2]]


def test_sq8_semantics_keep_stage_one_by_code_distance():
    x, c, lists = _deployment(n=400, dim=8, n_lists=4, seed=3)
    q = np.random.default_rng(4).standard_normal((6, 8)).astype(np.float32)
    cfg = {"k": 3, "nprobe": 4, "precision": "int8", "rerank_factor": 2}
    ref = R.Reference(x, c, lists, cfg)
    ids, _ = ref.answers(q)
    grid = R.sq_grid(x, 127)
    codes, qc = R.sq_encode(x, grid, 127), R.sq_encode(q, grid, 127)
    for i in range(len(q)):
        code_d = ((codes - qc[i]) ** 2).sum(1)
        keep = np.argsort(code_d, kind="stable")[:6]
        exact = ((x[keep].astype(np.float64) - q[i]) ** 2).sum(1)
        assert set(ids[i]) == set(keep[np.argsort(exact)[:3]])


def test_misassigned_counts_rows_in_a_far_list_and_not_rounding_ties():
    x, c, lists = _deployment(n=3000, dim=8, n_lists=6, seed=5)
    assert R.misassigned(x, c, lists) == 0
    d = ((x[:, None, :].astype(np.float64) - c[None]) ** 2).sum(-1)
    wrong = lists.copy()
    wrong[:7] = d[:7].argmax(1)                  # the farthest list
    wrong[7] = -1                                # in no list: not counted here
    assert R.misassigned(x, c, wrong) == 7
    # a row all but equidistant from two centroids may sit in either list
    tie = np.array([[0.5, 0, 0, 0, 0, 0, 0, 0], [0.5 + 1e-4, 0, 0, 0, 0, 0, 0, 0]],
                   np.float32)
    cc = np.array([[0] * 8, [1] + [0] * 7], np.float32)
    assert R.misassigned(tie, cc, np.array([1, 0])) == 0
