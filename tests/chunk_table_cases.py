"""Exactness of the executor's chunk table: the resident step reads the
probed lists in place, chunk by chunk, and must score each probed live
row exactly once.

:func:`run` serves batches that build tables with lists adjacent in the
pack (merged into one run), lists longer than a chunk, list starts off
the row alignment of the corpus dtype, a last window pulled back inside its shard,
tombstones inside a run, a filter that leaves few rows of each list,
and empty probe sets, and checks every answer against
``search_oracle``. ``tests/test_executor.py`` calls it on one device and,
in a subprocess with four host devices, on a 2 × 2 mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \\
        python tests/chunk_table_cases.py fp32 2
"""

import sys

import numpy as np

from repro.config import HarmonyConfig
from repro.core import NumRange, assign_queries, build_ivf, search_oracle
from repro.core.search import filter_excluded_rows
from repro.serve.executor import ExecutorConfig, SpmdExecutor

NLIST, DIM, K = 16, 16, 5
# answers this long hold every row of any query's three probed lists, so
# a row scored twice or not at all shows
K_ALL = 448
# 64 rows, a multiple of every row alignment: on this corpus some list is
# cut so that its last window would cross the shard's end, on one shard
# and on two, at the alignment of fp32 rows and of int8 rows
CHUNK = 64


def _index():
    """Well-separated lists of 37–139 rows (most starts off the row
    alignment), a price column, and 16 queries near corpus rows."""
    rng = np.random.default_rng(11)
    sizes = rng.integers(37, 140, NLIST)
    centers = rng.standard_normal((NLIST, DIM)) * 10
    x = np.concatenate([c + rng.standard_normal((n, DIM))
                        for c, n in zip(centers, sizes)]).astype(np.float32)
    x = x[rng.permutation(len(x))]
    price = rng.uniform(0.0, 1.0, len(x))
    cfg = HarmonyConfig(dim=DIM, nlist=NLIST, nprobe=3, topk=K, kmeans_iters=10)
    q = x[rng.choice(len(x), 16)] + 3.0 * rng.standard_normal((16, DIM))
    return build_ivf(x, cfg, meta={"price": price}), q.astype(np.float32)


def _check_table(ex: SpmdExecutor, probes: np.ndarray, dead) -> dict:
    """The table holds each probed, live row in exactly one live slot,
    its chunks start aligned and inside the shard, and it fits the ladder.
    Returns what it exercised: lists adjacent in the pack, lists longer
    than a chunk, unaligned list starts, more than one run, and a last
    window pulled back inside the shard."""
    table = ex._chunk_table(probes, dead)
    want = set()
    seen = {"adjacent": False, "long": False, "unaligned": False,
            "runs": False, "clamped": False}
    spans = sorted((*ex.corpus.cluster_slices[int(c)], int(c))
                   for c in np.unique(probes) if c >= 0)
    for i, (v, lo, hi, c) in enumerate(spans):
        plo = ex.index.cluster_rows(c)[0]       # shard row lo is packed row plo
        for r in range(lo, hi):
            if dead is None or not dead[plo + r - lo]:
                want.add((v, r))
        seen["long"] |= hi - lo > CHUNK
        seen["unaligned"] |= lo % ex.row_align != 0
        seen["adjacent"] |= (i > 0 and spans[i - 1][0] == v
                             and spans[i - 1][2] == lo < hi)
    if table is None:
        assert not want
        return seen
    rows = table.row_table()
    got = [(v, int(r)) for v in range(rows.shape[0]) for r in rows[v] if r >= 0]
    assert len(got) == len(set(got)) == table.rows
    assert set(got) == want
    assert (table.starts % ex.row_align == 0).all()
    assert (table.starts + CHUNK <= ex.cap_full).all()
    assert table.n_iter * CHUNK <= table.cap <= ex.cap_full
    assert not table.live[:, table.n_iter:].any()
    # each shard's windows come first and hold a live row each; the rest
    # of the table is all dead
    has_live = table.live.any(axis=2)
    assert (has_live[:, 1:] <= has_live[:, :-1]).all()
    assert has_live.sum(axis=1).max() == table.n_iter
    gaps = np.diff(table.starts[:, :table.n_iter], axis=1)
    seen["runs"] |= bool((gaps > CHUNK).any())
    # windows overlap only where the last one was pulled back inside
    seen["clamped"] |= bool((gaps < CHUNK).any())
    return seen


def _check_answer(res, oracle) -> None:
    """Scores equal the oracle's (ids may differ only across equal
    scores), and no id appears twice in an answer."""
    finite = np.isfinite(oracle.scores)
    assert np.array_equal(np.isfinite(res.scores), finite)
    np.testing.assert_allclose(res.scores[finite], oracle.scores[finite],
                               rtol=1e-4, atol=1e-4)
    for r in np.unique(np.nonzero((res.ids != oracle.ids) & finite)[0]):
        np.testing.assert_allclose(np.sort(res.scores[r]),
                                   np.sort(oracle.scores[r]),
                                   rtol=1e-4, atol=1e-4)
    for row in res.ids:
        ids = row[row >= 0]
        assert len(ids) == len(set(ids.tolist())), row


def _clamped_lists(ex: SpmdExecutor) -> list:
    """Lists whose own last window would cross their shard's end."""
    out = []
    for c in range(ex.index.nlist):
        v, lo, hi = ex.corpus.cluster_slices[c]
        a = lo - lo % ex.row_align
        if a + CHUNK * ((hi - 1 - a) // CHUNK) > ex.cap_full - CHUNK:
            out.append(c)
    return out


def run(precision: str, d_blocks: int = 1) -> None:
    index, q = _index()
    assert 3 * index.sizes.max() <= K_ALL
    # int8: stage 1 keeps every candidate, so the re-rank is exact
    ex = SpmdExecutor(index, ExecutorConfig(
        chunk=CHUNK, qb_buckets=(8,), use_pallas=False, precision=precision,
        rerank_factor=-(-index.nb // K), d_blocks=d_blocks))
    probed = np.bincount(assign_queries(index, q[8:]).ravel(), minlength=NLIST)
    lo0, hi0 = index.cluster_rows(int(np.argmax(probed)))
    tomb = np.zeros(index.nb, bool)
    tomb[np.arange(index.nb) % 7 == 3] = True          # inside every run
    tomb[lo0:hi0 - 1] = True                             # a list but its last row
    flt = NumRange("price", 0.0, 0.1)                    # ~90% of each list out
    ends = _clamped_lists(ex)
    assert ends
    # (queries, nprobe, tombstones, filter)
    cases = [(q[:8], None, None, None), (q[8:], None, tomb, None),
             (q[:8], None, None, flt), (q[8:], None, tomb, flt),
             (index.centers[ends], 1, None, None)]
    seen = {}
    for qs, nprobe, dead, f in cases:
        excluded = filter_excluded_rows(index, f, dead)
        for k in (K, K_ALL):
            res = ex.search_batch(qs, k=k, nprobe=nprobe, dead_rows=excluded)
            _check_answer(res, search_oracle(index, qs, k=k, nprobe=nprobe,
                                             dead_rows=dead, flt=f))
        for key, hit in _check_table(ex, assign_queries(index, qs, nprobe),
                                     excluded).items():
            seen[key] = seen.get(key, False) | hit
    assert all(seen.values()), seen
    # empty probe sets: none asked for, and every probed row dead
    scanned = ex.rows_scanned
    for res in (ex.search_batch(q[:4], nprobe=0),
                ex.search_batch(q[:4], dead_rows=np.ones(index.nb, bool))):
        assert (res.ids == -1).all() and np.isinf(res.scores).all()
    assert ex.rows_scanned == scanned
    summary = ex.stats_summary()
    assert summary["rows_scanned"] == summary["chunks_scanned"] * CHUNK > 0
    assert 0 < summary["rows_gathered"] <= summary["rows_scanned"]


if __name__ == "__main__":
    run(sys.argv[1], int(sys.argv[2]))
    print("CHUNK_TABLE_OK")
