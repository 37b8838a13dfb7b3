"""Plain reference of IVF search, and the comparison that decides `correct`.

Independent of the code under test: NumPy in float64 over the benchmark's
own vectors (row i of the generated corpus is id i). From the index it reads
only the state that IVF search is defined over: the centroids and which
list each id belongs to. It holds each id's list to its nearest centroid
(``misassigned``), picks the probed lists itself and scans exactly those
lists.

Two semantics, as the configuration states them:

* ``fp32`` (IVF-Flat): the k nearest rows of the probed lists by exact L2.
* ``int8`` (IVF-SQ8 with re-rank): rows and queries are encoded on one
  affine int8 grid fit to the corpus range; stage 1 keeps the ``k *
  rerank_factor`` rows of the probed lists nearest in the integer code
  distance; stage 2 returns the k of those nearest by exact L2.

A served answer is judged by two gaps, each the largest over its k rows:

* the *rank gap*: how far the answer's rows depart from what the semantics
  allow, relative to the exact distance (IVF-Flat: the served rows' exact
  distances against the reference's ladder of the k nearest; IVF-SQ8: a
  row stage 1 could not have kept, or a nearer row it surely kept that
  the answer left out). Rounding ties give gaps under 1e-4; a wrong row
  1e-3 and more.
* the *score gap*: how far each served score lies from its row's
  float32 distance computed in the standard form |q|^2 + |x|^2 - 2 q.x,
  with the dot product correctly rounded, relative to the exact distance.
  Full float32 arithmetic reads a few 1e-6; products in a lower precision
  read an order of magnitude more.
"""

from __future__ import annotations

import itertools

import numpy as np

# f32 rounding of a centroid distance, as a share of |q|^2 + |c|^2: lists
# whose distance lies within this band of the nprobe-th are tied, and
# either choice is a valid probe set
PROBE_TIE = 3e-5
# a served row whose exact distance lies within this share of the
# reference's is taken as a rounding tie
TIE = 1e-5
# stage-1 code distances this many integer units from the cut may fall on
# either side of it once the program combines them in float32
CODE_MARGIN = 2
INVALID = 1e300        # the gap of an answer that names no valid row
# The program's k-means may take its dot products in one bfloat16 pass (a
# TPU's default for float32): each product x_j c_j is then off by up to
# 2^-7 of itself, a distance by up to 2^-6 sum_j |x_j c_j|, and two lists
# compared by the sum of both. A row whose list lies farther than that
# from its nearest centroid is in the wrong list.
ASSIGN_ROUNDING = 2.0 ** -6
SCREEN_ROWS = 1 << 16  # rows per block of the screening pass


class Lists:
    """IVF list membership as plain arrays: ``members[offsets[l]:offsets[l+1]]``
    are the ids of list l."""

    def __init__(self, centroids: np.ndarray, list_of_id: np.ndarray):
        self.centroids = np.asarray(centroids, np.float64)
        self.list_of_id = np.asarray(list_of_id, np.int64)
        n_lists = len(self.centroids)
        self.members = np.argsort(self.list_of_id, kind="stable")
        counts = np.bincount(self.list_of_id, minlength=n_lists)
        self.offsets = np.zeros(n_lists + 1, np.int64)
        np.cumsum(counts, out=self.offsets[1:])

    def rows(self, lst: int) -> np.ndarray:
        return self.members[self.offsets[lst]:self.offsets[lst + 1]]

    @property
    def sizes(self) -> np.ndarray:
        return np.diff(self.offsets)


def _screen(x: np.ndarray, centroids: np.ndarray, list_of_id: np.ndarray) -> np.ndarray:
    """Rows whose list's centroid is farther than half the rounding band
    from the nearest centroid, by float32 distances at full precision on
    the default JAX device (a fast filter; the verdict is float64)."""
    import jax
    import jax.numpy as jnp

    c = jnp.asarray(np.asarray(centroids, np.float32))

    @jax.jit
    def gap(xb, a):
        hi = jax.lax.Precision.HIGHEST
        d = jnp.sum(c * c, axis=1)[None, :] - 2.0 * jnp.matmul(xb, c.T, precision=hi)
        best = jnp.argmin(d, axis=1)
        s_abs = lambda l: jnp.sum(jnp.abs(xb) * jnp.abs(c[l]), axis=1)  # noqa: E731
        band = ASSIGN_ROUNDING * (s_abs(a) + s_abs(best))
        return (jnp.take_along_axis(d, a[:, None], 1)[:, 0]
                - jnp.take_along_axis(d, best[:, None], 1)[:, 0]) > 0.5 * band

    rows = min(SCREEN_ROWS, len(x))
    out = []
    for lo in range(0, len(x), rows):
        xb = np.zeros((rows, x.shape[1]), np.float32)
        ab = np.zeros(rows, np.int32)
        n = min(rows, len(x) - lo)
        xb[:n], ab[:n] = x[lo:lo + n], np.maximum(list_of_id[lo:lo + n], 0)
        out.append(lo + np.nonzero(np.asarray(gap(xb, ab))[:n])[0])
    return np.concatenate(out)


def misassigned(x: np.ndarray, centroids: np.ndarray, list_of_id: np.ndarray) -> int:
    """Rows in a list whose centroid lies farther from the row, in float64,
    than the nearest centroid plus the program's rounding band. Rows in
    no list (-1) are not counted here."""
    rows = _screen(x, centroids, list_of_id)
    rows = rows[list_of_id[rows] >= 0]
    if not len(rows):
        return 0
    c = np.asarray(centroids, np.float64)
    xr = x[rows].astype(np.float64)
    d = (c * c).sum(1)[None, :] - 2.0 * xr @ c.T
    a, best = list_of_id[rows], d.argmin(1)
    s_abs = lambda l: (np.abs(xr) * np.abs(c[l])).sum(1)  # noqa: E731
    band = ASSIGN_ROUNDING * (s_abs(a) + s_abs(best))
    return int(np.sum(d[np.arange(len(rows)), a] - d[np.arange(len(rows)), best] > band))


def probe_sets(lists: Lists, q: np.ndarray, nprobe: int) -> list:
    """For each query, every valid probe set: the ``nprobe`` nearest
    centroids, and where lists tie at the cut, each way of filling it."""
    q = np.asarray(q, np.float64)
    c = lists.centroids
    qn, cn = (q * q).sum(1), (c * c).sum(1)
    d = qn[:, None] - 2.0 * q @ c.T + cn[None, :]
    order = np.argsort(d, axis=1, kind="stable")
    out = []
    for i in range(len(q)):
        o = order[i]
        cut = d[i, o[nprobe - 1]]
        band = PROBE_TIE * (qn[i] + cn[o[nprobe - 1]])
        sure = [l for l in o[:nprobe] if d[i, l] < cut - band]
        tied = [l for l in o[: nprobe + 8] if abs(d[i, l] - cut) <= band]
        free = nprobe - len(sure)
        if len(tied) == free:
            out.append([np.asarray(o[:nprobe])])
        else:
            combos = itertools.islice(itertools.combinations(tied, free), 64)
            out.append([np.asarray(sure + list(t)) for t in combos])
    return out


def sq_grid(x: np.ndarray, levels: int) -> tuple:
    """One affine grid over all dimensions fit to the corpus's range:
    codes in [-levels, levels]."""
    mn, mx = float(x.min()), float(x.max())
    return np.float32(max((mx - mn) / (2 * levels), 1e-8)), np.float32(0.5 * (mn + mx))


def sq_encode(v: np.ndarray, grid: tuple, levels: int) -> np.ndarray:
    scale, zero = grid
    c = np.rint((np.asarray(v, np.float32) - zero) / scale)
    return np.clip(c, -levels, levels).astype(np.int64)


def exact_d2(x: np.ndarray, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Exact squared L2 from one query to the given ids, float64."""
    diff = x[ids].astype(np.float64) - np.asarray(q, np.float64)[None, :]
    return (diff * diff).sum(1)


def _valid(ids: np.ndarray, n: int, k: int) -> bool:
    return (len(ids) == k and (ids >= 0).all() and (ids < n).all()
            and len(np.unique(ids)) == k)


def f32_d2(x: np.ndarray, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float32 squared L2 in the standard form: float32 norms, the dot
    product correctly rounded to float32, combined in float32."""
    xs = x[ids]
    qn = np.sum(q * q, dtype=np.float32)
    xn = np.sum(xs * xs, axis=1, dtype=np.float32)
    dot = (xs.astype(np.float64) @ np.asarray(q, np.float64)).astype(np.float32)
    return (qn + xn) - np.float32(2.0) * dot


def score_gap(x, q, ids, scores, own) -> float:
    """Largest relative distance of a served score from its row's float32
    distance."""
    e32 = f32_d2(x, q, ids).astype(np.float64)
    return float((np.abs(np.asarray(scores, np.float64) - e32) / own).max())


def flat_gap(scanned, own, ids, k) -> float:
    """Rank gap of one IVF-Flat answer: rank by rank, how far the served
    rows' exact distances lie from the reference's ladder of the k
    nearest, relative to it. The best over the valid probe sets;
    ``scanned`` holds (candidate ids, their exact distances) for each."""
    best = INVALID
    for cand, d, _ in scanned:
        if len(d) < k or not np.isin(ids, cand).all():
            continue
        ref = np.sort(np.partition(d, k - 1)[:k])
        best = min(best, float((np.abs(np.sort(own) - ref) / ref).max()))
    return best


def sq8_gap(scanned, own, ids, kp) -> float:
    """Rank gap of one IVF-SQ8 answer. Stage 1 may keep any ``kp`` rows
    between the sure set (code distance below the cut by more than the
    margin) and the possible set (within the margin above it). The answer
    departs by a served row outside the possible set (invalid), or by a
    sure row nearer than the served k-th that it left out. The best over
    the valid probe sets; ``scanned`` holds (candidate ids, exact
    distances, code distances)."""
    best = INVALID
    worst = own.max()
    for cand, d, code_d in scanned:
        cut = np.partition(code_d, kp - 1)[kp - 1] if len(cand) > kp else code_d.max()
        if not np.isin(ids, cand[code_d <= cut + CODE_MARGIN]).all():
            continue
        left = (code_d < cut - CODE_MARGIN) & ~np.isin(cand, ids) & (d < worst * (1 - TIE))
        best = min(best, float((worst - d[left].min()) / worst) if left.any() else 0.0)
    return best


class Reference:
    """The reference for one deployment: the corpus, the index's lists and
    the semantics the configuration states. ``levels`` is the code range
    of the scalar quantiser (127 for int8)."""

    def __init__(self, x: np.ndarray, centroids, list_of_id, cfg: dict,
                 levels: int = 127):
        self.x = x
        self.lists = Lists(centroids, list_of_id)
        self.k, self.nprobe = cfg["k"], cfg["nprobe"]
        self.sq = cfg["precision"] == "int8"
        if self.sq:
            self.kp = cfg["k"] * cfg["rerank_factor"]
            self.levels = levels
            self.grid = sq_grid(x, levels)
            self.codes = sq_encode(x, self.grid, levels).astype(np.int8)

    def misassigned(self) -> int:
        return misassigned(self.x, self.lists.centroids, self.lists.list_of_id)

    def probe_sets(self, q: np.ndarray) -> list:
        return probe_sets(self.lists, q, self.nprobe)

    def _scan(self, q: np.ndarray, choices: list) -> list:
        """Exact (and, for SQ8, code) distances from each query to every
        list of its probe sets, one BLAS product per list. Returns per
        query one (candidate ids, distances, code distances) per choice."""
        q64 = np.asarray(q, np.float64)
        qn = (q64 * q64).sum(1)
        qc = sq_encode(q, self.grid, self.levels).astype(np.float64) if self.sq else None
        by_list: dict = {}
        for i, ch in enumerate(choices):
            for lst in set(np.concatenate(ch).tolist()):
                by_list.setdefault(lst, []).append(i)
        parts = [dict() for _ in range(len(q))]
        for lst, qi in by_list.items():
            rows = self.lists.rows(lst)
            xl = self.x[rows].astype(np.float64)
            d = (xl * xl).sum(1)[None, :] - 2.0 * q64[qi] @ xl.T + qn[qi][:, None]
            d = np.maximum(d, 0.0)
            cd = None
            if self.sq:
                cl = self.codes[rows].astype(np.float64)
                cd = ((cl * cl).sum(1)[None, :] - 2.0 * qc[qi] @ cl.T
                      + (qc[qi] * qc[qi]).sum(1)[:, None])
            for j, i in enumerate(qi):
                parts[i][lst] = (rows, d[j], None if cd is None else cd[j])
        out = []
        for i, ch in enumerate(choices):
            per = []
            for probes in ch:
                pieces = [parts[i][int(l)] for l in probes]
                per.append(tuple(
                    None if pieces[0][j] is None
                    else np.concatenate([p[j] for p in pieces])
                    for j in range(3)))
            out.append(per)
        return out

    def gaps(self, q: np.ndarray, ids: np.ndarray, scores: np.ndarray,
             block: int = 1024) -> tuple:
        """(rank gaps, score gaps) of each served answer (rows of
        ``ids``/``scores``)."""
        ids = np.asarray(ids, np.int64)
        rank = np.full(len(q), INVALID)
        score = np.full(len(q), INVALID)
        for lo in range(0, len(q), block):
            qb = q[lo:lo + block]
            scanned = self._scan(qb, probe_sets(self.lists, qb, self.nprobe))
            for j, sc in enumerate(scanned):
                i = lo + j
                if not _valid(ids[i], len(self.x), self.k):
                    continue
                own = exact_d2(self.x, q[i], ids[i])
                rank[i] = (sq8_gap(sc, own, ids[i], self.kp) if self.sq
                           else flat_gap(sc, own, ids[i], self.k))
                score[i] = score_gap(self.x, q[i], ids[i], scores[i], own)
        return rank, score

    def answers(self, q: np.ndarray) -> tuple:
        """The reference's own answers over its first valid probe set: ids
        and float64 distances, [n, k] each."""
        choices = [ch[:1] for ch in probe_sets(self.lists, q, self.nprobe)]
        ids = np.empty((len(q), self.k), np.int64)
        d2 = np.empty((len(q), self.k))
        for i, ((cand, d, code_d),) in enumerate(self._scan(q, choices)):
            if self.sq:
                keep = np.argsort(code_d, kind="stable")[: self.kp]
                cand, d = cand[keep], d[keep]
            top = np.argsort(d, kind="stable")[: self.k]
            ids[i], d2[i] = cand[top], d[top]
        return ids, d2
