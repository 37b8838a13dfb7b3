"""Seeded data and traffic for the on-chip benchmark.

The corpus stands in for ann-benchmarks' SIFT1M: a Gaussian mixture in
float32 with a positive offset, so that norms are several times the
nearest-neighbour distances, as in SIFT. Each component holds the same
number of rows, so every seed has the same sizes and only the geometry
moves. Queries are fresh draws from the same mixture (held-out samples,
as SIFT's query set is), so no query repeats a stored vector.

Derived from ``repro.data.vectors.make_dataset``/``make_queries``, rewritten
to draw float32 directly and with no per-query Python loop.
"""

from __future__ import annotations

import numpy as np

# one independent stream per purpose, so the corpus does not depend on how
# many queries a run draws
STREAM_CORPUS, STREAM_QUERIES, STREAM_ARRIVALS, STREAM_WARM, STREAM_SAMPLE = range(5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Generator for one purpose of one seed. Any whole number is a seed;
    negative ones wrap to 64 bits."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def _centers(seed: int, dim: int, g: dict) -> np.ndarray:
    """Component centers: the first draw of the corpus stream."""
    rng = rng_for(seed, STREAM_CORPUS)
    c = rng.standard_normal((g["components"], dim), dtype=np.float32)
    c *= np.float32(g["center_scale"] / np.sqrt(dim))
    return c + np.float32(g["offset"])


def _draw(rng: np.random.Generator, centers: np.ndarray, comp: np.ndarray,
          g: dict) -> np.ndarray:
    """Rows around ``centers[comp]``: per-row lognormal radius (log-sd
    ``radius_sigma``) times a Gaussian direction, so local density varies
    as in real corpora."""
    n, dim = len(comp), centers.shape[1]
    radius = np.exp(np.float32(g["radius_sigma"])
                    * rng.standard_normal((n, 1), dtype=np.float32))
    x = rng.standard_normal((n, dim), dtype=np.float32)
    x *= radius * np.float32(g["spread"] / np.sqrt(dim))
    x += centers[comp]
    return x


def corpus(seed: int, rows: int, dim: int, g: dict) -> np.ndarray:
    """[rows, dim] float32; row i is id i. Components hold equal shares."""
    centers = _centers(seed, dim, g)
    rng = rng_for(seed, STREAM_CORPUS)
    rng.standard_normal((g["components"], dim), dtype=np.float32)  # the centers
    comp = rng.permutation(np.arange(rows) % g["components"])
    return _draw(rng, centers, comp, g)


def component_probs(seed: int, components: int, mix: dict) -> np.ndarray:
    """Query share of each mixture component: uniform, or Zipf with
    constant ``zipf`` over ranks permuted by the seed."""
    if mix["components"] == "uniform":
        return np.full(components, 1.0 / components)
    if mix["components"] != "zipf":
        raise ValueError(f"unknown component distribution {mix['components']!r}")
    w = 1.0 / np.arange(1, components + 1) ** float(mix["zipf"])
    w = w[rng_for(seed, STREAM_QUERIES).permutation(components)]
    return w / w.sum()


def queries(seed: int, n: int, dim: int, g: dict, mix: dict,
            stream: int = STREAM_QUERIES) -> np.ndarray:
    """[n, dim] float32 queries drawn from the mixture at the mix's
    component shares."""
    centers = _centers(seed, dim, g)
    p = component_probs(seed, g["components"], mix)
    rng = rng_for(seed, stream)
    rng.permutation(g["components"])                    # same draw as above
    comp = rng.choice(g["components"], size=n, p=p)
    return _draw(rng, centers, comp, g)


def arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop at ``rate``:
    a Poisson process conditioned on round(rate * seconds) arrivals, so
    every seed offers the same number of requests."""
    n = max(1, int(round(rate * seconds)))
    gaps = rng_for(seed, STREAM_ARRIVALS).exponential(size=n + 1)
    return (np.cumsum(gaps)[:-1] / gaps.sum() * seconds).astype(np.float64)
