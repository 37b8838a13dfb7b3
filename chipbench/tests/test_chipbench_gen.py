"""The seeded generator: deterministic per seed, float32, fixed sizes."""

import numpy as np
import pytest

from chipbench import gen

G = {"components": 7, "center_scale": 1.6, "spread": 3.0, "offset": 1.0,
     "radius_sigma": 0.1}


def test_corpus_is_deterministic_per_seed():
    a, b = gen.corpus(3, 700, 16, G), gen.corpus(3, 700, 16, G)
    assert a.dtype == np.float32 and a.shape == (700, 16)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, gen.corpus(4, 700, 16, G))


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, -5])
def test_any_whole_number_is_a_seed(seed):
    assert np.array_equal(gen.queries(seed, 5, 16, G, {"components": "uniform"}),
                          gen.queries(seed, 5, 16, G, {"components": "uniform"}))


def test_queries_follow_the_mix_and_differ_by_stream():
    zipf = {"components": "zipf", "zipf": 0.99}
    p = gen.component_probs(1, 7, zipf)
    assert p.sum() == pytest.approx(1.0) and p.max() > 2 * p.min()
    assert np.allclose(gen.component_probs(1, 7, {"components": "uniform"}), 1 / 7)
    q = gen.queries(1, 50, 16, G, zipf)
    w = gen.queries(1, 50, 16, G, zipf, stream=gen.STREAM_WARM)
    assert q.dtype == np.float32 and not np.array_equal(q, w)


def test_arrivals_fill_the_window_with_a_fixed_count():
    for seed in (1, 2):
        t = gen.arrivals(seed, 250.0, 4.0)
        assert len(t) == 1000
        assert (np.diff(t) > 0).all() and 0 < t[0] and t[-1] < 4.0
    assert not np.array_equal(gen.arrivals(1, 250.0, 4.0), gen.arrivals(2, 250.0, 4.0))
