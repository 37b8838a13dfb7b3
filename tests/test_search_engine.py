"""End-to-end exactness of the HARMONY staged engine vs the single-node
oracle, across modes/plans/metrics. Pruning must never change results."""

import numpy as np
import pytest

from repro.config import HarmonyConfig
from repro.core import (
    assign_queries,
    build_ivf,
    harmony_search,
    plan_search,
    preassign,
    prewarm_tau,
    search_oracle,
)
from repro.data import make_dataset, make_queries


def _compare(oracle, got, rtol=1e-4, atol=1e-4):
    """Scores must match; ids must match except across near-ties."""
    assert oracle.scores.shape == got.scores.shape
    np.testing.assert_allclose(got.scores, oracle.scores, rtol=rtol, atol=atol)
    nq, k = oracle.ids.shape
    for i in range(nq):
        if not np.array_equal(oracle.ids[i], got.ids[i]):
            # permit permutations among (near-)tied scores only
            assert set(oracle.ids[i ].tolist()) == set(got.ids[i].tolist()) or np.allclose(
                np.sort(oracle.scores[i]), np.sort(got.scores[i]), rtol=rtol, atol=atol
            ), f"query {i}: ids diverge beyond ties"


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset(nb=6000, dim=96, n_components=24, seed=3)
    cfg = HarmonyConfig(dim=96, nlist=32, nprobe=6, topk=10, kmeans_iters=8)
    index = build_ivf(ds.x, cfg)
    q = make_queries(ds, nq=64, skew=0.3, seed=7)
    return ds, cfg, index, q


@pytest.mark.parametrize("mode,n_nodes", [("harmony", 8), ("vector", 4), ("dimension", 4)])
def test_engine_matches_oracle(setup, mode, n_nodes):
    ds, cfg, index, q = setup
    cfg2 = cfg.replace(mode=mode)
    decision = plan_search(index, n_nodes, cfg2)
    corpus = preassign(index, decision.plan)
    oracle = search_oracle(index, q)
    got = harmony_search(index, corpus, q)
    _compare(oracle, got)


def test_pruning_is_exact(setup):
    """enable_pruning on/off must give identical result sets."""
    ds, cfg, index, q = setup
    # pin a plan with dimension blocks so intermediate pruning is exercised
    decision = plan_search(index, 8, cfg.replace(mode="dimension"))
    corpus = preassign(index, decision.plan)
    on = harmony_search(index, corpus, q, enable_pruning=True)
    off = harmony_search(index, corpus, q, enable_pruning=False)
    _compare(off, on)
    # and pruning actually skipped work
    assert on.stats["pair_flops"] < off.stats["pair_flops"]


def test_prewarm_samples_a_repeated_probe_once(setup):
    """A probe table that repeats a cluster (a filter duplicate-fills the
    slots of excluded clusters) gives the τ of the table without the
    repeats: a sampled row counted twice toward K would pull τ below the
    kth-best and prune a true answer."""
    ds, cfg, index, q = setup
    probes = assign_queries(index, q, 2)        # 2 × 4 samples < K = 10
    rep = np.concatenate([probes, probes[:, :1].repeat(4, axis=1)], axis=1)
    want = prewarm_tau(index, q, probes, cfg.topk, samples_per_cluster=4)
    assert np.isinf(want).all()
    np.testing.assert_array_equal(
        prewarm_tau(index, q, rep, cfg.topk, samples_per_cluster=4), want)


def test_pipeline_off_matches(setup):
    ds, cfg, index, q = setup
    decision = plan_search(index, 8, cfg)
    corpus = preassign(index, decision.plan)
    oracle = search_oracle(index, q)
    got = harmony_search(index, corpus, q, pipeline=False)
    _compare(oracle, got)


def test_pruning_ratio_increases_by_slice(setup):
    """Paper Table 3: later slices prune more."""
    ds, cfg, index, q = setup
    cfg2 = cfg.replace(mode="dimension")
    decision = plan_search(index, 4, cfg2)
    corpus = preassign(index, decision.plan)
    res = harmony_search(index, corpus, q)
    ratios = res.stats["slice_pruned_ratio"]
    assert ratios[0] == 0.0
    assert all(ratios[i] <= ratios[i + 1] + 1e-9 for i in range(len(ratios) - 1))
    assert ratios[-1] > 0.2  # meaningful pruning by the last slice


def test_recall_against_brute_force(setup):
    """IVF with nprobe=6/32 should give decent recall on clustered data."""
    from repro.data import brute_force_topk, recall_at_k

    ds, cfg, index, q = setup
    decision = plan_search(index, 8, cfg)
    corpus = preassign(index, decision.plan)
    got = harmony_search(index, corpus, q)
    true_idx, _ = brute_force_topk(ds.x, q, cfg.topk)
    rec = recall_at_k(got.ids, true_idx)
    assert rec > 0.8, rec
