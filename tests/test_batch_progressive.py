"""Batched progressive engine: exact per-lane parity with the per-query
drivers, bucketed capacity growth, lane recycling, and certificates."""
import numpy as np
import pytest

from repro.core.batch_progressive import (BatchProgressiveDriver,
                                          ProgressiveEngine,
                                          SignatureBudgetExceeded, batch_pds,
                                          batch_pgs, batch_pss)
from repro.core.pds import pds
from repro.core.pgs import pgs
from repro.core.progressive import ProgressiveDriver
from repro.core.pss import pss
from repro.index.flat import build_knn_graph


def _normalize(v):
    return (v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True),
                           1e-9)).astype(np.float32)


def _queries(x, num, seed=3, noise=0.05, unit=False):
    rng = np.random.default_rng(seed)
    qs = (x[rng.integers(0, x.shape[0], num)]
          + rng.normal(size=(num, x.shape[1])).astype(np.float32) * noise)
    return _normalize(qs) if unit else qs.astype(np.float32)


@pytest.fixture(scope="module")
def big_graph():
    """~10k-point cosine-space graph with mild clustering."""
    rng = np.random.default_rng(5)
    n, d = 10_000, 32
    centers = rng.normal(size=(64, d)) * 0.25
    x = _normalize(centers[rng.integers(0, 64, n)]
                   + rng.normal(size=(n, d)).astype(np.float32))
    return build_knn_graph(x, metric="cos", M=8), x


def _assert_lane_matches(r, bres, i):
    np.testing.assert_array_equal(np.asarray(r.ids), bres.ids[i])
    np.testing.assert_array_equal(np.asarray(r.scores), bres.scores[i])
    assert r.stats.certified == bool(bres.stats.certified[i])
    assert r.stats.exhausted == bool(bres.stats.exhausted[i])
    assert r.stats.K_final == int(bres.stats.K_final[i])
    assert r.stats.growths == int(bres.stats.growths[i])


# ------------------------------------------------------- 10k parity (slow) --

@pytest.mark.slow
@pytest.mark.parametrize("eps", [0.5, 0.8])
@pytest.mark.parametrize("k", [5, 10])
def test_batch_pss_matches_per_query_10k(big_graph, eps, k):
    graph, x = big_graph
    qs = _queries(x, 6, unit=True)
    bres = batch_pss(graph, qs, k, eps, ef=10)
    for i in range(qs.shape[0]):
        _assert_lane_matches(pss(graph, qs[i], k, eps, ef=10), bres, i)


@pytest.mark.slow
@pytest.mark.parametrize("eps", [0.5, 0.8])
def test_batch_pgs_matches_per_query_10k(big_graph, eps):
    graph, x = big_graph
    qs = _queries(x, 6, unit=True)
    bres, _, K = batch_pgs(graph, qs, 5, eps, ef=10)
    for i in range(qs.shape[0]):
        r, _, K_i = pgs(graph, qs[i], 5, eps, ef=10)
        np.testing.assert_array_equal(np.asarray(r.ids), bres.ids[i])
        np.testing.assert_array_equal(np.asarray(r.scores), bres.scores[i])
        assert K_i == int(K[i])


@pytest.mark.slow
@pytest.mark.parametrize("eps", [0.5, 0.8])
def test_batch_pds_matches_per_query_10k(big_graph, eps):
    graph, x = big_graph
    qs = _queries(x, 6, unit=True)
    # max_K bounds the Theorem-1 blow-up at high diversification (the paper's
    # N/A cells) identically in both drivers, exercising the exhausted path
    bres = batch_pds(graph, qs, 5, eps, ef=10, max_K=2000)
    for i in range(qs.shape[0]):
        r = pds(graph, qs[i], 5, eps, ef=10, max_K=2000)
        np.testing.assert_array_equal(np.asarray(r.ids), bres.ids[i])
        np.testing.assert_array_equal(np.asarray(r.scores), bres.scores[i])
        assert r.stats.certified == bool(bres.stats.certified[i])
        assert r.stats.exhausted == bool(bres.stats.exhausted[i])
        assert r.stats.K_final == int(bres.stats.K_final[i])


# ------------------------------------------------- lane recycling (slow) ----

def _serve_continuously(graph, qs, ks, epss, num_lanes, ef=10, max_k=10):
    """Drive the engine directly: admit whenever a lane frees (so later
    queries land on recycled lanes), return per-query results."""
    eng = ProgressiveEngine(graph, num_lanes=num_lanes, max_k=max_k)
    pending = list(range(len(qs)))
    inflight, results = {}, {}
    while pending or inflight:
        for lane in eng.free_lanes():
            if not pending:
                break
            qi = pending.pop(0)
            eng.admit(int(lane), qs[qi], k=int(ks[qi]), eps=float(epss[qi]),
                      ef=ef)
            inflight[int(lane)] = qi
        for lane in eng.step():
            results[inflight.pop(lane)] = eng.result(lane)
    return [results[i] for i in range(len(qs))], eng


@pytest.mark.slow
@pytest.mark.parametrize("eps", [0.5, 0.8])
@pytest.mark.parametrize("k", [5, 10])
def test_lane_recycle_parity_10k(big_graph, eps, k):
    """A certified lane re-admitted with a new query must be bit-identical
    to a fresh solo driver for that query — 2 lanes serving 4 queries means
    every later query runs on a recycled slot."""
    graph, x = big_graph
    qs = _queries(x, 4, unit=True)
    results, eng = _serve_continuously(graph, qs, np.full(4, k),
                                       np.full(4, eps), num_lanes=2)
    assert eng.driver.B == 2  # queries 2..3 necessarily recycled a lane
    for i, r in enumerate(results):
        solo = pss(graph, qs[i], k, eps, ef=10)
        np.testing.assert_array_equal(np.asarray(solo.ids), r.ids)
        np.testing.assert_array_equal(np.asarray(solo.scores), r.scores)
        assert solo.stats.certified == r.stats.certified
        assert solo.stats.exhausted == r.stats.exhausted
        assert solo.stats.K_final == r.stats.K_final
        assert solo.stats.growths == r.stats.growths
        assert solo.stats.search_calls == r.stats.search_calls
        assert solo.stats.div_calls == r.stats.div_calls


# ------------------------------------------------ small-graph parity (fast) --

@pytest.fixture(scope="module")
def small_graph_l2():
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(12, 24)) * 2.0
    x = (centers[rng.integers(0, 12, 600)]
         + rng.normal(size=(600, 24)) * 0.3).astype(np.float32)
    return build_knn_graph(x, metric="l2", M=8), x


def test_batch_pss_small_parity(small_graph_l2):
    graph, x = small_graph_l2
    qs = _queries(x, 6)
    bres = batch_pss(graph, qs, 5, 0.0, ef=10)
    for i in range(qs.shape[0]):
        _assert_lane_matches(pss(graph, qs[i], 5, 0.0, ef=10), bres, i)


def test_batch_pds_small_parity(small_graph_l2):
    graph, x = small_graph_l2
    qs = _queries(x, 5)
    bres = batch_pds(graph, qs, 5, 0.0, ef=10)
    for i in range(qs.shape[0]):
        r = pds(graph, qs[i], 5, 0.0, ef=10)
        np.testing.assert_array_equal(np.asarray(r.ids), bres.ids[i])
        np.testing.assert_array_equal(np.asarray(r.scores), bres.scores[i])
        assert r.stats.certified == bool(bres.stats.certified[i])
        assert r.stats.K_final == int(bres.stats.K_final[i])


def test_lane_recycle_mixed_k_eps_parity(small_graph_l2):
    """Continuous serving over 2 lanes with per-request (k, eps): every
    recycled lane must reproduce a fresh solo pss driver bit-for-bit."""
    graph, x = small_graph_l2
    qs = _queries(x, 6, seed=7)
    ks = np.array([5, 3, 4, 5, 3, 4])
    epss = np.array([0.0, -0.5, 0.0, -0.5, 0.0, -0.5])
    results, _ = _serve_continuously(graph, qs, ks, epss, num_lanes=2,
                                     max_k=8)
    for i, r in enumerate(results):
        solo = pss(graph, qs[i], int(ks[i]), float(epss[i]), ef=10)
        np.testing.assert_array_equal(np.asarray(solo.ids), r.ids)
        np.testing.assert_array_equal(np.asarray(solo.scores), r.scores)
        assert solo.stats.certified == r.stats.certified
        assert solo.stats.K_final == r.stats.K_final
        assert solo.stats.search_calls == r.stats.search_calls


def test_signature_budget_cap(small_graph_l2):
    graph, x = small_graph_l2
    qs = _queries(x, 2)
    driver = BatchProgressiveDriver(graph, qs, ef=10, k=5, capacity0=64,
                                    max_signatures=3)
    # "init", "search" and "stable_count" fill the budget
    driver.ensure_stable(np.full(2, 40))
    with pytest.raises(SignatureBudgetExceeded):
        driver._grow_lanes(np.array([200, 200]), np.ones(2, bool))


def test_batch_pss_certificates_fire(small_graph_l2):
    graph, x = small_graph_l2
    qs = _queries(x, 4)
    bres = batch_pss(graph, qs, 3, -3.0, ef=10)
    assert bres.stats.certified.all()
    assert (bres.ids >= 0).all()


# --------------------------------------------------------- growth coverage --

def test_bucketed_growth_exact_rebuild(small_graph_l2):
    """Lanes growing to different targets are rebuilt per power-of-two
    bucket; each lane's queue must equal a solo driver grown the same way."""
    graph, x = small_graph_l2
    qs = _queries(x, 3)
    driver = BatchProgressiveDriver(graph, qs, ef=10, k=5, capacity0=64)
    driver.ensure_stable(np.full(3, 40))
    driver._grow_lanes(np.array([100, 300, 700]), np.ones(3, bool))
    assert driver.caps.tolist() == [128, 512, 1024]
    assert (driver.stats.growths == 1).all()
    for i, tgt in enumerate([100, 300, 700]):
        solo = ProgressiveDriver(graph, qs[i], 10, 5, capacity0=64)
        solo.ensure_stable(40)
        solo._grow_to(tgt)
        assert solo.capacity == driver.caps[i]
        np.testing.assert_array_equal(
            np.asarray(driver.state.queue.ids[i][:solo.capacity]),
            np.asarray(solo.state.queue.ids))
        np.testing.assert_array_equal(
            np.asarray(driver.state.queue.scores[i][:solo.capacity]),
            np.asarray(solo.state.queue.scores))
        np.testing.assert_array_equal(
            np.asarray(driver.state.queue.stable[i][:solo.capacity]),
            np.asarray(solo.state.queue.stable))


def test_growth_path_parity(small_graph_l2):
    """A small initial capacity forces at least one rebuild inside the
    engine loop; results must still match solo drivers started the same."""
    graph, x = small_graph_l2
    qs = _queries(x, 4, seed=11)
    bdriver = BatchProgressiveDriver(graph, qs, ef=10, k=5, capacity0=32)
    bres, bdriver, K = batch_pgs(graph, qs, 5, 0.0, ef=10, driver=bdriver)
    assert (bdriver.stats.growths >= 1).all()
    for i in range(qs.shape[0]):
        solo = ProgressiveDriver(graph, qs[i], 10, 5, capacity0=32)
        r, solo, K_i = pgs(graph, qs[i], 5, 0.0, ef=10, driver=solo)
        np.testing.assert_array_equal(np.asarray(r.ids), bres.ids[i])
        np.testing.assert_array_equal(np.asarray(r.scores), bres.scores[i])
        assert solo.stats.growths == int(bdriver.stats.growths[i])
        assert K_i == int(K[i])
