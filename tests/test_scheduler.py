"""Continuous-batching lane scheduler: admission, recycling parity,
backpressure, latency stats, and the pre-warmed compile ladder."""
import numpy as np
import pytest

from repro.core import batch_progressive as bp
from repro.core import lane_state
from repro.core.pds import pds
from repro.core.pss import pss
from repro.index.flat import build_knn_graph
from repro.serve.scheduler import (LaneScheduler, SchedulerSaturated,
                                   jain_fairness)


def jit_cache_sizes() -> dict[str, int]:
    """Tracing-cache sizes of the engine's jitted device functions: a
    serving pass that recompiles shows up as a growing entry."""
    fns = dict(search=bp._batched_search_loop, rebuild=bp._rebuild_lanes,
               prefix=bp._mask_prefix, adjacency=bp._batched_adjacency,
               div_astar=bp._batched_div_astar, theorem1=bp._batched_theorem1,
               stable_count=bp._batched_stable_count, set_row=bp._set_row,
               recycle=lane_state._recycle, pad=lane_state._pad_lanes,
               fused_round=bp.kops._ref_fused_round_batch)
    return {name: int(f._cache_size()) for name, f in fns.items()}


@pytest.fixture(scope="module")
def graph_and_queries():
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(12, 24)) * 2.0
    x = (centers[rng.integers(0, 12, 600)]
         + rng.normal(size=(600, 24)) * 0.3).astype(np.float32)
    graph = build_knn_graph(x, metric="l2", M=8)
    qs = (x[rng.integers(0, 600, 10)]
          + rng.normal(size=(10, 24)).astype(np.float32) * 0.05)
    return graph, qs.astype(np.float32)


MIX_KS = [5, 3, 5, 3, 5, 3, 5, 3, 5, 3]
MIX_EPS = [0.0, -0.5, 0.0, -0.5, 0.0, -0.5, 0.0, -0.5, 0.0, -0.5]


def test_scheduler_matches_solo_pss(graph_and_queries):
    """More requests than lanes with mixed per-request (k, eps): every
    result — including those served on recycled lanes — must equal a fresh
    per-query PSS driver bit-for-bit."""
    graph, qs = graph_and_queries
    sched = LaneScheduler(graph, num_lanes=3, max_k=8, default_ef=10,
                          prewarm=False)
    results = sched.run(qs, MIX_KS, MIX_EPS)
    assert len(results) == len(qs)
    for i, r in enumerate(results):
        solo = pss(graph, qs[i], MIX_KS[i], MIX_EPS[i], ef=10)
        np.testing.assert_array_equal(np.asarray(solo.ids), r.ids)
        np.testing.assert_array_equal(np.asarray(solo.scores), r.scores)
        assert solo.stats.certified == r.stats.certified
        assert solo.stats.K_final == r.stats.K_final


def test_lockstep_and_continuous_agree(graph_and_queries):
    """Admission policy changes latency, never results."""
    graph, qs = graph_and_queries
    a = LaneScheduler(graph, num_lanes=3, max_k=8, default_ef=10,
                      admission="continuous", prewarm=False)
    b = LaneScheduler(graph, num_lanes=3, max_k=8, default_ef=10,
                      admission="lockstep", prewarm=False)
    ra = a.run(qs, MIX_KS, MIX_EPS)
    rb = b.run(qs, MIX_KS, MIX_EPS)
    for x, y in zip(ra, rb):
        np.testing.assert_array_equal(x.ids, y.ids)
        np.testing.assert_array_equal(x.scores, y.scores)


def test_explicit_backend_matches_graph_construction(graph_and_queries):
    """LaneScheduler(backend=ProgressiveEngine(...)) is the same scheduler
    as the graph-convenience constructor — bit-identical results."""
    from repro.core.batch_progressive import ProgressiveEngine

    graph, qs = graph_and_queries
    eng = ProgressiveEngine(graph, num_lanes=3, max_k=8, default_ef=10)
    a = LaneScheduler(backend=eng, prewarm=False)
    assert a.backend is eng and a.num_lanes == 3
    b = LaneScheduler(graph, num_lanes=3, max_k=8, default_ef=10,
                      prewarm=False)
    ra = a.run(qs, MIX_KS, MIX_EPS)
    rb = b.run(qs, MIX_KS, MIX_EPS)
    for x, y in zip(ra, rb):
        np.testing.assert_array_equal(x.ids, y.ids)
        np.testing.assert_array_equal(x.scores, y.scores)


def test_scheduler_runs_pds_requests(graph_and_queries):
    graph, qs = graph_and_queries
    sched = LaneScheduler(graph, num_lanes=2, max_k=8, default_ef=10,
                          prewarm=False)
    reqs = [sched.submit(qs[i], 4, 0.0, ef=10, method="pds")
            for i in range(4)]
    sched.drain()
    for i, req in enumerate(reqs):
        solo = pds(graph, qs[i], 4, 0.0, ef=10)
        np.testing.assert_array_equal(np.asarray(solo.ids), req.result.ids)
        assert solo.stats.certified == req.result.stats.certified


def test_backpressure(graph_and_queries):
    graph, qs = graph_and_queries
    sched = LaneScheduler(graph, num_lanes=2, max_pending=2, prewarm=False)
    sched.submit(qs[0], 3, 0.0)
    sched.submit(qs[1], 3, 0.0)
    with pytest.raises(SchedulerSaturated):
        sched.submit(qs[2], 3, 0.0)
    assert sched.try_submit(qs[2], 3, 0.0) is None
    sched.pump()                       # admits into lanes, queue drains
    assert sched.try_submit(qs[2], 3, 0.0) is not None
    sched.drain()
    assert len(sched.completed) == 3


def test_latency_stats_and_fairness(graph_and_queries):
    graph, qs = graph_and_queries
    sched = LaneScheduler(graph, num_lanes=3, max_k=8, default_ef=10,
                          prewarm=False)
    sched.run(qs, 5, 0.0)
    st = sched.latency_stats()
    assert st["completed"] == len(qs)
    assert st["pending"] == 0 and st["inflight"] == 0
    assert st["p99_latency"] >= st["p50_latency"] >= 0
    assert st["p99_wait"] >= 0 and st["p99_service"] > 0
    assert 0 < st["fairness"] <= 1
    assert st["throughput"] > 0
    for r in sched.completed:
        assert r.t_submit <= r.t_admit <= r.t_done
    assert jain_fairness([1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert jain_fairness([]) == 1.0


def test_prewarm_ladder_no_unplanned_recompiles(graph_and_queries):
    """The scheduler pre-warms the capacity ladder at start; after one
    serving pass populated the diversify-stage signatures, a second pass
    over the same request shapes must not trace anything new — neither in
    the engine's signature log nor in the jitted functions' caches."""
    graph, qs = graph_and_queries
    sched = LaneScheduler(graph, num_lanes=3, max_k=8, default_ef=10,
                          prewarm=True, prewarm_capacity=1024)
    sched.run(qs, MIX_KS, MIX_EPS)
    sched.engine.signatures.freeze()
    before = jit_cache_sizes()
    sched.run(qs.copy(), list(MIX_KS), list(MIX_EPS))  # repeat traffic
    assert sched.engine.signatures.unplanned == []
    assert jit_cache_sizes() == before
    assert sched.latency_stats()["unplanned_signatures"] == 0
