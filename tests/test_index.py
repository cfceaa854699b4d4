"""Index builders: HNSW + fast KNN-graph; reachability/recall/determinism."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.beam_search import beam_search
from repro.index.flat import (_directed_reachable, build_knn_graph,
                              exact_topk)
from repro.index.hnsw import build_hnsw


def test_knng_fully_reachable(clustered_data):
    g = build_knn_graph(clustered_data, metric="l2", M=8)
    entries = np.asarray(g.entry)
    assert entries.shape == (600,) and np.unique(entries).size == 600
    for e in entries:
        reached = _directed_reachable(np.asarray(g.neighbors), int(e))
        assert reached.all()


def test_knng_deterministic(clustered_data):
    g1 = build_knn_graph(clustered_data[:300], metric="cos", M=6)
    g2 = build_knn_graph(clustered_data[:300], metric="cos", M=6)
    np.testing.assert_array_equal(np.asarray(g1.neighbors),
                                  np.asarray(g2.neighbors))


def test_hnsw_recall(clustered_data):
    x = clustered_data[:400]
    g = build_hnsw(x, metric="l2", M=8, ef_construction=60)
    rng = np.random.default_rng(0)
    recs = []
    for _ in range(8):
        q = x[rng.integers(len(x))] + \
            rng.normal(size=x.shape[1]).astype(np.float32) * 0.05
        ids, _ = beam_search(g, jnp.asarray(q), k=5, L=60)
        gt, _ = exact_topk(q[None], x, 5, "l2")
        recs.append(len(set(np.asarray(ids).tolist())
                        & set(gt[0].tolist())) / 5)
    assert np.mean(recs) >= 0.95


def test_hnsw_has_upper_levels(clustered_data):
    g = build_hnsw(clustered_data[:500], metric="l2", M=8,
                   ef_construction=40)
    assert g.num_upper_levels >= 1


def test_exact_topk_tie_break():
    x = np.zeros((5, 3), np.float32)
    ids, _ = exact_topk(np.zeros((1, 3), np.float32), x, 3, "ip")
    np.testing.assert_array_equal(ids[0], [0, 1, 2])


@pytest.mark.parametrize("metric", ["l2", "ip", "cos"])
def test_exact_topk_matches_numpy(metric, clustered_data):
    x = clustered_data[:200]
    q = clustered_data[201]
    ids, scores = exact_topk(q[None], x, 10, metric)
    from repro.core.similarity import query_sim
    sims = np.asarray(query_sim(jnp.asarray(q), jnp.asarray(x), metric))
    order = np.lexsort((np.arange(len(x)), -sims))[:10]
    np.testing.assert_array_equal(ids[0], order)


def _tight_clusters(n=3000, d=64, c=24, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(c, d)) * 2.0
    x = centers[rng.integers(0, c, n)] + rng.normal(size=(n, d)) * 0.35
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def test_search_starts_at_best_entry_candidate():
    """A level-0 graph carries entry candidates; a search starts at the
    one most similar to its query (first index on ties)."""
    from repro.core.beam_search import init_state
    from repro.core.similarity import query_sim

    x = _tight_clusters()
    g = build_knn_graph(x, metric="cos", M=8)
    cands = np.asarray(g.entry)
    assert cands.shape == (1024,) and np.unique(cands).size == 1024
    for q in x[[5, 900, 2999]] + np.float32(0.01):
        sims = np.asarray(query_sim(jnp.asarray(q), jnp.asarray(x[cands]),
                                    "cos"))
        state = init_state(g, jnp.asarray(q), 1024)
        assert int(state.queue.ids[0]) == cands[int(np.argmax(sims))]


def test_entry_candidates_reach_every_cluster():
    """kNN edges rarely leave a tight cluster: from the medoid alone a beam
    search settles in the wrong one, from the best entry candidate it
    finds the query's own neighbours."""
    from repro.core.graph import make_flat_graph

    x = _tight_clusters()
    g = build_knn_graph(x, metric="cos", M=8)
    medoid_only = make_flat_graph(x, np.asarray(g.neighbors), None,
                                  int(np.asarray(g.entry)[0]), "cos")
    rng = np.random.default_rng(1)
    qs = x[rng.integers(0, len(x), 32)] + rng.normal(
        size=(32, x.shape[1])).astype(np.float32) * np.float32(0.01)
    truth, _ = exact_topk(qs, x, 10, "cos")

    def recall(graph):
        got = [np.asarray(beam_search(graph, jnp.asarray(q), 10, 20)[0])
               for q in qs]
        return np.mean([np.isin(t, r).mean() for t, r in zip(truth, got)])

    assert recall(g) >= 0.95
    assert recall(medoid_only) < recall(g)
