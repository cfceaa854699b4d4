"""div-A* exactness: python oracle vs brute force vs JAX implementation."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.div_astar import div_astar
from repro.core.div_astar_ref import brute_force_diverse, div_astar_ref


@st.composite
def instances(draw):
    n = draw(st.integers(4, 12))
    k = draw(st.integers(2, min(5, n)))
    seed = draw(st.integers(0, 2**31 - 1))
    dens = draw(st.floats(0.05, 0.7))
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=n) * 3
    adj = np.triu(rng.random((n, n)) < dens, 1)
    adj = adj | adj.T
    return scores, adj, k


@given(instances())
@settings(max_examples=60, deadline=None)
def test_ref_matches_brute_force_all_sizes(inst):
    scores, adj, k = inst
    sets, sc, complete = div_astar_ref(scores, adj, k)
    assert complete
    for m in range(1, k + 1):
        bset, bsc = brute_force_diverse(scores, adj, m)
        if bset is None:
            assert sets[m - 1] is None
        else:
            assert abs(sc[m - 1] - bsc) < 1e-9
            # returned set is valid + achieves the score
            s = sets[m - 1]
            assert len(s) == m
            for a in s:
                for b in s:
                    if a != b:
                        assert not adj[a, b]


@given(instances())
@settings(max_examples=40, deadline=None)
def test_jax_matches_ref(inst):
    scores, adj, k = inst
    _, sc, _ = div_astar_ref(scores, adj, k)
    res = div_astar(jnp.asarray(scores, jnp.float32), jnp.asarray(adj), k)
    assert bool(res.complete)
    for m in range(1, k + 1):
        got = float(res.best_scores[m - 1])
        want = sc[m - 1]
        if np.isfinite(want):
            assert abs(got - want) < 1e-3
        else:
            assert not np.isfinite(got)


def test_padding_with_neg_inf():
    scores = np.array([5.0, 4.0, 3.0, -np.inf, -np.inf])
    adj = np.zeros((5, 5), bool)
    adj[0, 1] = adj[1, 0] = True
    res = div_astar(jnp.asarray(scores, jnp.float32), jnp.asarray(adj), 2)
    assert abs(float(res.best_scores[1]) - 8.0) < 1e-5  # {0, 2}
    sel = sorted(np.asarray(res.best_sets[1]).tolist())
    assert sel == [0, 2]


def test_budget_reports_incomplete():
    rng = np.random.default_rng(0)
    n = 40
    scores = rng.normal(size=n)
    adj = np.triu(rng.random((n, n)) < 0.4, 1)
    adj = adj | adj.T
    res = div_astar(jnp.asarray(scores, jnp.float32), jnp.asarray(adj), 8,
                    max_expansions=5)
    assert not bool(res.complete)


def test_hopeless_tail_costs_no_budget():
    """A long tail of low-scored candidates behind a dense clique of near
    duplicates: the first sibling that fails the bound ends its level, so
    the search completes on a small budget and matches the oracle."""
    rng = np.random.default_rng(3)
    n, k = 4000, 8
    scores = np.concatenate([10.0 + rng.random(40), rng.random(n - 40)])
    adj = np.zeros((n, n), bool)
    adj[:40, :40] = rng.random((40, 40)) < 0.7
    adj = np.triu(adj, 1)
    adj = adj | adj.T
    res = div_astar(jnp.asarray(scores, jnp.float32), jnp.asarray(adj), k,
                    max_expansions=20_000)
    assert bool(res.complete)
    _, ref_scores, ref_complete = div_astar_ref(scores, adj, k)
    assert ref_complete
    np.testing.assert_allclose(np.asarray(res.best_scores), ref_scores,
                               rtol=1e-5)
