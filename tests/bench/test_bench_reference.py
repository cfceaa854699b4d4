"""The benchmark's float64 reference, against the program's own oracle at
a small size, and the control that has to fail the comparison."""
import numpy as np
import pytest

from bench import control, data as D, reference as R, spec
from bench.run import correct_of


@pytest.fixture(scope="module")
def corpus():
    x, metric = D.make_dataset("deep-like", 1500, 16, seed=5)
    eps = D.calibrate_eps(x, metric, D.PHI_TARGETS["medium"], seed=5)
    return x, metric, eps, list(D.query_pool(x, 6, data_seed=5))


def test_reference_agrees_with_the_programs_oracle(corpus):
    from repro.core.baselines import div_astar_oracle
    x, metric, eps, qs = corpus
    x64 = x.astype(np.float64)
    same = 0
    for q in qs:
        ids, total, frontier, scores, ok = R.host_oracle(x64, metric, q, 10,
                                                         eps)
        prog = div_astar_oracle(x, metric, q, 10, eps, X=256)
        pids = np.asarray(prog.ids)
        assert ok and scores.sum() == pytest.approx(total)
        # the program's float32 oracle may pick another set of a total
        # within float32 rounding of the optimum, never a worse one
        got = R.sims64(q, x64[pids], metric).sum()
        assert total - R.f32_total_tol(total, 10) <= got <= total + 1e-9
        same += sorted(ids.tolist()) == sorted(pids.tolist())
    assert same >= len(qs) - 1


def test_reference_brute_force_at_a_tiny_size():
    from repro.core.div_astar_ref import brute_force_diverse
    rng = np.random.default_rng(3)
    for _ in range(5):
        s = rng.normal(size=12)
        adj = rng.random((12, 12)) < 0.3
        adj = adj | adj.T
        np.fill_diagonal(adj, False)
        sets, best = R.div_astar_ref(s, adj, 4)
        want, want_score = brute_force_diverse(s, adj, 4)
        assert best[3] == pytest.approx(want_score)
        assert sets[3] == sorted(want)


def test_recheck_holds_on_an_optimal_frontier_and_fails_when_cut(corpus):
    x, metric, eps, qs = corpus
    x64 = x.astype(np.float64)
    q = qs[0]
    ids, total, frontier, _, _ = R.host_oracle(x64, metric, q, 10, eps)
    got = float(R.sims64(q, x64[ids], metric).sum())
    assert R.recheck_frontier(frontier, q, x64, metric, 10, eps, got)[0]
    # a served total that is not the frontier's optimum
    assert not R.recheck_frontier(frontier, q, x64, metric, 10, eps,
                                  got - 1e-2)[0]
    # a frontier shorter than k proves nothing
    assert not R.recheck_frontier(frontier[:5], q, x64, metric, 10, eps,
                                  got)[0]
    row = R.check_answer(dict(ids=ids, scores=R.sims64(q, x64[ids], metric),
                              certified=True, frontier=frontier),
                         q, x64, metric, 10, eps)
    assert row["recall"] == 1.0 and row["recheck_fail"] == 0
    assert row["score_err"] < 1e-12 and row["div_excess"] <= 0


def test_high_precision_split_is_three_passes():
    a = np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32)
    hi, lo = control._split(a)
    assert np.all(np.abs(a - hi - lo) <= np.abs(a) * 2.0 ** -16)
    exact = a.astype(np.float64) @ a.T.astype(np.float64)
    err_high = np.abs(control.dot_high(a, a) - exact).max()
    err_f32 = np.abs(a @ a.T - exact).max()
    assert err_f32 < err_high < 1e-2


CELLS = [w["name"] for w in spec.load_json(
    spec.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    """The control at the cell's size and limits, on a smaller sample: the
    comparison fails it on every seed."""
    cell = spec.load_cell(workload)
    cell.traffic = dict(cell.traffic, check_sample=8)
    for seed in (21, 22, 23):
        checks = control.control_readings(cell, seed)
        assert not correct_of(checks), checks


@pytest.mark.parametrize("workload", CELLS)
def test_search_fault_is_not_correct(workload):
    """A search that reads half the corpus, at the cell's size and limits,
    on the whole sample: it names real rows with their true scores, and
    ``suboptimal`` fails it on every seed."""
    cell = spec.load_cell(workload)
    for seed in (21, 22, 23):
        checks = control.control_readings(cell, seed, "half_rows")
        assert not correct_of(checks), checks
        c = checks["suboptimal"]
        assert c["value"] > c["limit"], checks
