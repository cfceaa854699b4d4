"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath,
``correct`` comes out false, once for each fault a one-chip search cell can
have, and each through the number that is there to catch it."""
import numpy as np
import pytest
from bench_tiny import TINY_SCHEDULER

from bench import spec
from bench.reference import sims64
from bench.run import run_cell

SEED = 3000000019


def run(root, cell, break_path=None, grace=30.0):
    return run_cell(spec.load_cell(cell, root), SEED, 1.0, False,
                    impl="ref", root=root, workers=0, grace=grace,
                    break_path=break_path, scheduler_kw=TINY_SCHEDULER)


def _alter_results(db, change):
    """``change(result, q, x, metric)`` applied to every result where the
    engine produces it."""
    engine = db.engine
    result = engine.result

    def altered(lane):
        r = result(lane)
        change(r, np.asarray(engine.driver.qs[lane]),
               np.asarray(engine.graph.vectors), engine.graph.metric)
        return r
    engine.result = altered


def alter_answer(db):
    """An answer altered where it is produced: the engine's result for a
    lane names a neighbouring row in place of its first id."""
    engine = db.engine
    result = engine.result

    def altered(lane):
        r = result(lane)
        r.ids[0] = (r.ids[0] + 1) % engine.graph.size
        return r
    engine.result = altered


def worse_neighbour(db):
    """A search that returns a real but worse neighbour: each answer's best
    id is replaced by the row least similar to the query, reported with
    its true score."""
    def change(r, q, x, metric):
        s = sims64(q, x, metric)
        s[r.ids[r.ids >= 0]] = np.inf
        far = int(np.argmin(s))
        best = int(np.argmax(r.scores))
        r.ids[best], r.scores[best] = far, np.float32(s[far])
    _alter_results(db, change)


def near_duplicate(db):
    """An answer that is not eps-diverse: its last id is replaced by the
    row most similar to its first, reported with its true score."""
    def change(r, q, x, metric):
        s = sims64(x[r.ids[0]], x, metric)
        s[r.ids[r.ids >= 0]] = -np.inf
        twin = int(np.argmax(s))
        r.ids[-1] = twin
        r.scores[-1] = np.float32(sims64(q, x[twin:twin + 1], metric)[0])
    _alter_results(db, change)


def truncated(db):
    """An answer one id short: its last id is dropped."""
    def change(r, q, x, metric):
        r.ids[-1], r.scores[-1] = -1, 0.0
    _alter_results(db, change)


def swap_answers(db):
    """Answers exchanged at harvest: each request gets the answer of the
    request harvested before it."""
    harvest = db.backend.harvest
    last = []

    def swapped():
        out = []
        for lane, r in harvest():
            out.append((lane, last[0] if last else r))
            last[:] = [r]
        return out
    db.backend.harvest = swapped


def false_certificate(db):
    """Every answer claims a Theorem-2 certificate, issued over a frontier
    of its first two served ids alone, where the engine produces it."""
    engine = db.engine
    harvest = engine.harvest

    def claimed():
        out = harvest()
        for lane, r in out:
            r.stats.certified = True
            engine.last_candidates[lane] = (np.asarray(r.ids)[:2],
                                            np.asarray(r.scores)[:2], 0.0)
        return out
    engine.harvest = claimed


def stuck_step(db):
    """A step that returns its state unchanged: no lane ever finishes."""
    db.backend.step = lambda: []


def half_harvest(db):
    """Half of the finished lanes left out of the harvest: their requests
    are never answered."""
    harvest = db.backend.harvest

    def half():
        return harvest()[::2]
    db.backend.harvest = half


def test_sound_run_is_correct(tiny_root):
    out = run(tiny_root, "tiny.tb")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"qps", "recall_at_k", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_sound_open_loop_run_is_correct(tiny_root):
    out = run(tiny_root, "tiny.to")
    assert out["correct"], out["checks"]
    assert {"p50_ms", "p95_ms", "recall_at_k", "setup_s"} <= set(
        out["metrics"])
    assert out["metrics"]["p95_ms"]["value"] >= \
        out["metrics"]["p50_ms"]["value"] > 0


@pytest.mark.parametrize("fault, check", [
    (alter_answer, "score_err"),
    (swap_answers, "score_err"),
    (worse_neighbour, "suboptimal"),
    (near_duplicate, "div_excess"),
    (truncated, "short"),
    (false_certificate, "recheck_fail"),
    (stuck_step, "unanswered"),
    (half_harvest, "unanswered"),
])
def test_broken_path_is_not_correct(tiny_root, fault, check):
    # a fault that leaves requests unanswered is waited for briefly
    grace = 2.0 if check == "unanswered" else 30.0
    out = run(tiny_root, "tiny.tb", break_path=fault, grace=grace)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]
