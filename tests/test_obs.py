"""The serving path's spans and its dispatch counter.

Spans: a tiny scheduler served under ``jax.profiler`` leaves ``sched.pump``
around ``engine.step`` around ``engine.search`` around ``engine.sync`` in
the trace, with each request's id on the ``sched.refill`` that admitted it
and on the ``sched.harvest`` that answered it.

Counter: every call of one of the engine's jitted programs is noted once
in its ``SignatureLog``, so a pump's difference of ``total`` is the number
of programs it dispatched.
"""
import collections
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import batch_progressive as bp
from repro.core import lane_state
from repro.index.flat import build_knn_graph
from repro.serve.scheduler import LaneScheduler

PREFIXES = ("sched.", "engine.", "diversify.", "verify.")


@pytest.fixture(scope="module")
def tiny():
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(12, 16)) * 2.0
    x = (centers[rng.integers(0, 12, 600)]
         + rng.normal(size=(600, 16)) * 0.3).astype(np.float32)
    qs = (x[rng.integers(0, 600, 12)]
          + rng.normal(size=(12, 16)).astype(np.float32) * 0.05)
    return build_knn_graph(x, metric="l2", M=8), qs


def _scheduler(graph):
    return LaneScheduler(graph, num_lanes=4, max_k=8, default_ef=16,
                         capacity0=64, prewarm_capacity=128)


def _spans(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith(PREFIXES)]
    return out


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_spans_nest_and_carry_request_ids(tiny, tmp_path):
    graph, qs = tiny
    sched = _scheduler(graph)
    sched.run(qs[:4], 5, -1.0)                  # compile outside the trace
    steps0 = sched.steps
    jax.profiler.start_trace(str(tmp_path))
    reqs = [sched.submit(q, 5, -1.0) for q in qs]
    while sched.pending or sched.inflight:
        sched.pump()
    jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    by = collections.defaultdict(list)
    for s in spans:
        by[s[0]].append(s)
    for child, parent in (("engine.step", "sched.pump"),
                          ("engine.search", "engine.step"),
                          ("engine.sync", "engine.step")):
        assert by[child] and all(_inside(c, by[parent]) for c in by[child])
    syncs_in_search = [s for s in by["engine.sync"]
                       if _inside(s, by["engine.search"])]
    assert {s[3]["site"] for s in syncs_in_search} >= {"steps",
                                                       "stable_count"}
    assert len(by["engine.step"]) == sched.steps - steps0
    assert all(s[3]["capacity"] >= 64 for s in by["engine.search"])

    def rids(name):
        return [int(r) for s in by[name]
                for r in str(s[3].get("rids", "")).split()]
    admitted, answered = rids("sched.refill"), rids("sched.harvest")
    for req in reqs:
        assert req.result is not None
        assert admitted.count(req.rid) == 1, req.rid
        assert answered.count(req.rid) == 1, req.rid


def _count_calls(monkeypatch):
    """Wrap every jitted program the engine calls; returns the counter."""
    calls = collections.Counter()

    def wrap(module, name):
        inner = getattr(module, name)

        def counted(*a, **kw):
            calls[name] += 1
            return inner(*a, **kw)
        monkeypatch.setattr(module, name, counted)

    for name in ("_batched_search_loop", "_rebuild_lanes",
                 "_batched_stable_count", "_batched_adjacency",
                 "_batched_div_astar", "_batched_theorem1", "_mask_prefix",
                 "_set_row"):
        wrap(bp, name)
    for name in ("_recycle", "_pad_lanes", "init_lanes"):
        wrap(lane_state, name)
    wrap(bp.kops, "fused_round_batch")
    return calls


@pytest.mark.parametrize("method", ["pss", "pds"])
def test_each_dispatch_is_noted_once(tiny, monkeypatch, method):
    graph, qs = tiny
    sched = _scheduler(graph)
    calls = _count_calls(monkeypatch)
    log = sched.backend.signature_log
    for q in qs:
        sched.submit(q, 5, -1.0, method=method)
    pumps = grown = 0
    while sched.pending or sched.inflight:
        before, n0 = dict(calls), log.total
        sched.pump()
        made = sum(calls.values()) - sum(before.values())
        assert log.total - n0 == made, (pumps, calls, before)
        grown += calls["_rebuild_lanes"] > before.get("_rebuild_lanes", 0)
        pumps += 1
    # the run met growth, and every program a step can call
    assert grown and pumps > 2
    want = {"_batched_search_loop", "_batched_stable_count", "_recycle",
            "_set_row", "_rebuild_lanes", "_pad_lanes"}
    want |= ({"fused_round_batch", "_batched_div_astar", "_mask_prefix",
              "_batched_adjacency"} if method == "pss" else
             {"_batched_theorem1", "_batched_div_astar"})
    assert want <= set(calls), calls


def test_prewarm_covers_the_step_path_dispatches(tiny):
    """The capacity ladder's prewarm compiles every program a step's
    search, growth and recycle dispatch, so serving within the ladder
    notes none of them as unplanned."""
    graph, qs = tiny
    sched = LaneScheduler(graph, num_lanes=4, max_k=8, default_ef=16,
                          capacity0=64, prewarm_capacity=1024)
    log = sched.backend.signature_log
    log.freeze()
    sched.run(qs, 5, -1.0)
    assert any(sig[0] == "pad" for sig in log.counts)    # lanes grew
    step_path = {"init", "search", "stable_count", "pad", "rebuild",
                 "recycle", "set_query"}
    assert [s for s in log.unplanned if s[0] in step_path] == []
