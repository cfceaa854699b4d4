"""Load generators: arrivals from the seed, open-loop latency from the due
instant, and the closed backlog's depth."""
import collections
import types

import numpy as np
import pytest

from bench import loops


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0)


class FakeScheduler:
    """One lane; each pump serves the request in the lane in ``service``
    seconds of the fake clock, and pump number ``stall_at`` stalls for
    ``stall`` seconds first."""

    def __init__(self, clock, service=0.01, stall_at=None, stall=0.0,
                 max_pending=1000):
        self.clock, self.service = clock, service
        self.stall_at, self.stall = stall_at, stall
        self.pending = collections.deque()
        self.inflight = {}
        self.max_pending = max_pending
        self.pumps = 0
        self.num_lanes = 1

    def try_submit(self, q, k, eps):
        if len(self.pending) >= self.max_pending:
            return None
        r = types.SimpleNamespace(q=q, result=None, t_submit=self.clock(),
                                  t_admit=None, t_done=None)
        self.pending.append(r)
        return r

    def pump(self):
        self.pumps += 1
        if self.pumps == self.stall_at:
            self.clock.t += self.stall
        if not self.inflight and self.pending:
            r = self.pending.popleft()
            r.t_admit = self.clock()
            self.inflight[0] = r
        if self.inflight:
            self.clock.t += self.service
            r = self.inflight.pop(0)
            r.result, r.t_done = "answer", self.clock()


def test_poisson_arrivals_follow_the_seed():
    a = loops.poisson_arrivals(50.0, 20.0, np.random.default_rng([7, 4]))
    b = loops.poisson_arrivals(50.0, 20.0, np.random.default_rng([7, 4]))
    c = loops.poisson_arrivals(50.0, 20.0, np.random.default_rng([8, 4]))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.max() < 20.0 and np.all(np.diff(a) > 0)
    assert len(a) == pytest.approx(1000, rel=0.1)


def open_run(stall_at=None, stall=0.0):
    clock = Clock()
    sched = FakeScheduler(clock, stall_at=stall_at, stall=stall)
    arrivals = np.arange(40) * 0.05            # 20 req/s, 2 s
    sent, t0, t1, waiting = loops.run_open(
        sched, list(range(100)), 5, 0.1, arrivals, 2.0, clock=clock,
        sleep=clock.sleep)
    left = loops.finish(sched, list(range(100)), 5, 0.1, waiting, sent,
                        10.0, clock=clock)
    return sent, left


def test_open_loop_times_from_the_due_instant():
    calm, left = open_run()
    assert not left and len(calm) == 40
    assert max(s.latency for s in calm) == pytest.approx(0.01)
    stalled, left = open_run(stall_at=5, stall=1.0)
    assert not left
    # the stall (1 s at the 5th pump, while request 4 is in its lane)
    # delays every request due before it ends, each by what it waited
    t_stall_end = stalled[4].req.t_done
    behind = [s for s in stalled[5:] if s.t_due < t_stall_end]
    assert len(behind) >= 15
    for s in behind:
        assert s.latency >= t_stall_end - s.t_due
    assert stalled[5].latency > 0.9
    # timed from the scheduler's own submit, most of the wait would not show
    assert max(s.req.t_done - s.req.t_submit for s in behind) < 0.3


def test_open_loop_keeps_offering_when_pushed_back():
    clock = Clock()
    sched = FakeScheduler(clock, service=0.2, max_pending=2)
    arrivals = np.arange(20) * 0.05
    sent, _, _, waiting = loops.run_open(sched, list(range(50)), 5, 0.1,
                                         arrivals, 1.0, clock=clock,
                                         sleep=clock.sleep)
    assert waiting                      # due, pushed back, still counted
    left = loops.finish(sched, list(range(50)), 5, 0.1, waiting, sent,
                        60.0, clock=clock)
    assert not left and all(s.done for s in sent)
    # the last request waited behind the whole queue, from its due instant
    assert sent[-1].latency > 2.0


def test_backlog_keeps_its_depth():
    clock = Clock()
    sched = FakeScheduler(clock)
    depths = []
    pump = sched.pump

    def watched():
        depths.append(len(sched.pending))
        pump()
    sched.pump = watched
    sent, t0, t1 = loops.run_backlog(sched, list(range(1000)), 5, 0.1, 3,
                                     1.0, clock=clock)
    assert t1 - t0 == 1.0
    assert min(depths) == 3
    assert len(sent) == pytest.approx(100, abs=4)    # 0.01 s each


def test_every_seed_serves_the_same_pool_in_another_order():
    from bench import data as D
    x, _ = D.make_dataset("deep-like", 500, 8, seed=0)
    pool = D.query_pool(x, 16, data_seed=0)
    a, b = D.QueryStream(pool, 1), D.QueryStream(pool, 2)
    first_a = np.stack([a[i] for i in range(16)])
    first_b = np.stack([b[i] for i in range(16)])
    key = lambda m: sorted(map(tuple, m.tolist()))       # noqa: E731
    assert key(first_a) == key(first_b) == key(pool)
    assert not np.array_equal(first_a, first_b)
    # each pass over the pool has an order of its own
    again = np.stack([a[i] for i in range(16, 32)])
    assert key(again) == key(pool) and not np.array_equal(again, first_a)
    assert np.array_equal(np.stack([D.QueryStream(pool, 1)[i]
                                    for i in range(16)]), first_a)


def test_backlog_rehearsal_offers_its_count_however_slow():
    """A warm-up pass offers a fixed number of requests: a slow pass (the
    first, which loads programs) offers as many as a fast one, so every
    pass meets the same requests."""
    for service in (0.01, 0.5):
        clock = Clock()
        sched = FakeScheduler(clock, service=service)
        sent, t0, t1 = loops.run_backlog(sched, list(range(1000)), 5, 0.1,
                                         3, 1.0, clock=clock, count=40)
        assert [s.index for s in sent] == list(range(40))
        assert t1 == clock()
    assert t1 - t0 > 1.0


def test_seeds_pick_their_queries_from_a_large_pool():
    from bench import data as D
    x, _ = D.make_dataset("deep-like", 500, 8, seed=0)
    pool = D.query_pool(x, 2000, data_seed=0)
    key = lambda s: {tuple(s[i].tolist()) for i in range(100)}   # noqa: E731
    a, b = key(D.QueryStream(pool, 1)), key(D.QueryStream(pool, 2))
    assert len(a) == len(b) == 100 and len(a & b) < 20
    assert key(D.QueryStream(pool, 1)) == a


def test_spans_keep_only_the_window():
    from bench.run import Spans
    backend = types.SimpleNamespace(step=lambda: [], harvest=lambda: [],
                                    active_count=lambda: 2)
    sched = types.SimpleNamespace(try_submit=lambda *a: None,
                                  pump=lambda: None, num_lanes=4)
    spans = Spans(types.SimpleNamespace(scheduler=sched, backend=backend))
    backend.step()
    spans.on = True
    backend.step()
    backend.step()
    spans.on = False
    backend.step()     # the backlog drains after the window
    assert len(spans.steps) == 2 and spans.occupancy == [0.5, 0.5]
