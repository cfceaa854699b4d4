"""Load generators: a closed backlog and open-loop Poisson arrivals.

Both drive a scheduler through its public calls (``try_submit``, ``pump``,
``pending``, ``inflight``) and time on one clock, the scheduler's. Each
request is a ``Sent`` record: its stream index, the instant it was due, the
scheduler's request handle (``None`` until the scheduler took it) and when
it was handed over. An open-loop request is timed from the instant it was
due, not from when the scheduler took it, so a stall that delays the
requests behind it shows in every one of them.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Sent:
    index: int
    t_due: float
    req: object = None
    t_sent: float | None = None

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.result is not None

    @property
    def latency(self) -> float:
        """Due instant to result, in seconds (inf if none came)."""
        return self.req.t_done - self.t_due if self.done else float("inf")


def poisson_arrivals(rate: float, horizon: float, rng) -> np.ndarray:
    """Arrival offsets (seconds) of a Poisson process of ``rate`` per
    second over ``[0, horizon)``."""
    gaps = rng.exponential(1.0 / rate, int(rate * horizon * 1.5) + 64)
    t = np.cumsum(gaps)
    while t[-1] < horizon:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, len(gaps)))])
    return t[t < horizon]


def _offer(sched, sent: Sent, stream, k, eps, clock) -> bool:
    req = sched.try_submit(stream[sent.index], k, eps)
    if req is None:
        return False
    sent.req, sent.t_sent = req, clock()
    return True


def run_backlog(sched, stream, k, eps, depth: int, seconds: float,
                clock=time.monotonic, start: int = 0,
                count: int | None = None):
    """Closed backlog: keep ``depth`` requests pending and pump, for
    ``seconds``, or, where ``count`` is given, until ``count`` requests
    have been offered, however long that takes. Returns ``(sent, t_start,
    t_end)``; a request's due instant is when it was offered."""
    sent: list[Sent] = []
    i = start
    t0 = clock()
    t_end = t0 + seconds
    while (clock() < t_end) if count is None else (len(sent) < count):
        while len(sched.pending) < depth and (count is None
                                              or len(sent) < count):
            s = Sent(i, clock())
            if not _offer(sched, s, stream, k, eps, clock):
                break
            sent.append(s)
            i += 1
        sched.pump()
    return sent, t0, t_end if count is None else clock()


def run_open(sched, stream, k, eps, arrivals, seconds: float,
             clock=time.monotonic, sleep=time.sleep, start: int = 0):
    """Open loop: request ``start + j`` is due at ``t_start +
    arrivals[j]``; offered once due (and retried while the scheduler pushes
    back), whatever the state of the earlier ones. Returns ``(sent,
    t_start, t_end)`` for the requests due in the window."""
    arrivals = np.asarray(arrivals)
    arrivals = arrivals[arrivals < seconds]
    t0 = clock()
    t_end = t0 + seconds
    sent = [Sent(start + j, t0 + float(a)) for j, a in enumerate(arrivals)]
    nxt = 0          # first request not yet offered
    waiting = []     # due, offered, pushed back
    while True:
        now = clock()
        if now >= t_end:
            break
        while nxt < len(sent) and sent[nxt].t_due <= now:
            waiting.append(sent[nxt])
            nxt += 1
        while waiting and _offer(sched, waiting[0], stream, k, eps, clock):
            waiting.pop(0)
        if sched.pending or sched.inflight:
            sched.pump()
        elif nxt < len(sent):
            sleep(max(0.0, min(sent[nxt].t_due, t_end) - clock()))
        else:
            sleep(max(0.0, t_end - clock()))
    # every request due in the window is offered before the window ends
    for s in sent[nxt:]:
        waiting.append(s)
    return sent, t0, t_end, waiting


def finish(sched, stream, k, eps, waiting, sent, grace: float,
           clock=time.monotonic):
    """After the window: offer what is still waiting and pump until every
    request in ``sent`` has its answer or ``grace`` seconds have passed.
    Returns the requests that got no answer."""
    t_stop = clock() + grace
    waiting = list(waiting)
    while clock() < t_stop:
        while waiting and _offer(sched, waiting[0], stream, k, eps, clock):
            waiting.pop(0)
        if all(s.done for s in sent) and not waiting:
            break
        sched.pump()
    return [s for s in sent if not s.done]
