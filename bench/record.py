"""What one run holds for the metric readers (``bench/end_to_end``,
``bench/layer_metrics``)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class RunRecord:
    loop: str                   # "backlog" (closed) or "open" (Poisson)
    num_lanes: int
    setup_s: float              # build, and the warm-up's program obtains
    t_start: float              # the window, on the scheduler's clock
    t_end: float
    sent: list                  # bench.loops.Sent of the window
    t_closed: float = 0.0       # when the last answer came, or grace ended
    recall: float | None = None  # mean recall@k of the checked sample
    step_spans: list = dataclasses.field(default_factory=list)
    occupancy: list = dataclasses.field(default_factory=list)
    trace: object = None        # bench.trace.TraceSummary (traced run)

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    @property
    def completed(self) -> list:
        """Requests answered inside the window."""
        return [s for s in self.sent
                if s.done and s.req.t_done <= self.t_end]

    def latencies(self) -> list:
        """Seconds from each request's due instant to its answer; one that
        never came counts until ``t_closed``."""
        return [s.latency if s.done else self.t_closed - s.t_due
                for s in self.sent]

    def answered_stats(self) -> list:
        """``SearchStats`` of the requests answered inside the window."""
        return [s.req.result.stats for s in self.completed]
