"""The program's own spans in a traced run, beside the device's busy time.

The serving path puts spans named ``<layer>.<what>`` into the profiler's
trace (``repro.obs``): ``sched.*`` in the scheduler, ``engine.*`` for each
step, search burst, lane growth and host sync, ``diversify.*`` and
``verify.*`` for the rounds. They sit on the host plane, on the device
planes' clock, with their metadata as event stats (``capacity`` on
``engine.search``, ``site`` on ``engine.sync``, ``dispatches`` on
``sched.pump``: the engine's ``SignatureLog`` count made in that pump).
Each run of a jitted program is an event of the device plane's ``XLA
Modules`` line, named after the function (``jit__batched_search_loop(<hash>)``
for the beam search); the op events carry no op metadata, so device time
is read per module.

``of_run(run, reader_file)`` reduces the trace that a traced run left in
``<benchmark dir>/.cache/trace`` (``bench/run.py``), once per run, and
checks that its window is the run's own. It returns None where there is
nothing to read: an untraced run, or a program that has no such spans.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from bench.trace import (DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, _clip,
                         _union, find_xplane)
from bench.trace import PROGRAM_PREFIXES as PREFIXES

MODULES_LINE = "XLA Modules"
#: the XLA module of the engine's beam search (``core.batch_progressive``)
BEAM_SEARCH = "jit__batched_search_loop"


@dataclasses.dataclass
class Span:
    name: str
    start: int                  # ns, on the trace's clock
    end: int
    meta: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclasses.dataclass
class ProgramTrace:
    window: tuple               # (start, end) ns of ``bench.window``
    spans: list                 # program spans that start in the window
    busy: list                  # per chip: disjoint op intervals, clipped
    module_s: dict              # XLA module -> device seconds in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def self_s(self, parent: str, child: str) -> list:
        """Seconds of each ``parent`` span less its ``child`` spans."""
        kids = self.named(child)
        return [p.seconds - sum(c.seconds for c in kids
                                if p.start <= c.start and c.end <= p.end)
                for p in self.named(parent)]

    def host_bound_s(self) -> float:
        """Seconds of the window, mean over chips, in which the device ran
        no op and the host was in no ``engine.sync`` span."""
        lo, hi = self.window
        syncs = _union(_clip([(s.start, s.end)
                              for s in self.named("engine.sync")], lo, hi))
        out = 0
        for merged in self.busy:
            idle = _gaps(merged, lo, hi)
            out += sum(e - s for s, e in idle) - _overlap(idle, syncs)
        return out / 1e9 / len(self.busy)


def _gaps(merged, lo, hi):
    """The complement of disjoint sorted intervals within [lo, hi)."""
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _overlap(a, b) -> int:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = out = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0, e - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce_program(profile) -> ProgramTrace | None:
    """``profile`` is a ``jax.profiler.ProfileData``; None where it holds
    no window, no device plane or no program span."""
    window, spans, devices, modules = None, [], [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events)
            devices.append(ops)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(PREFIXES):
                    spans.append(Span(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    if window is None or not devices or not spans:
        return None
    lo, hi = window
    busy = [_union(_clip(ops, lo, hi)) for ops in devices]
    module_s = {}
    for n, s, e in modules:
        if e > lo and s < hi:
            key = n.split("(")[0]
            module_s[key] = module_s.get(key, 0.0) + (min(e, hi)
                                                      - max(s, lo)) / 1e9
    spans = sorted((s for s in spans if lo <= s.start < hi),
                   key=lambda s: s.start)
    return ProgramTrace(window=window, spans=spans, busy=busy,
                        module_s=module_s)


def trace_dir(reader_file) -> Path:
    """Where ``bench/run.py`` leaves a traced run's profile, beside the
    readers of the benchmark that ``reader_file`` belongs to."""
    return Path(reader_file).resolve().parents[1] / ".cache" / "trace"


def of_run(run, reader_file) -> ProgramTrace | None:
    """The program's spans of ``run``'s traced window, reduced once per
    run (kept on the run as ``program_trace``)."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_trace"):
        run.program_trace = _load(trace_dir(reader_file), run.trace.window_s)
    return run.program_trace


def _load(directory: Path, window_s: float) -> ProgramTrace | None:
    from jax.profiler import ProfileData
    try:
        profile = ProfileData.from_file(find_xplane(str(directory)))
    except FileNotFoundError:
        return None
    out = reduce_program(profile)
    if out is None or abs(out.window_s - window_s) > 1e-9:
        return None     # no program spans, or another run's trace
    return out
