"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-operation device time and idle gaps labelled by the host's spans.

Planes whose name starts with ``/device:TPU:`` (and no other suffix) are the
chips; their ``XLA Ops`` line holds one event per operation run on the
device. The host spans are on the host plane, on the same clock: the
benchmark's own (``TraceAnnotation`` names starting with ``bench.``) and the
program's (``sched.``, ``engine.``, ``diversify.``, ``verify.``; see
``bench.program_trace``). The window is the host span ``bench.window``.
Each idle gap is labelled by the span that is innermost over most of it.

A device event's name is the HLO instruction's text. Per-op time counts
top-level events only (a ``while`` op's event spans its body's ops); a
Pallas kernel is a custom call, selected by substrings of that text
(``kernel_names``), at any depth.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: the program's spans (``repro.obs``), named ``<layer>.<what>``
PROGRAM_PREFIXES = ("sched.", "engine.", "diversify.", "verify.")
HOST_PREFIXES = (SPAN_PREFIX,) + PROGRAM_PREFIXES
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # union of op intervals, mean over chips
    chips: int
    op_s: dict                    # op name -> summed device seconds
    kernel_s: dict                # kernel name -> summed device seconds
    gaps: list                    # [(label, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Merge ``(start, end)`` pairs; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _op_key(name: str) -> str:
    """An op's name for the breakdown: the HLO instruction's name and the
    start of its result type (a device event's name is the instruction's
    whole text)."""
    head, _, rest = name.lstrip("%").partition(" = ")
    return f"{head} = {rest[:60]}" if rest else head


def _top_level(events):
    """The events not nested in an earlier one (a ``while`` op's event
    spans the ops of its body)."""
    out, end = [], float("-inf")
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if ev[1] >= end:
            out.append(ev)
            end = ev[2]
        elif ev[2] > end:      # overlaps the end: count its own part
            out.append((ev[0], end, ev[2]))
            end = ev[2]
    return out


def _label(gap, spans):
    """The span that is innermost over most of ``gap``: each instant of
    the gap is charged to the shortest host span over it (spans nest, so
    that is the innermost), and the span charged most names the gap; on a
    tie, the one that covers more of it."""
    s0, e0 = gap
    over = [(name, max(s, s0), min(e, e0), e - s) for name, s, e in spans
            if min(e, e0) > max(s, s0)]
    cuts = sorted({s0, e0} | {t for _, a, b, _ in over for t in (a, b)})
    charged, cover = {}, {}
    for name, a, b, _ in over:
        cover[name] = cover.get(name, 0) + b - a
    for a, b in zip(cuts, cuts[1:]):
        inside = [sp for sp in over if sp[1] <= a and b <= sp[2]]
        if inside:
            name = min(inside, key=lambda sp: sp[3])[0]
            charged[name] = charged.get(name, 0) + b - a
    if not charged:
        return "no span"
    return max(charged, key=lambda n: (charged[n], cover[n]))


def reduce_profile(profile, kernel_names=(), top: int = 10) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    spans, window = [], None
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices.append(ops)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(HOST_PREFIXES):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("trace has no TPU device plane")
    lo, hi = window
    busy, op_s, kernel_s, gaps = 0.0, {}, {}, []
    for ops in devices:
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        merged = _union(_clip([(s, e) for _, s, e in inside], lo, hi))
        busy += sum(e - s for s, e in merged)
        for n, s, e in _top_level(inside):
            key = _op_key(n)
            op_s[key] = op_s.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        for n, s, e in inside:
            for k in kernel_names:
                if k in n:
                    kernel_s[k] = (kernel_s.get(k, 0.0)
                                   + (min(e, hi) - max(s, lo)) / 1e9)
                    break
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(g, spans), (g[1] - g[0]) / 1e9) for g in gaps[:top]]
    ops_top = dict(sorted(op_s.items(), key=lambda kv: -kv[1]))
    return TraceSummary(window_s=(hi - lo) / 1e9,
                        busy_s=busy / 1e9 / len(devices),
                        chips=len(devices), op_s=ops_top, kernel_s=kernel_s,
                        gaps=labelled)


def reduce_dir(trace_dir: str, kernel_names=(), top: int = 10):
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          kernel_names, top)
