"""Cut a profiler trace (``.xplane.pb``) of a benchmark run down to what
the benchmark's trace reductions read, for a test fixture or for reading
again later.

    python tools/trim_trace.py <trace dir or .xplane.pb> <out.xplane.pb> [--pumps N]

Kept: the host spans of the benchmark (``bench.``) and of the program
(``sched.``, ``engine.``, ``diversify.``, ``verify.``) with their metadata;
on each TPU plane, the ``XLA Ops`` events not nested in another (a
``while`` op's event stands for its body's), the first of each op carrying
the stats of that op's first event, and the ``XLA Modules`` events. With ``--pumps N`` the window
(``bench.window``) is cut to end where its N-th ``sched.pump`` ends, and
no event that starts after that end is kept.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.program_trace import MODULES_LINE, PREFIXES  # noqa: E402
from bench.trace import (DEVICE_PLANE, OPS_LINE, SPAN_PREFIX,  # noqa: E402
                         WINDOW_SPAN, _top_level, find_xplane)

HOST_PREFIXES = (SPAN_PREFIX,) + PREFIXES


def _quote(text: str) -> str:
    out = []
    for b in text.encode("utf-8"):
        c = chr(b)
        out.append(c if 32 <= b < 127 and c not in '\\"' else f"\\{b:03o}")
    return '"' + "".join(out) + '"'


class _Plane:
    """One plane of the text proto: events by line, metadata by name."""

    def __init__(self, name: str):
        self.name, self.lines, self.events, self.stats = name, {}, {}, {}

    def _meta(self, table: dict, name: str) -> int:
        return table.setdefault(name, len(table) + 1)

    def _stats(self, stats) -> str:
        out = []
        for key, value in stats:
            kind = ("int64_value" if isinstance(value, int) else
                    "double_value" if isinstance(value, float) else
                    "str_value")
            v = _quote(value) if kind == "str_value" else repr(value)
            out.append(f"stats {{ metadata_id: "
                       f"{self._meta(self.stats, key)} {kind}: {v} }}")
        return " ".join(out)

    def add(self, line: str, name: str, start: int, end: int, stats=()):
        mid = self._meta(self.events, name)
        self.lines.setdefault(line, []).append(
            f"events {{ metadata_id: {mid} offset_ps: {round(start * 1e3)} "
            f"duration_ps: {round((end - start) * 1e3)} "
            f"{self._stats(stats)} }}")

    def text(self, pid: int) -> str:
        lines = "\n".join(
            f"lines {{ id: {i} name: {_quote(n)} timestamp_ns: 0\n"
            + "\n".join(evs) + "\n}"
            for i, (n, evs) in enumerate(self.lines.items(), 1))
        events = "\n".join(
            f"event_metadata {{ key: {mid} value {{ id: {mid} "
            f"name: {_quote(n)} }} }}" for n, mid in self.events.items())
        stats = "\n".join(
            f"stat_metadata {{ key: {sid} value {{ id: {sid} "
            f"name: {_quote(n)} }} }}" for n, sid in self.stats.items())
        return (f"planes {{ id: {pid} name: {_quote(self.name)}\n{lines}\n"
                f"{events}\n{stats}\n}}")


def trim(profile, pumps: int | None = None) -> str:
    """The text proto of the cut trace."""
    host, window, pump_ends = _Plane("/host:CPU"), None, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name == "sched.pump":
                    pump_ends.append((e.start_ns, e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    lo, hi = window
    if pumps:
        ends = sorted(e for s, e in pump_ends if lo <= s < hi)
        hi = min(hi, ends[min(pumps, len(ends)) - 1]) if ends else hi
    planes = [host]
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = _Plane(plane.name)
            planes.append(dev)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    first, ops = {}, []
                    for e in line.events:
                        if e.start_ns < hi:
                            if e.name not in first:
                                first[e.name] = list(e.stats)
                            ops.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
                    for n, s, e in _top_level(ops):
                        dev.add(OPS_LINE, n, s, e, stats=first.pop(n, ()))
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        if e.start_ns < hi:
                            dev.add(MODULES_LINE, e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    host.add(line.name, e.name, lo, hi)
                elif e.name.startswith(HOST_PREFIXES) and e.start_ns < hi:
                    host.add(line.name, e.name, e.start_ns,
                             e.start_ns + e.duration_ns, stats=list(e.stats))
    return "\n".join(p.text(i) for i, p in enumerate(planes, 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--pumps", type=int, default=None)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    path = (args.trace if args.trace.endswith(".xplane.pb")
            else find_xplane(args.trace))
    text = trim(ProfileData.from_file(path), args.pumps)
    with open(args.out, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    print(f"{args.out}: {os.path.getsize(args.out)} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
