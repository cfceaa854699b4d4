"""The program's spans in a traced run (``bench.program_trace``) and the
per-layer metrics that read them, on a trace small enough to check by hand
and on one recorded on a TPU v5e (``data/``)."""
from pathlib import Path
from types import SimpleNamespace

import pytest
from bench_tiny import make_root
from jax.profiler import ProfileData

from bench import spec
from bench.program_trace import reduce_program
from bench.record import RunRecord
from bench.trace import PROGRAM_PREFIXES, reduce_profile

DATA = Path(__file__).parent / "data"
READERS = ("sched.host_ms_per_step", "engine.sync_ms_per_step",
           "engine.lane_capacity", "engine.dispatches_per_step",
           "engine.search_device_ms_per_req", "device.host_bound_share")

# one chip; the window is [0, 100) us. Device: the beam search's while op
# at [10, 30), a copy at [25, 40) and a kernel at [60, 70): busy [10, 40)
# and [60, 70), idle 60 us; the search's XLA module runs over [9, 30) and
# again from 150 us, after the window. Host: the benchmark's spans as in
# test_bench_trace.py; two pumps in the window, [1, 49) and [50, 98), with
# engine.step children [5, 45) and [52, 90), a search in each (capacity
# 4096, then 2048) and pulls at [30, 41), [41, 42) and [65, 75); a third
# pump starts after the window.
PROGRAM = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 150000000 duration_ps: 10000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 21000000 }
    events { metadata_id: 4 offset_ps: 150000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "while.12" } }
  event_metadata { key: 2 value { id: 2 name: "copy.3" } }
  event_metadata { key: 3 value { id: 3 name: "fused_round_kernel" } }
  event_metadata { key: 4 value { id: 4
    name: "jit__batched_search_loop(7)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 3 offset_ps: 45000000 duration_ps: 55000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 48000000
      stats { metadata_id: 1 int64_value: 0 }
      stats { metadata_id: 2 int64_value: 12 } }
    events { metadata_id: 5 offset_ps: 5000000 duration_ps: 40000000 }
    events { metadata_id: 6 offset_ps: 6000000 duration_ps: 36000000
      stats { metadata_id: 3 int64_value: 4096 } }
    events { metadata_id: 7 offset_ps: 30000000 duration_ps: 11000000
      stats { metadata_id: 4 str_value: "steps" } }
    events { metadata_id: 7 offset_ps: 41000000 duration_ps: 1000000
      stats { metadata_id: 4 str_value: "stable_count" } }
    events { metadata_id: 4 offset_ps: 50000000 duration_ps: 48000000
      stats { metadata_id: 1 int64_value: 1 }
      stats { metadata_id: 2 int64_value: 6 } }
    events { metadata_id: 5 offset_ps: 52000000 duration_ps: 38000000 }
    events { metadata_id: 6 offset_ps: 53000000 duration_ps: 22000000
      stats { metadata_id: 3 int64_value: 2048 } }
    events { metadata_id: 7 offset_ps: 65000000 duration_ps: 10000000
      stats { metadata_id: 4 str_value: "steps" } }
    events { metadata_id: 4 offset_ps: 105000000 duration_ps: 10000000
      stats { metadata_id: 1 int64_value: 2 }
      stats { metadata_id: 2 int64_value: 40 } }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.pump" } }
  event_metadata { key: 3 value { id: 3 name: "bench.step" } }
  event_metadata { key: 4 value { id: 4 name: "sched.pump" } }
  event_metadata { key: 5 value { id: 5 name: "engine.step" } }
  event_metadata { key: 6 value { id: 6 name: "engine.search" } }
  event_metadata { key: 7 value { id: 7 name: "engine.sync" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
  stat_metadata { key: 2 value { id: 2 name: "dispatches" } }
  stat_metadata { key: 3 value { id: 3 name: "capacity" } }
  stat_metadata { key: 4 value { id: 4 name: "site" } }
}
"""


def _without(text, *names):
    """The trace with the host events of the named metadata ids dropped."""
    keep, depth = [], 0
    for line in text.split("\n"):
        if depth == 0 and any(f"events {{ metadata_id: {i} offset_ps" in line
                              for i in names):
            depth = line.count("{") - line.count("}")
        elif depth:
            depth += line.count("{") - line.count("}")
        else:
            keep.append(line)
    return "\n".join(keep)


def _run(text, completed=4):
    return _run_profile(ProfileData.from_text_proto(text), completed)


def _run_profile(profile, completed):
    done = SimpleNamespace(done=True, req=SimpleNamespace(t_done=0.5))
    run = RunRecord(loop="backlog", num_lanes=8, setup_s=1.0, t_start=0.0,
                    t_end=1.0, sent=[done] * completed)
    run.trace = reduce_profile(profile)
    run.program_trace = reduce_program(profile)
    return run


def _read(run, root=spec.ROOT):
    return {n: spec.load_reader("layer_metrics", n, root)(run)
            for n in READERS}


def test_readers_by_hand():
    got = _read(_run(PROGRAM))
    # pump self time: (48 - 40) and (48 - 38) us; the third pump starts
    # after the window
    assert got["sched.host_ms_per_step"] == pytest.approx(9e-3)
    assert got["engine.sync_ms_per_step"] == pytest.approx(11e-3)   # 22/2
    assert got["engine.lane_capacity"] == pytest.approx(3072)
    assert got["engine.dispatches_per_step"] == pytest.approx(9)    # 18/2
    # the search module's 21 us in the window, over 4 answers
    assert got["engine.search_device_ms_per_req"] == pytest.approx(5.25e-3)
    # idle [0, 10), [40, 60), [70, 100): 60 us, of which [40, 42) and
    # [70, 75) fall in a pull
    assert got["device.host_bound_share"] == pytest.approx(53.0)


def test_search_time_reads_only_the_search_module():
    other = PROGRAM.replace("jit__batched_search_loop", "jit_other")
    assert _read(_run(other))["engine.search_device_ms_per_req"] is None


def test_program_spans_leave_the_benchmark_reduction_unchanged():
    """``busy_s``, ``idle_share``, ``op_s``, ``kernel_s`` and the idle
    gaps' lengths read the same with the program's spans as without them;
    only the gaps' labels change."""
    bare = _without(PROGRAM, 4, 5, 6, 7)
    with_spans = reduce_profile(ProfileData.from_text_proto(PROGRAM),
                                kernel_names=("fused_round",))
    without = reduce_profile(ProfileData.from_text_proto(bare),
                             kernel_names=("fused_round",))
    for field in ("window_s", "busy_s", "chips", "op_s", "kernel_s"):
        assert getattr(with_spans, field) == getattr(without, field), field
    assert [g[1] for g in with_spans.gaps] == [g[1] for g in without.gaps]
    assert with_spans.busy_s == pytest.approx(40e-6)
    assert with_spans.idle_share == pytest.approx(0.6)


def test_gaps_name_the_innermost_program_span():
    """Each instant of an idle gap is charged to the innermost span over
    it, and the span charged most names the gap. [70, 100): engine.sync
    5 us, engine.step 15, sched.pump 8, bench.step 2. [40, 60):
    engine.sync 2, engine.step 4, sched.pump 6, bench.pump 1,
    engine.search 7. [0, 10): bench.pump 1, sched.pump 4, engine.step 1,
    engine.search 4; the tie goes to sched.pump, which covers more of the
    gap."""
    tr = reduce_profile(ProfileData.from_text_proto(PROGRAM))
    assert [g[0] for g in tr.gaps] == ["engine.step", "engine.search",
                                       "sched.pump"]
    assert [g[1] for g in tr.gaps] == pytest.approx([30e-6, 20e-6, 10e-6])


def test_a_program_without_spans_reads_nothing():
    """A checkout whose program has no spans (the benchmark laid over an
    older program): every new reader finds nothing, and none raises."""
    run = _run(_without(PROGRAM, 4, 5, 6, 7))
    assert run.program_trace is None
    assert _read(run) == dict.fromkeys(READERS)
    untraced = RunRecord(loop="backlog", num_lanes=8, setup_s=1.0,
                         t_start=0.0, t_end=1.0, sent=[])
    assert _read(untraced) == dict.fromkeys(READERS)


def test_readers_find_the_runs_own_trace(tmp_path):
    """A traced run's readers reduce the profile it left in
    ``bench/.cache/trace`` of their own checkout, and only if its window
    is the run's."""
    root = make_root(tmp_path)
    trace_dir = root / "bench" / ".cache" / "trace" / "plugins"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(PROGRAM))
    run = _run(PROGRAM)
    del run.program_trace
    got = _read(run, root)
    assert got == _read(_run(PROGRAM))
    other = _run(PROGRAM.replace("duration_ps: 100000000 }",
                                 "duration_ps: 90000000 }", 1))
    del other.program_trace
    assert _read(other, root) == dict.fromkeys(READERS)


def test_chip_trace():
    """Three pumps of ``deep96-l2.phi-low.backlog`` traced on a TPU v5e
    (seed 3000000111, cut by ``tools/trim_trace.py --pumps 3``): the spans
    nest, a request's id is on the refill that admits it and the harvest
    that answers it, and each reader reads what the spans say."""
    profile = ProfileData.from_file(
        str(DATA / "deep96-l2.phi-low.backlog.xplane.pb"))
    run = _run_profile(profile, completed=8)
    trace, pt = run.trace, run.program_trace
    assert trace.window_s == pytest.approx(1.516718837)
    assert trace.busy_s == pytest.approx(1.401579225)
    assert pt.window_s == trace.window_s

    def inside(child, parents):
        return [p for p in parents
                if p.start <= child.start and child.end <= p.end]
    pumps, steps = pt.named("sched.pump"), pt.named("engine.step")
    assert len(pumps) == len(steps) == 3
    for child, parent in (("engine.step", "sched.pump"),
                          ("engine.search", "engine.step"),
                          ("engine.sync", "engine.step")):
        for c in pt.named(child):
            assert len(inside(c, pt.named(parent))) == 1, (child, c)
    searches = pt.named("engine.search")
    assert [s.meta["capacity"] for s in searches] == [4096] * 3
    # the first pull after a search waits out the whole beam search
    for s in searches:
        first = [c for c in pt.named("engine.sync") if inside(c, [s])][0]
        assert first.meta["site"] == "steps"
        assert first.seconds > 0.9 * s.seconds
    rids = {name: [s.meta.get("rids", "").split() for s in pt.named(name)]
            for name in ("sched.refill", "sched.harvest")}
    wave = [str(r) for r in range(304, 312)]
    assert rids["sched.refill"][0] == wave == rids["sched.harvest"][1]

    got = _read(run)
    own = [p.seconds - s.seconds for p, s in zip(pumps, steps)]
    assert got["sched.host_ms_per_step"] == pytest.approx(1e3 * sum(own) / 3)
    syncs = sum(s.seconds for s in pt.named("engine.sync"))
    assert got["engine.sync_ms_per_step"] == pytest.approx(1e3 * syncs / 3)
    assert got["engine.lane_capacity"] == 4096
    assert got["engine.dispatches_per_step"] == pytest.approx(
        sum(p.meta["dispatches"] for p in pumps) / 3)
    # the beam search is nearly all of the device's busy time
    search_s = got["engine.search_device_ms_per_req"] * 8 / 1e3
    assert 0.95 * trace.busy_s < search_s <= trace.busy_s
    # idle time outside any pull is part of the idle time
    assert 0 < got["device.host_bound_share"] < 100 * trace.idle_share
    # each of the longest idle gaps lies under a program span: the pulls'
    # waits and the refills, not the benchmark's own pump
    labels = [g[0] for g in trace.gaps]
    assert len(labels) == 10
    assert all(n.startswith(PROGRAM_PREFIXES) for n in labels), labels
    assert labels[0] == "engine.sync"
