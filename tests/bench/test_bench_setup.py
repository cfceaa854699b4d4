"""``setup_s`` on a whole run of a tiny cell on the CPU: the build (process
start to the end of the DB's build) plus the seconds the warm-up spent
obtaining programs, and not the warm-up's serving time."""
import re
import time

from bench_tiny import TINY_SCHEDULER

import bench.run as R
from bench import spec

SEED = 3000000023


def run(root, capsys):
    """One run from a process start taken now; returns the result, the
    set-up phases and each warm-up pass's obtain seconds, as logged."""
    out = R.run_cell(spec.load_cell("tiny.tb", root), SEED, 1.0, False,
                     impl="ref", root=root, workers=0,
                     scheduler_kw=TINY_SCHEDULER, t_process=time.monotonic())
    log = capsys.readouterr().out
    line = next(ln for ln in log.splitlines() if "] set-up " in ln)
    phases = {k: float(v) for k, v in re.findall(r"(\w+)=(\S+)", line)}
    passes = [float(v) for v in
              re.findall(r"programs obtained in (\S+) s", log)]
    return out, phases, passes


def test_setup_is_build_and_obtain(tiny_root, capsys):
    out, phases, passes = run(tiny_root, capsys)
    assert out["correct"], out["checks"]
    setup_s = out["metrics"]["setup_s"]["value"]
    assert setup_s == phases["build_s"] + phases["warmup_obtain_s"]
    assert phases["build_s"] >= phases["db_prewarm_s"] > 0
    assert len(passes) == phases["warmup_passes"]
    assert 0 <= phases["warmup_obtain_s"] <= phases["warmup_s"]
    # a union of the spans: no more than the phases' sums, as much as the
    # passes' own unions (the passes do not overlap)
    assert phases["warmup_obtain_s"] <= (phases["warmup_trace_s"]
                                         + phases["warmup_lower_s"]
                                         + phases["warmup_compile_s"])
    assert abs(phases["warmup_obtain_s"] - sum(passes)) < 1e-9
    if phases["warmup_programs"]:
        assert phases["warmup_compile_s"] > 0


def test_an_extra_pass_that_obtains_nothing_costs_no_setup(
        tiny_root, capsys, monkeypatch):
    """A warm-up forced to one more pass of the same requests, which finds
    every program already obtained: that pass's obtain seconds are 0, and
    ``setup_s`` rises by less than the pass took to serve."""
    run(tiny_root, capsys)              # every program the cell meets
    _, base, base_passes = run(tiny_root, capsys)
    assert len(base_passes) == 1

    serve = R.serve
    served = []

    def serve_once_more(*a, count=None, **kw):
        got = serve(*a, count=count, **kw)
        if count is not None:
            served.append(got[2] - got[1])
            if len(served) == 1:        # the first pass seems to obtain one
                R.compile_counter().n += 1
        return got
    monkeypatch.setattr(R, "serve", serve_once_more)
    out, more, passes = run(tiny_root, capsys)
    assert out["correct"], out["checks"]
    assert more["warmup_passes"] == 2 and passes[1] == 0.0
    assert more["warmup_obtain_s"] == passes[0]
    assert out["metrics"]["setup_s"]["value"] == more["setup_s"] == (
        more["build_s"] + more["warmup_obtain_s"])
    assert more["setup_s"] - base["setup_s"] < served[1]
