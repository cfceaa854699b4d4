"""Run one cell of the benchmark once, on the chip it is started on.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a deployment
(``bench/configs``) and a traffic mix (``bench/traffic``). The run serves
that traffic through the program's own path: ``DiverseVectorDB`` over a
graph from ``index.flat.build_knn_graph`` -> ``LaneScheduler`` (``try_submit``
/ ``pump``) -> ``ProgressiveEngine`` -> ``kernels/ops`` on the compiled
Pallas rung.

Set-up (``setup_s``, process start to the window's start): the corpus,
eps and the query pool from the deployment's data seed, the graph (built,
or read from ``bench/.cache/graphs``), the DB with its prewarmed capacity
ladder, and warm-up passes that rehearse the window's own traffic until
one obtains no program. Then the window: ``--seconds`` of the cell's loop
over queries that the seed picks from the pool and orders, with nothing
compiled in it (the count is printed). After it, every request of the window is served to
its answer (a minute's grace), device memory is read, the program is freed,
and a sample of the window's answers drawn from the seed is compared with
the float64 reference (``bench.reference``).

``--trace 1`` records a profiler trace of a window of at most
``TRACE_WINDOW_S`` seconds with the benchmark's own host spans
(``bench.submit``, ``bench.pump``, ``bench.step``, ``bench.harvest``) and
reports the cell's per-layer metrics; ``--trace 0``
reports its end-to-end metrics. The last line of standard output is one
JSON object; the last lines of standard error are the numbers compared,
each beside its limit. The run exits 2, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

from bench import data as D  # noqa: E402
from bench import loops, spec  # noqa: E402
from bench.record import RunRecord  # noqa: E402
from bench.trace import WINDOW_SPAN  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: Pallas kernels in the device trace: Mosaic custom calls
KERNEL_NAMES = ("tpu_custom_call",)
GRACE_S = 60.0
#: a traced run's window: the profiler keeps a bounded number of device
#: events (a 30 s trace lost its last 11 s) and reading them takes minutes
TRACE_WINDOW_S = 8.0
WARMUP_GRACE_S = 600.0
REHEARSALS = 8


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS:8.3f}s] {msg}", flush=True)


class CompileCounter:
    """Executables JAX obtained (compiled, or loaded from the persistent
    cache) and how many of them were loads."""

    def __init__(self):
        self.n = 0
        self.loaded = 0

    def on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.n += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT:
            self.loaded += 1


_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's one counter (JAX's listeners cannot be removed)."""
    global _COUNTER
    if _COUNTER is None:
        import jax.monitoring
        _COUNTER = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(
            _COUNTER.on_duration)
        jax.monitoring.register_event_listener(_COUNTER.on_event)
    return _COUNTER


def use_compile_cache(root: Path) -> str:
    """Keep every program in JAX's persistent cache, at the fixed path
    ``<checkout>/.jax_cache`` (or ``JAX_COMPILATION_CACHE_DIR`` where the
    caller set it). Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_graph(cell, x, metric, seed, root: Path):
    """The program's kNN graph over ``x``, cached under
    ``bench/.cache/graphs`` by config, size, metric, degree, builder and
    data seed. Returns ``(graph, built)``."""
    from repro.core.graph import make_flat_graph
    from repro.index.flat import build_knn_graph
    cfg = cell.config
    if cfg["builder"] != "knng":
        raise ValueError(f"builder {cfg['builder']!r}: only knng is served")
    key = (f"{cell.config_name}-n{cfg['n']}-d{cfg['d']}-{metric}"
           f"-M{cfg['M']}-{cfg['builder']}-s{seed}")
    path = root / "bench" / ".cache" / "graphs" / f"{key}.npz"
    if path.exists():
        z = np.load(path)
        return make_flat_graph(x, z["neighbors"], None, z["entry"],
                               metric), False
    g = build_knn_graph(x, metric, M=cfg["M"], seed=seed)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".part.npz")
    np.savez(tmp, neighbors=np.asarray(g.neighbors),
             entry=np.asarray(g.entry))
    os.replace(tmp, path)
    return g, True


def build_db(cell, graph, metric, impl, scheduler_kw=None):
    from repro.db import DiverseVectorDB
    cfg = cell.config
    return DiverseVectorDB(
        index=graph, metric=metric, num_lanes=cfg["num_lanes"],
        max_k=cfg["max_k"], default_ef=cfg["default_ef"], M=cfg["M"],
        builder=cfg["builder"], prewarm=True,
        backend_kw={"kernel_impl": impl, "capacity0": cfg["capacity0"]},
        scheduler_kw=scheduler_kw or {})


def serve(sched, traffic, stream, k, eps, seconds, rng, start=0,
          clock=time.monotonic, count=None):
    """One stretch of the mix's loop; returns ``(sent, t0, t1, waiting)``.
    A backlog given ``count`` offers that many requests, however long it
    takes."""
    if traffic["loop"] == "backlog":
        depth = int(traffic["pending_per_lane"]) * sched.num_lanes
        sent, t0, t1 = loops.run_backlog(sched, stream, k, eps, depth,
                                         seconds, clock, start, count)
        return sent, t0, t1, []
    if traffic["loop"] == "open":
        arr = loops.poisson_arrivals(float(traffic["rate_per_s"]), seconds,
                                     rng)
        return loops.run_open(sched, stream, k, eps, arr, seconds, clock,
                              start=start)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def rehearsal_count(traffic, seconds) -> int | None:
    """Requests a backlog's first warm-up pass offers: ``rehearse_per_s``
    times the window's seconds (None for an open loop, whose passes are
    timed)."""
    if traffic["loop"] != "backlog":
        return None
    return int(np.ceil(float(traffic["rehearse_per_s"]) * seconds))


def rehearse(sched, traffic, stream, k, eps, seconds, seed, counter):
    """Warm-up: serve the window's own queries and arrivals, from the
    start, then every answer; again until a pass obtains no program. The
    program compiles per shape signature, and which signatures a request
    meets depends on its query and on the lanes it shares rounds with, so
    only the window's own sequence warms them. A closed backlog's rounds do
    not depend on timing: each pass offers the same count of requests, from
    ``rehearsal_count`` up to a tenth more than the last pass's rate serves
    in the window, so a pass that starts from the lane capacity that the
    one before left (as the window does) and obtains nothing has met every
    shape of the window; the program's speed alone sets how many passes
    that takes. An open loop's pass lasts a tenth longer than the window.
    Returns the number of passes and the count of the last (None for an
    open loop)."""
    count = rehearsal_count(traffic, seconds)
    for p in range(1, REHEARSALS + 1):
        c0 = counter.n
        sent, t0, t1, waiting = serve(sched, traffic, stream, k, eps,
                                      1.1 * seconds, window_rng(seed),
                                      count=count)
        left = loops.finish(sched, stream, k, eps, waiting, sent,
                            WARMUP_GRACE_S)
        if left:
            raise RuntimeError(f"warm-up: {len(left)} requests unanswered")
        rate = len(sent) / max(t1 - t0, 1e-9)
        log(f"warm-up pass {p}: {len(sent)} requests at {rate!r} req/s, "
            f"{counter.n - c0} programs obtained")
        if count is not None and 1.1 * rate * seconds > count:
            count = int(np.ceil(1.1 * rate * seconds))
        elif counter.n == c0:
            return p, count
    raise RuntimeError(f"warm-up still compiling after {REHEARSALS} passes")


def window_rng(seed):
    """The generator of the window's arrivals (and of its rehearsals')."""
    return np.random.default_rng([seed, 4])


class Spans:
    """The benchmark's own host spans around the calls into each layer
    (traced runs): ``TraceAnnotation`` in the profile, and the ``step``
    intervals and lane occupancy kept for the readers."""

    def __init__(self, db):
        from jax.profiler import TraceAnnotation
        self.steps, self.occupancy = [], []
        #: steps are kept only while the window is open, not as the backlog
        #: drains after it
        self.on = False
        sched, backend = db.scheduler, db.backend
        lanes = sched.num_lanes

        def wrap(obj, attr, name, before=None, keep=None):
            inner = getattr(obj, attr)

            def call(*a, **kw):
                on = self.on
                if on and before is not None:
                    before()
                t0 = time.monotonic()
                with TraceAnnotation(name):
                    out = inner(*a, **kw)
                if on and keep is not None:
                    keep.append((t0, time.monotonic()))
                return out
            setattr(obj, attr, call)

        wrap(sched, "try_submit", "bench.submit")
        wrap(sched, "pump", "bench.pump")
        wrap(backend, "step", "bench.step", keep=self.steps, before=lambda:
             self.occupancy.append(backend.active_count() / lanes))
        wrap(backend, "harvest", "bench.harvest")


def record_frontiers(db) -> dict:
    """Keep each answer's certificate frontier (the backend's
    ``last_candidates`` row at harvest), keyed by the answer's id."""
    frontiers: dict = {}
    backend = db.backend
    harvest = backend.harvest

    def recording_harvest():
        out = harvest()
        for lane, result in out:
            rec = backend.last_candidates[lane]
            frontiers[id(result)] = None if rec is None else rec[0]
        return out

    backend.harvest = recording_harvest
    return frontiers


# ----------------------------------------------------------- reference pool

_X64 = None


def _init_worker(dataset, n, d, data_seed):
    global _X64
    x, _ = D.make_dataset(dataset, n, d, data_seed)
    _X64 = x.astype(np.float64)


def _check(args):
    from bench.reference import check_answer
    ans, q, metric, k, eps = args
    return check_answer(ans, q, _X64, metric, k, eps)


def check_sample(cfg, metric, k, eps, answers, queries, workers):
    """Run the float64 reference over the sampled answers, in ``workers``
    processes that rebuild the corpus from its seed (none: in this
    one)."""
    tasks = [(a, q, metric, k, eps) for a, q in zip(answers, queries)]
    init = (cfg["dataset"], cfg["n"], cfg["d"], cfg["data_seed"])
    if not workers:
        _init_worker(*init)
        return [_check(t) for t in tasks]
    import concurrent.futures as cf
    import multiprocessing as mp
    with cf.ProcessPoolExecutor(workers, mp.get_context("spawn"),
                                initializer=_init_worker,
                                initargs=init) as pool:
        return list(pool.map(_check, tasks))


def checks_of(summary, unanswered, limits, eps) -> dict:
    """The numbers compared, each beside its limit, in print order. A
    served pair may lie above eps by float32 rounding alone
    (``reference.f32_sim_tol``); the other limits are exact or the
    configuration's."""
    from bench.reference import f32_sim_tol
    return {
        "unanswered": {"value": unanswered, "limit": 0},
        "short": {"value": summary["short"], "limit": 0},
        "score_err": {"value": summary["score_err"],
                      "limit": limits["score_err"]},
        "div_excess": {"value": summary["div_excess"],
                       "limit": f32_sim_tol(eps)},
        "suboptimal": {"value": summary["suboptimal"],
                       "limit": limits["suboptimal"]},
        "recheck_fail": {"value": summary["recheck_fail"],
                         "limit": limits["recheck_fail"]},
    }


def correct_of(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def sample_indices(seed, traffic, sent: int) -> np.ndarray:
    """The checked sample: ``check_sample`` of the window's ``sent``
    requests, drawn from the seed."""
    return np.sort(np.random.default_rng([seed, 2]).choice(
        sent, min(int(traffic["check_sample"]), sent), replace=False))


# --------------------------------------------------------------------- run

def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             impl: str = "pallas", root: Path = spec.ROOT,
             t_process: float = T_PROCESS, workers: int = 8,
             grace: float = GRACE_S, break_path=None,
             scheduler_kw=None) -> dict:
    """Set up, measure, check. Returns the result object (the last line).

    ``break_path(db)``, where given, breaks the timed path underneath
    after the warm-up (the tests' faults); ``grace`` is how long answers
    due in the window are waited for after it; ``scheduler_kw`` goes to
    the DB's scheduler (the tests' smaller prewarm)."""
    import jax
    from bench.reference import summarize
    from repro.kernels import ops as kops

    cfg, traffic = cell.config, cell.traffic
    kops.set_default_impl(impl)
    counter = compile_counter()
    phases = {}
    t = time.monotonic()
    data_seed = int(cfg["data_seed"])
    x, metric = D.make_dataset(cfg["dataset"], cfg["n"], cfg["d"], data_seed)
    if metric != cfg["metric"]:
        raise ValueError(f"{cfg['dataset']} is {metric}, config says "
                         f"{cfg['metric']}")
    k = int(traffic["k"])
    eps = D.calibrate_eps(x, metric, D.PHI_TARGETS[traffic["phi"]],
                          data_seed)
    pool = D.query_pool(x, int(cfg["query_pool"]), data_seed,
                        float(cfg["query_noise"]))
    phases["data_s"] = time.monotonic() - t
    t = time.monotonic()
    graph, built = load_graph(cell, x, metric, data_seed, root)
    phases["graph_build_s" if built else "graph_load_s"] = \
        time.monotonic() - t
    t = time.monotonic()
    c0 = counter.n
    db = build_db(cell, graph, metric, impl, scheduler_kw)
    phases["db_prewarm_s"] = time.monotonic() - t
    phases["prewarm_programs"] = counter.n - c0
    frontiers = record_frontiers(db)
    sched = db.scheduler
    t = time.monotonic()
    c0 = counter.n
    stream = D.QueryStream(pool, seed)
    window_s = min(seconds, TRACE_WINDOW_S) if trace else seconds
    phases["warmup_passes"], rehearsed = rehearse(
        sched, traffic, stream, k, eps, window_s, seed, counter)
    phases["warmup_s"] = time.monotonic() - t
    phases["warmup_programs"] = counter.n - c0
    phases["programs_loaded"] = counter.loaded
    phases["programs_compiled"] = counter.n - counter.loaded
    log("set-up " + " ".join(f"{a}={b!r}" for a, b in phases.items()))

    if break_path is not None:
        break_path(db)
    spans = Spans(db) if trace else None
    trace_dir = root / "bench" / ".cache" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    c0 = counter.n
    setup_s = time.monotonic() - t_process
    window_cm = (jax.profiler.TraceAnnotation(WINDOW_SPAN)
                 if trace else contextlib.nullcontext())
    if spans is not None:
        spans.on = True
    with window_cm:
        sent, t0, t1, waiting = serve(
            sched, traffic, stream, k, eps, window_s, window_rng(seed))
    if spans is not None:
        spans.on = False
    window_compiles = counter.n - c0
    open_at_close = sum(not (s.done and s.req.t_done <= t1) for s in sent)
    log(f"window compiles={window_compiles} requests={len(sent)} "
        f"unanswered_at_close={open_at_close} seconds={t1 - t0!r}")
    if rehearsed is not None and len(sent) > rehearsed:
        log(f"the window offered {len(sent)} requests, the warm-up "
            f"{rehearsed}: the rest met shapes no pass warmed")
    if traffic["loop"] == "open":
        late = [s.t_sent - s.t_due for s in sent if s.t_sent is not None]
        log(f"generator lateness p50_ms={1e3 * float(np.median(late))!r} "
            f"max_ms={1e3 * max(late)!r}")
    unanswered = loops.finish(sched, stream, k, eps, waiting, sent, grace)
    t_closed = time.monotonic()
    if trace:   # after the answers: stopping the profiler takes seconds
        jax.profiler.stop_trace()
    mem = jax.devices()[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    rec = RunRecord(loop=traffic["loop"], num_lanes=sched.num_lanes,
                    setup_s=setup_s, t_start=t0, t_end=t1, sent=sent,
                    t_closed=t_closed)
    if trace:
        from bench.trace import reduce_dir
        rec.step_spans, rec.occupancy = spans.steps, spans.occupancy
        rec.trace = reduce_dir(str(trace_dir), KERNEL_NAMES)

    pick = sample_indices(seed, traffic, len(sent))
    answers, queries = [], []
    for i in pick:
        s = sent[i]
        if not s.done:
            continue
        r = s.req.result
        answers.append(dict(ids=np.asarray(r.ids), scores=np.asarray(r.scores),
                            certified=bool(r.stats.certified),
                            frontier=frontiers.get(id(r))))
        queries.append(stream[s.index])
    del db, sched, graph, spans
    gc.collect()
    t = time.monotonic()
    rows = check_sample(cfg, metric, k, eps, answers, queries, workers)
    summary = summarize(rows)
    rec.recall = summary["recall"] if rows else None
    log(f"reference: {len(rows)} answers in {time.monotonic() - t!r} s, "
        f"recall@{k}={summary['recall']!r} "
        f"widest total_gap={summary['total_gap']!r} "
        f"failed recheck margins={summary['recheck_margins']!r}")

    checks = checks_of(summary, len(unanswered), cfg["limits"], eps)
    correct = correct_of(checks)
    if trace:
        metrics = spec.read_metrics(cell.per_layer, "layer_metrics", rec,
                                    root)
    else:
        metrics = spec.read_metrics(cell.end_to_end, "end_to_end", rec, root)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": len(sent),
           "failed": len(unanswered), "metrics": metrics, "device": device}
    if trace:
        tr = rec.trace
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in list(tr.op_s.items())[:10]],
            "idle_gaps": [[n, s] for n, s in tr.gaps[:10]]}
    out["checks"] = checks
    return out


def emit(out: dict) -> None:
    """The numbers compared on standard error, then the result line."""
    for name, c in out["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seed = args.seed % (1 << 64)
    cell = spec.load_cell(args.workload)
    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench.run: the program is not in this checkout ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    use_compile_cache(spec.ROOT)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench.run: no TPU: jax.devices()[0].platform is "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench.run: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    log(f"device {devs[0].device_kind} x{len(devs)}; workload "
        f"{args.workload} seed={seed} seconds={args.seconds} "
        f"trace={args.trace}")
    out = run_cell(cell, seed, args.seconds, bool(args.trace))
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
