"""Serving-path benchmark: engine vs per-query loop, continuous vs lockstep
admission on skewed workloads, open-system (Poisson) load curves, the
fused-round kernel microbench, and the compressed-corpus scoring bench.

Seven modes:

* ``--mode engine`` (default) — PR 1's headline comparison: at serving batch
  sizes the per-query pause/inspect/resume loop pays its host round-trips
  and device dispatches per *query*, while the batched engine pays them per
  *round* for the whole batch — same per-lane semantics (exact parity with
  ``pss``), ~B-fold fewer dispatches.

* ``--mode skewed`` — the continuous-batching comparison: a heavy-tailed
  request mix (mixed ``k`` in {5, 10}, mostly light-diversification queries
  with a heavy tail of dense-G^eps ones whose div-A* trip counts explode)
  served by the *same* lane scheduler under two admission policies.
  Lockstep admission refills lanes only when the whole wave finished (every
  wave waits for its straggler); continuous admission recycles each
  certified lane immediately. Both policies return bit-identical per-request
  results (verified against the per-query ``pss`` driver — a parity
  violation exits nonzero, which is what the CI smoke job checks); the
  difference is purely p50/p99 latency and throughput. ``--tiny`` shrinks
  everything for the CI smoke job.

* ``--mode open`` — the open-system load generator: requests arrive by a
  Poisson process at ``--qps`` (comma-separated for a sweep) and are pushed
  through the scheduler in real time, reporting p50/p99 wait/latency and
  shed rate vs offered load. ``--backend engine`` (single-host
  ``ProgressiveEngine``), ``--backend sharded`` (a ``ShardedEngine`` over an
  in-process mesh of the available devices), or ``--backend both`` drive the
  *same* ``LaneScheduler`` — the point of the LaneBackend protocol. The
  sharded backend runs twice, as ``sharded-scratch`` and ``sharded-beam``
  (the resumable shard-local beams), and every load point reports the
  cumulative expansion / per-round counters — the measured work that
  resumption saves. An optional latency SLO (``--slo`` seconds) installs
  the shed callback: requests whose expected queue wait already exceeds the
  SLO are dropped at submit. All summary math (percentiles, Jain fairness)
  comes from ``repro.serve.scheduler`` so benchmark and scheduler stats
  cannot drift.

  ``--tenants N --policy {fifo,drr,slo_cost}`` turns the open mode into the
  multi-tenant fairness bench: a skewed two-tenant mix (tenant ``heavy``
  issues sparse heavy-eps / k=10 requests, tenant ``light`` floods cheap
  low-eps / k=5 ones) is served under the chosen admission policy
  (``serve.policies``). Per-tenant p50/p99/fairness, the cost model's
  calibration error, and the full request conservation law (served + shed
  + deferred == offered; violation exits nonzero — the CI ``policy-smoke``
  gate) are reported per load point. With ``--policy slo_cost`` the
  ``--slo`` value becomes the per-tenant latency budget (shed/defer at
  submit) instead of installing the legacy callback.

* ``--mode quantized`` — PR 7's compressed-corpus point: int8/PQ quantized
  similarity scoring vs full-float scoring, plus the score-then-verify
  shape (quantized prefilter of a ``4k`` frontier, exact float rerank,
  recall@k vs the exact float top-k). Every ``quant@<scheme>W<width>k<k>``
  point carries ``bytes_per_vector``; interpret-mode Pallas parity and the
  recall floor gate the exit code (the CI ``quantized-parity`` job).

* ``--mode churn`` — PR 9's mutable-index point: Poisson reads against one
  ``DiverseVectorDB`` with a ``--write-frac`` fraction of interleaved
  upserts/deletes (the delta fills and the rebuilt graph epoch-swaps
  mid-run). The write-op log is replayed to audit every served result —
  mixed-epoch violations, certificate soundness vs each result's corpus
  version, stale cache hits — and sampled live-path recall must stay
  within 1% of a rebuild-from-scratch twin at the same (k, eps, ef)
  budget. All four gates drive the exit code (the CI ``mutable-smoke``
  job).

* ``--mode diurnal`` — PR 10's elastic-serving point: a low -> peak -> low
  Poisson arrival schedule served twice through ``DiverseVectorDB`` — once
  with ``elastic=`` (the scheduler grows 2 -> 4 shards under the peak and
  shrinks back once the queue empties, migrating in-flight lanes between
  rounds) and once on a static 2-shard mesh. Per-phase p50/p99, scale-event
  counts, and migration-pause ms are reported; the run gates on Theorem-2
  parity of every captured certified frontier (0 violations), >= 1 grow +
  >= 1 shrink, and elastic peak-phase p99 no worse than the static
  small-mesh baseline (the CI ``elastic-smoke`` job). Needs >= 4 devices
  (``XLA_FLAGS=--xla_force_host_platform_device_count=4`` on CPU);
  ``--qps low,peak`` overrides the phase rates.

* ``--mode kernel`` — PR 6's fused-round point: one ``fused_round_batch``
  dispatch vs the per-stage chain it replaced in the engine's PGS round
  (prefix-mask, adjacency, greedy, host extraction), at serving (prefix
  width, k) shapes, with bit-parity cross-checks (fused vs staged, and
  interpret-mode Pallas vs the jnp oracle) that exit nonzero on any
  violation — the CI ``kernel-parity`` gate.

``--json PATH`` merges the run into a stable-schema JSON trend file
(``schema_version`` 2 — see ``docs/BENCH_SCHEMA.md`` for the field map and
the version-1 compatibility rule): one ``modes`` entry per bench mode,
point entries merged by key across invocations, so CI can upload a single
``BENCH_pr6.json`` artifact with skewed-admission, open-system,
policy/fairness, and fused-kernel numbers side by side.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax.numpy as jnp
import numpy as np

if __package__ in (None, ""):   # `python benchmarks/batch_bench.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import datasets as D
from benchmarks.common import emit, timed
from repro.core.api import diverse_search
from repro.core.batch import batch_greedy_diverse, batch_optimal_diverse
from repro.core.batch_progressive import (_batched_adjacency, _mask_prefix,
                                          batch_pss)
from repro.kernels import ops as kops
from repro.launch.compile_cache import use_compile_cache
from repro.serve.scheduler import LaneScheduler, jain_fairness, percentile


def run(n: int = D.N_DEFAULT, batch: int = 64, k: int = 10, ef: int = 10,
        phis: tuple = ("low", "medium")):
    graph, x, metric = D.load_graph("deep-like", n=n)
    queries = D.queries_for(x, batch)
    qs = jnp.asarray(queries)
    speedups = {}
    for phi in phis:
        eps = D.calibrate_eps(x, metric, D.PHI_TARGETS[phi])

        # per-query progressive driver loop (paper-faithful baseline)
        def loop_pss():
            return [diverse_search(graph, q, k=k, eps=eps, method="pss",
                                   ef=ef) for q in queries]
        _, dt_loop = timed(loop_pss, warmup=1, reps=1)
        emit(f"batch/{phi}/per_query_pss", dt_loop / batch * 1e6,
             "per-query us")

        # batched progressive engine (exact same per-lane results);
        # streams=2 overlaps host orchestration with device work
        def engine():
            return batch_pss(graph, qs, k, eps, ef=ef, streams=2)
        res, dt_e = timed(engine, warmup=1, reps=2)
        speedups[phi] = dt_loop / dt_e
        emit(f"batch/{phi}/progressive_engine", dt_e / batch * 1e6,
             f"certified_frac={res.stats.certified.mean():.2f};"
             f"speedup={dt_loop / dt_e:.1f}x")

        # legacy fixed-K div-A* (approximation: static candidate budget)
        def batched():
            out = batch_optimal_diverse(graph, qs, k, eps, K=128, ef=4)
            out[0].block_until_ready()
            return out
        out, dt_b = timed(batched, warmup=1, reps=2)
        cert = float(np.mean(np.asarray(out[3])))
        emit(f"batch/{phi}/batched_divastar", dt_b / batch * 1e6,
             f"certified_frac={cert:.2f};speedup={dt_loop/dt_b:.1f}x")

        def batched_greedy():
            out = batch_greedy_diverse(graph, qs, k, eps, L=256)
            out[0].block_until_ready()
            return out
        _, dt_g = timed(batched_greedy, warmup=1, reps=2)
        emit(f"batch/{phi}/batched_greedy", dt_g / batch * 1e6,
             f"speedup_vs_loop={dt_loop/dt_g:.1f}x")
    return speedups


# ------------------------------------------------------------ skewed mode ----

def make_skewed_workload(x, metric, requests: int, seed: int = 7):
    """Mixed (k, eps) request stream with a heavy diversification tail:
    75% light (phi ~ low) queries, 25% dense-G^eps (phi ~ medium) ones,
    k alternating in {5, 10}, order shuffled."""
    rng = np.random.default_rng(seed)
    queries = D.queries_for(x, requests)
    eps_light = D.calibrate_eps(x, metric, D.PHI_TARGETS["low"])
    eps_heavy = D.calibrate_eps(x, metric, D.PHI_TARGETS["medium"])
    ks = np.where(np.arange(requests) % 2 == 0, 5, 10)
    heavy = rng.permutation(requests) < requests // 4
    epss = np.where(heavy, eps_heavy, eps_light)
    perm = rng.permutation(requests)
    return queries[perm], ks[perm], epss[perm], heavy[perm]


def _serve(graph, queries, ks, epss, ef, lanes, admission, prewarm):
    sched = LaneScheduler(graph, num_lanes=lanes, max_k=int(ks.max()),
                          default_ef=ef, admission=admission,
                          max_pending=len(queries), prewarm=prewarm)
    results = sched.run(queries, ks, epss, efs=ef)
    return sched, results


def run_skewed(n: int = D.N_DEFAULT, requests: int = 64, lanes: int = 16,
               ef: int = 10, parity: str = "sample", seed: int = 7) -> dict:
    graph, x, metric = D.load_graph("deep-like", n=n)
    queries, ks, epss, heavy = make_skewed_workload(x, metric, requests, seed)
    print(f"# skewed workload: {requests} requests, {lanes} lanes, n={n}, "
          f"heavy_frac={heavy.mean():.2f}, ks={sorted(set(ks.tolist()))}",
          flush=True)

    # warmup: compiles the capacity ladder + every diversify signature the
    # workload reaches (jit caches are module-global, so both timed passes
    # below run fully warm)
    _serve(graph, queries, ks, epss, ef, lanes, "continuous", prewarm=True)

    out = {}
    for admission in ("lockstep", "continuous"):
        sched, results = _serve(graph, queries, ks, epss, ef, lanes,
                                admission, prewarm=False)
        stats = sched.latency_stats()
        out[admission] = (stats, results)
        emit(f"skewed/{admission}/p50_latency", stats["p50_latency"] * 1e6,
             "per-request us")
        emit(f"skewed/{admission}/p99_latency", stats["p99_latency"] * 1e6,
             f"fairness={stats['fairness']:.3f}")
        emit(f"skewed/{admission}/throughput", stats["throughput"],
             f"req_per_s;certified_frac={stats['certified_frac']:.2f};"
             f"signatures={stats['signatures']}")

    ls, cs = out["lockstep"][0], out["continuous"][0]
    p99_win = cs["p99_latency"] < ls["p99_latency"]
    tput_win = cs["throughput"] > ls["throughput"]
    print(f"# continuous vs lockstep: p99 "
          f"{ls['p99_latency']:.3f}s -> {cs['p99_latency']:.3f}s "
          f"({'better' if p99_win else 'WORSE'}), throughput "
          f"{ls['throughput']:.2f} -> {cs['throughput']:.2f} req/s "
          f"({'better' if tput_win else 'WORSE'})", flush=True)

    # parity: scheduler results (either admission — they are identical by
    # construction, assert that too) vs the per-query PSS driver
    violations = 0
    lock_res, cont_res = out["lockstep"][1], out["continuous"][1]
    for i in range(requests):
        if not (np.array_equal(lock_res[i].ids, cont_res[i].ids)
                and np.array_equal(lock_res[i].scores, cont_res[i].scores)):
            print(f"# PARITY VIOLATION lockstep!=continuous at request {i}")
            violations += 1
    if parity != "off":
        sample = (range(requests) if parity == "full" else
                  np.random.default_rng(0).choice(requests,
                                                  min(8, requests),
                                                  replace=False))
        for i in sample:
            solo = diverse_search(graph, queries[i], k=int(ks[i]),
                                  eps=float(epss[i]), method="pss", ef=ef)
            r = cont_res[i]
            if not (np.array_equal(np.asarray(solo.ids), r.ids)
                    and np.array_equal(np.asarray(solo.scores), r.scores)
                    and solo.stats.certified == r.stats.certified):
                print(f"# PARITY VIOLATION scheduler!=solo pss at request {i}")
                violations += 1
    print(f"# parity check: {violations} violations", flush=True)
    return dict(lockstep=ls, continuous=cs, p99_win=p99_win,
                tput_win=tput_win, parity_violations=violations)


# ----------------------------------------------------------- kernel mode ----

def _prefix_tiles(x, metric, B: int, width: int, seed: int = 7):
    """Realistic fused-round inputs: per-lane sorted top-``width`` prefixes
    of real query/corpus scores, with ragged per-lane budgets."""
    rng = np.random.default_rng(seed)
    qs = jnp.asarray(D.queries_for(x, B))
    sims = np.asarray(kops.batch_similarity_many(qs, jnp.asarray(x), metric,
                                                 impl="ref"))
    order = np.argsort(-sims, axis=1, kind="stable")[:, :width]
    ids = order.astype(np.int32)
    scores = np.take_along_axis(sims, order, axis=1).astype(np.float32)
    # ragged budgets: half the lanes run a partial prefix (exercises the
    # in-kernel masking the engine's _mask_prefix stage used to do)
    Ks = np.where(np.arange(B) % 2 == 0, width,
                  rng.integers(width // 2, width, size=B)).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(scores), Ks


def run_kernel(n: int = D.N_DEFAULT, B: int = 16,
               widths: tuple = (128, 256), ks: tuple = (5, 10),
               reps: int = 20, seed: int = 7) -> dict:
    """Fused round kernel vs the per-stage dispatch chain it replaced.

    For each (prefix width, k) point, times ``kops.fused_round_batch`` (one
    dispatch) against the engine's pre-PR-6 chain — ``_mask_prefix`` ->
    ``_batched_adjacency`` -> ``greedy_diversify_batch`` -> host extraction
    (3 dispatches + the same host gather) — on identical inputs, and
    cross-checks both for bit-equal results. Each point also runs the
    interpret-mode Pallas kernel on a sub-tile and asserts bit-parity with
    the jnp oracle, so a CPU-only CI run still exercises the kernel's own
    code path. Any mismatch counts as a parity violation (nonzero exit).
    """
    graph, x, metric = D.load_graph("deep-like", n=n)
    vectors = graph.vectors
    eps_val = D.calibrate_eps(x, metric, D.PHI_TARGETS["medium"])
    out: dict = {"parity_violations": 0}
    impl = kops._resolve(None)
    for width in widths:
        ids, scores, Ks = _prefix_tiles(x, metric, B, width, seed)
        eps = jnp.full(B, eps_val, jnp.float32)
        Ks_j = jnp.asarray(Ks)
        for k in ks:
            def fused():
                sid, ssc, cnt, _ = kops.fused_round_batch(
                    vectors, ids, scores, Ks_j, eps, k, metric)
                return np.asarray(sid), np.asarray(ssc), np.asarray(cnt)

            def staged():
                ids_m, sc_m = _mask_prefix(ids, scores, Ks_j)
                adj = _batched_adjacency(vectors, ids_m, eps, metric)
                sel, cnt = kops.greedy_diversify_batch(sc_m, adj, k,
                                                       valid=ids_m >= 0)
                s, i_np, s_np = (np.asarray(sel), np.asarray(ids_m),
                                 np.asarray(sc_m))
                g = np.maximum(s, 0)
                return (np.where(s >= 0, np.take_along_axis(i_np, g, 1), -1),
                        np.where(s >= 0, np.take_along_axis(s_np, g, 1), 0.0)
                        .astype(np.float32),
                        np.asarray(cnt))

            fres, dt_f = timed(fused, warmup=1, reps=reps)
            sres, dt_s = timed(staged, warmup=1, reps=reps)
            violations = 0
            for name, a, b in zip(("ids", "scores", "count"), fres, sres):
                if not np.array_equal(a, b):
                    print(f"# PARITY VIOLATION fused!=staged W={width} "
                          f"k={k}: {name}")
                    violations += 1
            # interpret-mode kernel vs oracle on a sub-tile (CPU-friendly)
            sub = min(4, B)
            want = kops.fused_round_batch(vectors, ids[:sub], scores[:sub],
                                          Ks_j[:sub], eps[:sub], k, metric,
                                          impl="ref")
            got = kops.fused_round_batch(vectors, ids[:sub], scores[:sub],
                                         Ks_j[:sub], eps[:sub], k, metric,
                                         impl="interpret")
            for name, a, b in zip(("ids", "scores", "count", "cert"),
                                  got, want):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    print(f"# PARITY VIOLATION interpret!=ref W={width} "
                          f"k={k}: {name}")
                    violations += 1
            speedup = dt_s / dt_f
            emit(f"kernel/W{width}k{k}/fused", dt_f * 1e6,
                 f"us_per_round;impl={impl}")
            emit(f"kernel/W{width}k{k}/staged", dt_s * 1e6,
                 f"us_per_round;speedup={speedup:.2f}x;"
                 f"violations={violations}")
            out[(width, k)] = dict(
                fused_s=dt_f, staged_s=dt_s, speedup=speedup,
                lanes=B, impl=impl, parity_violations=violations)
            out["parity_violations"] += violations
    return out


def _kernel_payload(res: dict) -> dict:
    """Point key: ``kernel@W<width>k<k>`` (mirrors the open mode's
    ``<kind>@...`` convention); ``parity_violations`` totals the file-level
    gate CI trips on."""
    points = sorted(kv for kv in res.items() if isinstance(kv[0], tuple))
    out = {f"kernel@W{w}k{k}": point for (w, k), point in points}
    out["parity_violations"] = res["parity_violations"]
    return out


# --------------------------------------------------------- quantized mode ---

def run_quantized(n: int = D.N_DEFAULT, B: int = 16, ks: tuple = (5, 10),
                  schemes: tuple = ("int8", "pq"), rerank_factor: int = 4,
                  reps: int = 10, recall_floor: float = 0.95,
                  seed: int = 7) -> dict:
    """Compressed-corpus scoring: quantized similarity kernels + exact
    float rerank vs full-float scoring.

    For each scheme, times the batched quantized op against
    ``batch_similarity_many`` on the float corpus, then runs the PR 7
    score-then-verify shape per ``k``: quantized scores pick a
    ``rerank_factor * k`` frontier, ``index.flat.exact_rerank`` re-scores
    it in float, and the top-k after rerank is compared against the exact
    float top-k (``recall_at_k``). Each scheme also cross-checks the
    interpret-mode Pallas kernel bitwise against the jnp oracle
    (CPU-friendly — the same parity contract ``tests/test_quant.py``
    pins), and every point carries ``bytes_per_vector`` — the memory
    knob this trade buys. A parity mismatch or a point under
    ``recall_floor`` counts as a violation (nonzero exit — the CI
    ``quantized-parity`` gate).
    """
    from repro import quant
    from repro.index.flat import exact_rerank, exact_topk

    x, metric = D.make_dataset("deep-like", n=n)
    queries = D.queries_for(x, B, seed)
    qs = jnp.asarray(queries)
    xs = jnp.asarray(x)
    impl = kops._resolve(None)
    out: dict = {"parity_violations": 0}
    f32_bpv = 4.0 * x.shape[1]

    def float_score():
        return np.asarray(kops.batch_similarity_many(qs, xs, metric))
    sims_f, dt_f = timed(float_score, warmup=1, reps=reps)
    truth = {k: exact_topk(queries, x, k, metric)[0] for k in ks}

    for scheme in schemes:
        corpus = quant.quantize_corpus(x, scheme, seed=seed)
        bpv = float(quant.corpus_bytes_per_vector(corpus))

        def quant_score():
            return np.asarray(kops.quantized_similarity_many(qs, corpus,
                                                             metric))
        sims_q, dt_q = timed(quant_score, warmup=1, reps=reps)

        violations = 0
        sub = min(4, B)
        want = np.asarray(kops.quantized_similarity_many(
            qs[:sub], corpus, metric, impl="ref"))
        got = np.asarray(kops.quantized_similarity_many(
            qs[:sub], corpus, metric, impl="interpret"))
        if not np.array_equal(want, got):
            print(f"# PARITY VIOLATION interpret!=ref scheme={scheme}: "
                  f"max|d|={np.abs(want - got).max()}")
            violations += 1

        for k in ks:
            width = rerank_factor * k
            # quantized prefilter (deterministic id tie-break, same
            # lexicographic order exact_topk uses) -> exact float rerank
            pre = np.lexsort((np.arange(n)[None, :].repeat(B, 0), -sims_q),
                             axis=1)[:, :width].astype(np.int32)
            rr_ids, _ = exact_rerank(queries, pre, x, metric)
            hits = [len(set(rr_ids[r, :k].tolist())
                        & set(truth[k][r].tolist())) / k for r in range(B)]
            rec = float(np.mean(hits))
            if rec < recall_floor:
                print(f"# RECALL VIOLATION {scheme}@W{width}k{k}: "
                      f"{rec:.3f} < floor {recall_floor}")
                violations += 1
            emit(f"quant/{scheme}W{width}k{k}", dt_q / B * 1e6,
                 f"us_per_query;bytes_per_vector={bpv:.1f};"
                 f"recall={rec:.3f};speedup_vs_float={dt_f / dt_q:.2f}x")
            out[(scheme, width, k)] = dict(
                quantized_s=dt_q, float_s=dt_f, speedup=dt_f / dt_q,
                bytes_per_vector=bpv, compression=f32_bpv / bpv,
                recall_at_k=rec, impl=impl,
                parity_violations=violations)
        out["parity_violations"] += violations
    return out


def _quantized_payload(res: dict) -> dict:
    """Point key: ``quant@<scheme>W<width>k<k>`` (the kernel mode's
    ``@W<width>k<k>`` convention, prefixed by scheme); every point carries
    ``bytes_per_vector``, and ``parity_violations`` totals the file-level
    gate CI trips on."""
    points = sorted(kv for kv in res.items() if isinstance(kv[0], tuple))
    out = {f"quant@{s}W{w}k{k}": point for (s, w, k), point in points}
    out["parity_violations"] = res["parity_violations"]
    return out


# ------------------------------------------------------------- open mode ----

def make_tenant_workload(x, metric, requests: int, tenants: int = 2,
                         heavy_frac: float = 1 / 16, seed: int = 7):
    """Skewed multi-tenant request stream for the fairness bench.

    Tenant ``heavy`` issues *sparse* heavy-diversification requests
    (phi ~ medium eps, k=10) — the expensive tail the paper's cost
    asymmetry produces; tenant ``light`` floods cheap low-eps k=5 requests.
    Under FIFO the sparse tenant's occasional request queues behind the
    flood; a fair policy should not let the flood starve it. The default
    ``heavy_frac`` keeps the heavy tenant's *work* share (request rate x
    per-request expansions, ~8x a light request's) well under half the
    system, so a work-fair scheduler has slack to insulate it — a heavy
    tenant offering *more* than its fair share gets throttled instead,
    which is the policy working as designed, not the showcase. With
    ``tenants > 2`` the extra tenants round-robin over the light stream
    (generic smoke shape). Returns (queries, ks, epss, heavy_mask, names).
    """
    rng = np.random.default_rng(seed)
    queries = D.queries_for(x, requests)
    eps_light = D.calibrate_eps(x, metric, D.PHI_TARGETS["low"])
    eps_heavy = D.calibrate_eps(x, metric, D.PHI_TARGETS["medium"])
    heavy = rng.random(requests) < heavy_frac
    if not heavy.any():
        heavy[requests // 2] = True   # the bench needs both tenants present
    ks = np.where(heavy, 10, 5)
    epss = np.where(heavy, eps_heavy, eps_light)
    if tenants <= 2:
        names = np.where(heavy, "heavy", "light")
    else:
        light_name = np.array([f"light{i % (tenants - 1)}"
                               for i in range(requests)])
        names = np.where(heavy, "heavy", light_name)
    return queries, ks, epss, heavy, names


def make_zipf_workload(x, metric, requests: int, zipf: float,
                       base: int | None = None, seed: int = 7):
    """Zipf-duplicated request trace — production traffic's shape.

    Draws each arrival's *identity* from a Zipf-like law over ``base``
    distinct queries: query rank ``r`` (1-based) arrives with probability
    proportional to ``r ** -zipf``, so a handful of hot queries repeat many
    times while the tail stays cold. ``zipf`` is the skew parameter
    (``1.0`` ≈ classic web-traffic skew; higher = hotter head; ``0.0`` is
    uniform duplication over the base pool). The base pool and its per-query
    ``(k, eps)`` parameters come from :func:`make_skewed_workload` with the
    same ``seed``, and duplicates share their base query's parameters
    exactly (a semantic cache key requires it) — so the trace is fully
    pinned by ``(requests, zipf, base, seed)``. Returns
    ``(queries, ks, epss, ranks)`` where ``ranks[i]`` is arrival ``i``'s
    base-pool index (duplication ground truth for hit-rate accounting).
    """
    base = base or max(requests // 4, 8)
    bq, bks, bepss, _ = make_skewed_workload(x, metric, base, seed)
    rng = np.random.default_rng(seed + 1)
    weights = np.arange(1, base + 1, dtype=np.float64) ** -float(zipf)
    ranks = rng.choice(base, size=requests, p=weights / weights.sum())
    return bq[ranks], bks[ranks], bepss[ranks], ranks


def _backend_scheduler_factory(kind: str, graph, x, metric, lanes: int,
                               max_k: int, ef: int, max_pending: int,
                               history: int, mesh_world: dict,
                               policy=lambda: "fifo", cache_size: int = 0):
    """Returns ``make(shed) -> LaneScheduler`` for one backend kind — the
    LaneBackend protocol in action: same scheduler, different engine.
    ``kind`` is ``engine`` or ``sharded-{scratch,beam}`` (the ShardedEngine
    resume mode). The sharded index/mesh are built once into ``mesh_world``,
    not per load point (jit caches are process-global, so later points also
    start warm). ``policy`` is a zero-arg factory returning a policy spec
    (name or configured ``AdmissionPolicy``), called once per scheduler —
    policies hold per-scheduler queue state, so load points never share an
    instance. ``cache_size > 0`` builds each scheduler with a semantic
    result cache of that capacity over the backend's corpus; ``make`` takes
    an optional override (``make(shed, cache_size=0)`` for the no-cache
    parity twin)."""
    if kind == "engine":
        return lambda shed, cache_size=cache_size: LaneScheduler(
            graph, num_lanes=lanes, max_k=max_k, default_ef=ef,
            max_pending=max_pending, history=history, prewarm=False,
            shed=shed, policy=policy(), cache_size=cache_size)
    resume = kind.split("-", 1)[1]
    if not mesh_world:
        import jax

        from repro.compat import make_mesh
        from repro.sharded_search import build_sharded_index

        shards = 1 << (jax.device_count().bit_length() - 1)  # pow2 <= devs
        n = (x.shape[0] // shards) * shards
        mesh_world["index"] = build_sharded_index(np.asarray(x[:n]), shards,
                                                  metric, M=12)
        mesh_world["mesh"] = make_mesh((shards,), ("data",))
        mesh_world["xs"] = x[:n]
    from repro.sharded_search import ShardedEngine

    return lambda shed, cache_size=cache_size: LaneScheduler(
        backend=ShardedEngine(mesh_world["index"], mesh_world["xs"],
                              mesh_world["mesh"], num_lanes=lanes,
                              max_k=max_k, resume=resume),
        max_pending=max_pending, history=history, prewarm=False, shed=shed,
        policy=policy(), cache_size=cache_size)


def make_slo_shed(slo: float):
    """Shed-at-submit policy: drop a request when the queue's expected wait
    (pending backlog x recent mean service time / lanes) already exceeds the
    SLO — the 'shed heavy load before it queues' half of SLO serving."""
    def shed(req, sched) -> bool:
        done = list(sched.completed)
        if not done:
            return False
        mean_svc = float(np.mean([r.service for r in done[-64:]]))
        expected_wait = len(sched.pending) * mean_svc / sched.num_lanes
        return expected_wait > slo
    return shed


def _audit_cache_hits(sched, hits) -> int:
    """Independent revalidation-soundness audit of served cache hits.

    For each hit, rescores the entry's recorded frontier against the *live*
    query with the oracle similarity (``repro.core.similarity.query_sim``,
    not the cache's kernel path) and re-runs ``theorem2_recheck``; a served
    hit whose recheck fails, or whose served ids are not the recheck's
    selected set, is a soundness violation (contract 14 — nonzero fails
    the producing job)."""
    from repro.core import theorems
    from repro.core.similarity import query_sim

    vectors = sched.cache.vectors
    metric = sched.cache.metric
    violations = 0
    for r in hits:
        e = r.cache_entry
        valid = e.cand_ids >= 0
        vecs = vectors[np.maximum(e.cand_ids, 0)]
        sc = np.asarray(query_sim(jnp.asarray(r.q), jnp.asarray(vecs),
                                  metric), np.float32)
        sc = np.where(valid, sc, -np.inf).astype(np.float32)
        order = np.argsort(-sc, kind="stable")
        certified, sel = theorems.theorem2_recheck(
            vectors, metric, e.cand_ids[order], sc[order], e.eps, e.k)
        if not certified or set(map(int, sel)) != set(map(int,
                                                         r.result.ids)):
            violations += 1
    return violations


def _cache_parity_check(make_sched, queries, ks, epss, ef) -> int:
    """Bit-parity gate: on a zero-duplicate (all-distinct) trace, a cached
    scheduler must return exactly what an uncached one does — cache-miss
    fall-through changes nothing (and with distinct queries the cache must
    not hit). Returns the number of mismatched requests."""
    plain = make_sched(None, cache_size=0)
    cached = make_sched(None)
    res_a = plain.run(queries, ks, epss, efs=ef)
    res_b = cached.run(queries, ks, epss, efs=ef)
    bad = 0
    for a, b in zip(res_a, res_b):
        if a is None or b is None:
            bad += (a is None) != (b is None)
            continue
        if (not np.array_equal(a.ids, b.ids)
                or not np.array_equal(a.scores, b.scores)):
            bad += 1
    bad += cached.total_cache_hits   # distinct queries must never hit
    return bad


def run_open(n: int, requests: int, lanes: int, ef: int, qps_list,
             backends=("engine",), slo: float | None = None,
             tenants: int = 1, policy: str = "fifo",
             heavy_frac: float = 1 / 16, seed: int = 7,
             zipf: float = 0.0, cache_size: int = 0) -> dict:
    if "engine" in backends:
        graph, x, metric = D.load_graph("deep-like", n=n)
    else:   # sharded-only: the single-host graph would be dead weight
        graph, (x, metric) = None, D.make_dataset("deep-like", n=n)
    multi = tenants > 1 or policy != "fifo"
    if zipf and multi:
        raise ValueError("--zipf drives the single-tenant fifo duplicated "
                         "trace; combine it with --tenants/--policy once a "
                         "workload needs both")
    if zipf:
        # Zipf-duplicated trace (with or without --cache-size): pinned by
        # (requests, zipf, seed), duplicates share (k, eps) exactly
        queries, ks, epss, _ranks = make_zipf_workload(x, metric, requests,
                                                       zipf, seed=seed)
        names = np.full(requests, "default")
    elif multi:
        queries, ks, epss, heavy, names = make_tenant_workload(
            x, metric, requests, tenants=max(tenants, 2),
            heavy_frac=heavy_frac, seed=seed)
    else:   # the PR 4 trace, unchanged — trend numbers stay comparable
        queries, ks, epss, heavy = make_skewed_workload(x, metric, requests,
                                                        seed)
        names = np.full(requests, "default")
    max_k = int(ks.max())
    warmup = min(lanes, requests)
    # --policy slo_cost repurposes --slo as the per-tenant latency budget;
    # otherwise --slo installs the legacy shed-at-submit callback
    slo_budget = slo if slo is not None else 2.0
    if policy == "slo_cost":
        from repro.serve.policies import SloCostPolicy
        if slo is None:
            print(f"# --policy slo_cost without --slo: using the default "
                  f"{slo_budget:g}s per-tenant budget", flush=True)
        policy_spec, shed_cb = lambda: SloCostPolicy(budget=slo_budget), None
    else:
        policy_spec = lambda: policy
        shed_cb = make_slo_shed(slo) if slo else None
    out = {}
    # the sharded backend runs once per resume mode: scratch restarts every
    # budget round cold, beam resumes the shard-local beams — the
    # expansions counters below are the work resumption saves
    kinds = [k2 for kind in backends for k2 in
             (("sharded-scratch", "sharded-beam") if kind == "sharded"
              else (kind,))]
    mesh_world: dict = {}
    for kind in kinds:
        # history must retain this run's requests plus the warmup pass, or
        # the served count below undercounts and trips a false violation
        make_sched = _backend_scheduler_factory(
            kind, graph, x, metric, lanes, max_k, ef, max_pending=requests,
            history=requests + warmup, mesh_world=mesh_world,
            policy=policy_spec, cache_size=cache_size)
        parity_bad = 0
        if cache_size:
            # zero-duplicate bit-parity gate, once per backend kind: the
            # cached scheduler must be invisible on an all-distinct trace
            pq, pks, pepss, _ = make_skewed_workload(
                x, metric, min(requests, 2 * lanes), seed + 101)
            parity_bad = _cache_parity_check(make_sched, pq, pks, pepss, ef)
            if parity_bad:
                print(f"# CACHE PARITY VIOLATION {kind}: {parity_bad} "
                      "mismatches on a zero-duplicate trace")
        if multi:
            # absorb the XLA compiles in a throwaway fifo pass first (jit
            # caches are process-global): the measured schedulers' cost
            # models must learn *warm* seconds-per-expansion, or slo_cost
            # sheds everything off compile-time-polluted predictions
            throwaway = _backend_scheduler_factory(
                kind, graph, x, metric, lanes, max_k, ef,
                max_pending=requests, history=warmup,
                mesh_world=mesh_world)(None)
            throwaway.run(queries[:warmup], ks[:warmup], epss[:warmup],
                          efs=ef)
        for qps in qps_list:
            sched = make_sched(shed_cb)
            # warm the compile caches outside the timed open-loop run so the
            # first arrivals don't pay XLA traces (it also calibrates the
            # cost model's seconds-per-expansion before real load arrives)
            sched.run(queries[:warmup], ks[:warmup], epss[:warmup], efs=ef,
                      tenants=names[:warmup])
            rng = np.random.default_rng(seed)
            arrivals = np.cumsum(rng.exponential(1.0 / qps, requests))
            shed_n = 0
            deferred_n = 0          # terminally deferred (never admitted)
            defer_retry: list = []  # [request index, giving-up deadline]
            # rid -> absolute first-offer time: a deferred-then-retried
            # request gets a fresh Request (fresh t_submit) on resubmit, so
            # client-perceived latency must be measured from the *first*
            # offer or slo_cost's deferrals would be excluded from p50/p99
            first_offer: dict = {}

            def offer(j) -> str:
                s0, d0 = sched.total_shed, sched.total_deferred
                r = sched.try_submit(queries[j], int(ks[j]), float(epss[j]),
                                     ef=ef, tenant=str(names[j]))
                if r is not None:
                    first_offer[r.rid] = t0 + arrivals[j]
                    return "ok"
                if sched.total_shed > s0:
                    return "shed"
                if sched.total_deferred > d0:
                    return "deferred"
                return "saturated"   # max_pending == requests: unreachable

            t0 = time.monotonic()
            i = 0
            while (i < requests or defer_retry or sched.pending
                   or sched.inflight):
                now = time.monotonic() - t0
                while i < requests and arrivals[i] <= now:
                    got = offer(i)
                    if got == "shed":
                        shed_n += 1
                    elif got in ("deferred", "saturated"):
                        defer_retry.append([i, arrivals[i] + slo_budget])
                    i += 1
                still = []
                for j, deadline in defer_retry:
                    if now > deadline:
                        deferred_n += 1   # gave up: SLO unmeetable anyway
                        continue
                    got = offer(j)
                    if got == "shed":
                        shed_n += 1
                    elif got != "ok":
                        still.append([j, deadline])
                defer_retry = still
                if sched.pending or sched.inflight:
                    sched.pump()
                elif i < requests:
                    time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
                elif defer_retry:
                    time.sleep(0.001)   # drained: only deadlines remain
            stats = sched.latency_stats()
            # percentiles over *this run's* requests only (the warmup pass
            # sits in the scheduler's history window too) — computed with
            # the exact helpers the scheduler itself uses (both timestamps
            # come from time.monotonic), so the two can never drift
            open_reqs = [r for r in sched.completed if r.t_submit >= t0]
            # latency/wait from the request's *first* offer (== t_submit
            # except for deferred-then-retried requests, whose resubmitted
            # Request would otherwise hide the time spent deferred)
            lats = [r.t_done - first_offer.get(r.rid, r.t_submit)
                    for r in open_reqs]
            waits = [r.t_admit - first_offer.get(r.rid, r.t_submit)
                     for r in open_reqs]
            # cache hits complete without a lane: count them apart from
            # search-served requests (the conservation law below bills
            # them separately), but their latencies stay in the pooled
            # percentiles — the latency win is the headline
            hit_reqs = [r for r in open_reqs if r.cache_hit]
            hits_n = len(hit_reqs)
            served = len(open_reqs) - hits_n
            # real per-lane counters out of the harvested SearchStats (the
            # sharded backend threads them from the resumable beam state)
            exp_total = sum(int(r.result.stats.expansions)
                            for r in open_reqs if r.result is not None)
            rounds_total = sum(int(r.result.stats.search_calls)
                               for r in open_reqs if r.result is not None)
            tag = (f"open/{kind}/qps{qps:g}" + (f"/{policy}" if multi else "")
                   + (f"/zipf{zipf:g}" if zipf else "")
                   + (f"/cache{cache_size}" if cache_size else ""))
            emit(f"{tag}/p50_latency", percentile(lats, 50) * 1e3, "ms")
            emit(f"{tag}/p99_latency", percentile(lats, 99) * 1e3,
                 f"ms;p99_wait_ms={percentile(waits, 99) * 1e3:.1f};"
                 f"fairness={jain_fairness(lats):.3f}")
            emit(f"{tag}/served", served,
                 f"of {requests} offered;shed={shed_n};"
                 f"deferred={deferred_n};cache_hits={hits_n}")
            emit(f"{tag}/expansions", exp_total,
                 f"cumulative;rounds={rounds_total};per_round="
                 f"{exp_total / max(rounds_total, 1):.1f}")
            point = dict(
                p50=percentile(lats, 50), p99=percentile(lats, 99),
                p99_wait=percentile(waits, 99), served=served, shed=shed_n,
                expansions_total=exp_total, rounds_total=rounds_total,
                expansions_per_round=exp_total / max(rounds_total, 1),
                throughput=(len(open_reqs)
                            / max(max(r.t_done or 0.0
                                      for r in open_reqs) - t0, 1e-9)
                            if open_reqs else 0.0))
            if zipf:
                point["zipf"] = zipf
            if cache_size:
                hit_lats = [r.t_done - first_offer.get(r.rid, r.t_submit)
                            for r in hit_reqs]
                soundness_bad = _audit_cache_hits(sched, hit_reqs)
                point.update(
                    cache_size=cache_size, cache_hits=hits_n,
                    hit_rate=hits_n / requests,
                    hit_p50_ms=percentile(hit_lats, 50) * 1e3,
                    soundness_violations=soundness_bad,
                    parity_violations=parity_bad)
                emit(f"{tag}/hit_rate", hits_n / requests,
                     f"hits={hits_n};hit_p50_ms="
                     f"{percentile(hit_lats, 50) * 1e3:.2f};"
                     f"soundness_violations={soundness_bad}")
                if soundness_bad or parity_bad:
                    print(f"# CACHE SOUNDNESS/PARITY VIOLATION {kind}@"
                          f"{qps}: soundness={soundness_bad} "
                          f"parity={parity_bad}")
                    point["violation"] = True
            if multi:
                per_tenant = {}
                for tname in sorted(set(str(t) for t in names)):
                    trs = [r for r in open_reqs if r.tenant == tname]
                    tl = [r.t_done - first_offer.get(r.rid, r.t_submit)
                          for r in trs]
                    per_tenant[tname] = dict(
                        served=len(trs),
                        p50=percentile(tl, 50), p99=percentile(tl, 99),
                        mean=float(np.mean(tl)) if tl else 0.0,
                        fairness=jain_fairness(tl))
                    emit(f"{tag}/tenant/{tname}/p99",
                         percentile(tl, 99) * 1e3,
                         f"ms;served={len(trs)};"
                         f"jain={jain_fairness(tl):.3f}")
                t_means = [t["mean"] for t in per_tenant.values()
                           if t["served"]]
                point.update(
                    policy=policy, deferred=deferred_n,
                    tenants=per_tenant,
                    tenant_fairness=jain_fairness(t_means),
                    calibration_error=stats["cost_calibration_error"])
                emit(f"{tag}/tenant_fairness",
                     jain_fairness(t_means),
                     f"jain_over_tenant_means;calibration_error="
                     f"{stats['cost_calibration_error']:.3f}")
            if served + shed_n + deferred_n + hits_n != requests:
                print(f"# OPEN-LOOP ACCOUNTING VIOLATION {kind}@{qps}: "
                      f"{served} served + {shed_n} shed + {deferred_n} "
                      f"deferred + {hits_n} hits != {requests}")
                point["violation"] = True
            out[(kind, qps)] = point
    return out


# ------------------------------------------------------------- churn mode ---

def _audit_live_hit(db, r) -> int:
    """Independent staleness audit of one served cache hit, run at serve
    time (before any later write): the served set must be live, and an
    oracle-rescored recheck of the independently re-merged frontier
    (stored entry frontier minus tombstones, plus the live delta) must
    re-certify and reselect exactly the served ids."""
    from repro.core import theorems
    from repro.core.similarity import query_sim

    e = r.cache_entry
    idx = db.index
    served = np.asarray(r.result.ids)
    served = served[served >= 0]
    if served.size == 0 or idx.deleted[served].any():
        return 1
    cand = np.asarray(e.cand_ids[e.cand_ids >= 0], np.int64)
    cand = cand[~idx.deleted[cand]]
    merged = np.unique(np.concatenate([cand, idx.delta_ids()]))
    sc = np.asarray(query_sim(jnp.asarray(np.asarray(r.q, np.float32)),
                              jnp.asarray(idx.float_view()[merged]),
                              idx.metric), np.float32)
    order = np.lexsort((merged, -sc))
    ok, sel = theorems.theorem2_recheck(idx.float_view(), idx.metric,
                                        merged[order], sc[order],
                                        e.eps, e.k)
    sel = np.asarray(sel)
    if not ok or set(map(int, sel[sel >= 0])) != set(map(int, served)):
        return 1
    return 0


def run_churn(n: int, requests: int, lanes: int, ef: int, qps: float = 8.0,
              write_frac: float = 0.1, cache_size: int = 0,
              oracle_samples: int = 8, seed: int = 7) -> dict:
    """Open-loop Poisson reads with a ``write_frac`` fraction of interleaved
    writes (alternating upserts/deletes) against one ``DiverseVectorDB`` —
    the live serving path of the mutable index, audited end to end.

    The write-op log is replayed to reconstruct each request's visible
    corpus (its harvest-tagged version's row range + deletion bitmap), and
    the run gates on:

    * mixed-epoch violations — a served id outside the tagged version's
      rows, or tombstoned there (contract 15);
    * certificate-soundness violations — a certified lane whose merged
      frontier fails an independent Theorem-2 recheck against its
      version's corpus, or reselects different ids;
    * stale cache hits — audited at serve time by :func:`_audit_live_hit`;
    * conservation — served + shed + deferred + hits == offered reads and
      applied == submitted writes;
    * recall — on ``oracle_samples`` sampled requests, served-set recall
      vs the certified diverse oracle over that request's visible rows
      must be within 1% of a rebuild-from-scratch twin (fresh graph over
      the same visible rows, same (k, eps, ef) budget).
    """
    from repro.core import theorems
    from repro.core.baselines import div_astar_oracle
    from repro.core.pss import pss
    from repro.db import DiverseVectorDB
    from repro.index.flat import build_knn_graph

    x, metric = D.make_dataset("deep-like", n=n)
    queries, ks, epss, _ = make_skewed_workload(x, metric, requests, seed)
    max_k = int(ks.max())
    write_every = max(1, int(round(1.0 / max(write_frac, 1e-9))))
    n_upserts = max(1, (requests // write_every + 1) // 2)
    db = DiverseVectorDB(
        x, metric, M=12, num_lanes=lanes, max_k=max_k, default_ef=ef,
        cache_size=cache_size, delta_capacity=max(2, n_upserts),
        background_rebuild=False, prewarm=False,
        scheduler_kw=dict(max_pending=requests + 8,
                          history=requests + lanes))
    rng = np.random.default_rng(seed)
    warmup = min(lanes, requests)
    db.scheduler.run(queries[:warmup], ks[:warmup], epss[:warmup], efs=ef)

    # write-op log: version -> (n_total, deleted bitmap) after every event
    # that can change the live view (writes here, swaps inside pump)
    snaps: dict = {}

    def snap():
        v = db.index.version
        if v not in snaps:
            snaps[v] = (db.index.n_total, db.index.deleted.copy())

    def poll():
        snap()
        for r in reqs:
            if (r is not None and r.result is not None
                    and r.lane is not None and id(r) not in metas):
                metas[id(r)] = db.backend.last_meta[r.lane]
                frontiers[id(r)] = db.backend.last_candidates[r.lane]

    snap()
    reqs, metas, frontiers = [], {}, {}
    arrivals = np.cumsum(rng.exponential(1.0 / qps, requests))
    shed_n = deferred_n = hits_n = stale_hits = 0
    upserts_done = deletes_done = 0
    write_flip = 0
    retry: list = []

    def do_write():
        nonlocal upserts_done, deletes_done, write_flip
        if write_flip % 2 == 0:
            base = rng.integers(0, len(x), 2)
            db.upsert(x[base] + rng.normal(size=(2, x.shape[1]))
                      .astype(np.float32) * 0.01)
            upserts_done += 1
        else:
            live = np.flatnonzero(~db.index.deleted)
            db.delete(rng.choice(live, 1))
            deletes_done += 1
        write_flip += 1
        snap()

    def offer(j) -> str:
        nonlocal hits_n, stale_hits
        s0, d0 = db.scheduler.total_shed, db.scheduler.total_deferred
        r = db.scheduler.try_submit(queries[j], int(ks[j]),
                                    float(epss[j]), ef=ef)
        if r is not None:
            reqs.append(r)
            if r.cache_hit:
                hits_n += 1
                stale_hits += _audit_live_hit(db, r)
            return "ok"
        if db.scheduler.total_shed > s0:
            return "shed"
        return "deferred" if db.scheduler.total_deferred > d0 \
            else "saturated"

    t0 = time.monotonic()
    i = 0
    while (i < requests or retry or db.scheduler.pending
           or db.scheduler.inflight or db.scheduler.write_queue):
        now = time.monotonic() - t0
        while i < requests and arrivals[i] <= now:
            if (i + 1) % write_every == 0:
                do_write()
            got = offer(i)
            if got == "shed":
                shed_n += 1
            elif got != "ok":
                retry.append(i)
            i += 1
        still = []
        for j in retry:
            got = offer(j)
            if got == "shed":
                shed_n += 1
            elif got != "ok":
                still.append(j)
        retry = still
        if db.scheduler.pending or db.scheduler.inflight:
            db.scheduler.pump()
            poll()
        elif i < requests:
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
    db.scheduler.drain()
    poll()

    stats = db.stats()
    open_reqs = [r for r in db.scheduler.completed if r.t_submit >= t0]
    lats = [r.t_done - r.t_submit for r in open_reqs]
    served = len(open_reqs) - hits_n

    # -- write-log replay audits --------------------------------------------
    mixed_epoch = cert_bad = 0
    audited = []
    for r in open_reqs:
        if r.cache_hit or r.result is None:
            continue
        meta = metas.get(id(r))
        if meta is None:           # lane reharvested before the poll saw it
            continue
        v = max(ver for ver in snaps if ver <= meta["version"])
        n_at, dele_at = snaps[v]
        ids = np.asarray(r.result.ids)
        ids = ids[ids >= 0]
        if ids.size == 0 or (ids >= n_at).any() or dele_at[ids].any():
            mixed_epoch += 1
            continue
        audited.append((r, v, n_at, dele_at))
        if r.result.stats.certified:
            fr = frontiers.get(id(r))
            ok, sel = theorems.theorem2_recheck(
                db.index.float_view()[:n_at], metric, fr[0], fr[1],
                float(r.eps), int(r.k))
            sel = np.asarray(sel)
            if not ok or not np.array_equal(sel, np.asarray(r.result.ids)):
                cert_bad += 1

    # -- sampled recall vs the rebuild-from-scratch twin ---------------------
    recall_live = recall_scratch = 1.0
    n_sampled = 0
    if audited and oracle_samples:
        idxs = np.unique(np.linspace(0, len(audited) - 1,
                                     min(oracle_samples, len(audited)))
                         .astype(int))
        n_sampled = len(idxs)
        rl, rs = [], []
        twins: dict = {}     # version -> scratch graph (samples share them)
        for j in idxs:
            r, v, n_at, dele_at = audited[j]
            live_rows = np.flatnonzero(~dele_at)
            x_live = db.index.float_view()[:n_at][live_rows]
            k, eps = int(r.k), float(r.eps)
            oracle = div_astar_oracle(x_live, metric, r.q, k, eps,
                                      X=min(512, len(x_live)))
            o_ids = np.asarray(oracle.ids)
            truth = set(map(int, live_rows[o_ids[o_ids >= 0]]))
            if v not in twins:
                twins[v] = build_knn_graph(x_live, metric=metric, M=12)
            tw = pss(twins[v], np.asarray(r.q), k, eps, ef=ef)
            t_ids = np.asarray(tw.ids)
            twin = set(map(int, live_rows[t_ids[t_ids >= 0]]))
            mine = set(map(int, np.asarray(r.result.ids)))
            mine.discard(-1)
            rl.append(len(mine & truth) / k)
            rs.append(len(twin & truth) / k)
        recall_live, recall_scratch = float(np.mean(rl)), float(np.mean(rs))

    conserve_ok = (served + shed_n + deferred_n + hits_n == requests
                   and stats["writes_applied"] == stats["writes"]
                   and stats["writes_pending"] == 0)
    recall_ok = recall_live >= recall_scratch - 0.01
    violation = bool(mixed_epoch or cert_bad or stale_hits
                     or not conserve_ok or not recall_ok)
    tag = (f"churn/qps{qps:g}/w{write_frac:g}"
           + (f"/cache{cache_size}" if cache_size else ""))
    emit(f"{tag}/p50_latency", percentile(lats, 50) * 1e3, "ms")
    emit(f"{tag}/p99_latency", percentile(lats, 99) * 1e3,
         f"ms;fairness={jain_fairness(lats):.3f}")
    emit(f"{tag}/served", served,
         f"of {requests} offered;shed={shed_n};hits={hits_n};"
         f"upserts={upserts_done};deletes={deletes_done};"
         f"swaps={stats['epoch_swaps']}")
    emit(f"{tag}/recall_live", recall_live,
         f"scratch_twin={recall_scratch:.3f};samples={n_sampled}")
    emit(f"{tag}/violations", int(violation),
         f"mixed_epoch={mixed_epoch};cert={cert_bad};"
         f"stale_hits={stale_hits};conservation_ok={conserve_ok}")
    if violation:
        print(f"# CHURN VIOLATION: mixed_epoch={mixed_epoch} "
              f"cert={cert_bad} stale_hits={stale_hits} "
              f"conservation={conserve_ok} recall_live={recall_live:.3f} "
              f"vs scratch={recall_scratch:.3f}")
    point = dict(
        p50=percentile(lats, 50), p99=percentile(lats, 99),
        served=served, shed=shed_n, deferred=deferred_n,
        cache_hits=hits_n, write_frac=write_frac,
        writes=int(stats["writes"]), upserts=upserts_done,
        deletes=deletes_done, epoch_swaps=int(stats["epoch_swaps"]),
        cache_invalidations=int(stats["cache_invalidations"]),
        mixed_epoch_violations=mixed_epoch,
        cert_soundness_violations=cert_bad, stale_hits=stale_hits,
        recall_live=recall_live, recall_scratch=recall_scratch,
        index=stats["index"])
    if violation:
        point["violation"] = True
    return {(qps, write_frac, cache_size): point}


def _churn_payload(res: dict) -> dict:
    """Point key: ``churn@qps<q>@w<frac>``, suffixed ``@cache<size>`` when
    the semantic cache rides the churn run."""
    def key(qps, frac, cache):
        k = f"churn@qps{qps:g}@w{frac:g}"
        if cache:
            k += f"@cache{cache}"
        return k
    return {key(*params): point for params, point in sorted(res.items())}


_DIURNAL_PHASES = ("low", "peak", "cooldown")


def _drive_open_loop(db, queries, ks_, epss, arrivals, ef):
    """Poisson-arrival driver shared by the diurnal runs: offer each request
    at its arrival time (retrying backpressure), pump between arrivals, and
    capture every completed lane's candidate frontier for the Theorem-2
    audit. Returns ``(reqs, frontiers, shards_seen)``."""
    sched = db.scheduler
    reqs: dict = {}
    frontiers: dict = {}
    shards_seen = {int(db.backend.num_shards)}

    def poll():
        shards_seen.add(int(db.backend.num_shards))
        for j, r in reqs.items():
            if (r.result is not None and r.lane is not None
                    and j not in frontiers):
                frontiers[j] = db.backend.last_candidates[r.lane]

    retry: list = []
    t0 = time.monotonic()
    i, total = 0, len(queries)
    while i < total or retry or sched.pending or sched.inflight:
        now = time.monotonic() - t0
        while i < total and arrivals[i] <= now:
            r = sched.try_submit(queries[i], int(ks_[i]), float(epss[i]),
                                 ef=ef)
            if r is None:
                retry.append(i)
            else:
                reqs[i] = r
            i += 1
        still = []
        for j in retry:
            r = sched.try_submit(queries[j], int(ks_[j]), float(epss[j]),
                                 ef=ef)
            if r is None:
                still.append(j)
            else:
                reqs[j] = r
        retry = still
        if sched.pending or sched.inflight:
            sched.pump()
            poll()
        elif i < total:
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
    poll()
    return reqs, frontiers, shards_seen


def run_diurnal(n: int, lanes: int, ef: int, qps_low: float = 2.0,
                qps_peak: float = 16.0, phase_requests=(6, 24, 6),
                seed: int = 7) -> dict:
    """Diurnal load (low -> peak -> low qps) against an elastic mesh and a
    static small-mesh twin — PR 10's scale-event point (contract 16).

    Both runs serve the *same* Poisson arrival schedule through the same
    facade. The static twin stays on the 2-shard mesh; the elastic run
    starts there with the 4-shard target prepared, and the scheduler's
    ``ElasticPolicy`` must perform at least one grow during the peak and
    one shrink once the queue empties — in-flight lanes straddling both.
    Reported per phase: p50/p99 latency; per run: scale-event count and
    migration-pause ms. Gates (exit nonzero on any):

    * parity — every captured certified frontier passes an independent
      Theorem-2 recheck (resharding is a capacity knob, never a results
      knob), on both runs;
    * elasticity — the elastic run records >= 1 grow and >= 1 shrink;
    * capacity — peak-phase p99 with elastic must not exceed the static
      small-mesh baseline (the grow is what absorbs the burst);
    * conservation — served == offered on both runs.
    """
    import jax

    from repro.core import theorems
    from repro.db import DiverseVectorDB
    from repro.serve.scheduler import ElasticPolicy

    if jax.device_count() < 4:
        raise SystemExit(
            "--mode diurnal needs >= 4 devices for the 2 <-> 4 shard scale "
            "path; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    x, metric = D.make_dataset("deep-like", n=n)
    total = int(sum(phase_requests))
    queries, _, epss, _ = make_skewed_workload(x, metric, total, seed)
    k = 5
    ks_ = np.full(total, k)
    rng = np.random.default_rng(seed)
    gaps, phase_of = [], []
    for ph, (m, rate) in enumerate(zip(phase_requests,
                                       (qps_low, qps_peak, qps_low))):
        gaps.extend(rng.exponential(1.0 / rate, int(m)))
        phase_of.extend([ph] * int(m))
    arrivals = np.cumsum(gaps)

    def build(elastic: bool) -> DiverseVectorDB:
        policy = ElasticPolicy(shrink_sustain=4, cooldown=4) \
            if elastic else None
        return DiverseVectorDB(
            x, metric, shards=("auto" if elastic else 2), elastic=policy,
            num_lanes=lanes, max_k=k, M=8, background_rebuild=False,
            prewarm=True,
            backend_kw=dict(K0=16, resume="beam"),
            scheduler_kw=dict(max_pending=total + 8, history=total + lanes,
                              prewarm_capacity=n, prewarm_ks=(k,)))

    res: dict = {}
    static_peak_p99 = None
    for kind in ("static", "elastic"):
        db = build(kind == "elastic")
        reqs, frontiers, shards_seen = _drive_open_loop(
            db, queries, ks_, epss, arrivals, ef)
        sched = db.scheduler
        if kind == "elastic":
            for _ in range(24):      # idle pumps: let the shrink fire
                sched.pump()
                if any(e["to_shards"] < e["from_shards"]
                       for e in sched.scale_events):
                    break
            shards_seen.add(int(db.backend.num_shards))
        xv = db.index.float_view()
        cert_bad = audited = 0
        for j, r in reqs.items():
            if r.result is None or not r.result.stats.certified:
                continue
            fr = frontiers.get(j)
            if fr is None:           # lane reharvested before the poll
                continue
            audited += 1
            ok, sel = theorems.theorem2_recheck(xv, metric, fr[0], fr[1],
                                                float(r.eps), int(r.k))
            if not ok or not np.array_equal(np.asarray(sel),
                                            np.asarray(r.result.ids)):
                cert_bad += 1
        lats: dict = {ph: [] for ph in _DIURNAL_PHASES}
        for j, r in reqs.items():
            lats[_DIURNAL_PHASES[phase_of[j]]].append(
                r.t_done - r.t_submit)
        served = sum(1 for r in reqs.values() if r.result is not None)
        events = list(getattr(sched, "scale_events", []))
        grows = sum(1 for e in events if e["to_shards"] > e["from_shards"])
        shrinks = sum(1 for e in events
                      if e["to_shards"] < e["from_shards"])
        pauses_ms = [e["pause_s"] * 1e3 for e in events]
        peak_p99 = percentile(lats["peak"], 99)
        conserve_ok = served == total
        violation = bool(cert_bad or not conserve_ok)
        if kind == "static":
            static_peak_p99 = peak_p99
        else:
            if not (grows >= 1 and shrinks >= 1):
                violation = True
            if peak_p99 > static_peak_p99:
                violation = True
        point = dict(
            kind=kind, qps_low=qps_low, qps_peak=qps_peak,
            requests=total, served=served,
            phases={ph: dict(p50=percentile(lats[ph], 50),
                             p99=percentile(lats[ph], 99),
                             served=len(lats[ph]))
                    for ph in _DIURNAL_PHASES},
            scale_events=len(events), grow_events=grows,
            shrink_events=shrinks,
            migration_pause_ms_max=max(pauses_ms, default=0.0),
            migration_pause_ms_mean=float(np.mean(pauses_ms))
            if pauses_ms else 0.0,
            shards_seen=sorted(shards_seen),
            shards_final=int(db.backend.num_shards),
            cert_soundness_violations=cert_bad, audited=audited)
        if kind == "elastic":
            point["static_peak_p99"] = static_peak_p99
        if violation:
            point["violation"] = True
        tag = f"diurnal/qps{qps_low:g}-{qps_peak:g}/{kind}"
        for ph in _DIURNAL_PHASES:
            emit(f"{tag}/{ph}_p99", point["phases"][ph]["p99"] * 1e3,
                 f"ms;p50={point['phases'][ph]['p50'] * 1e3:.1f}ms;"
                 f"served={point['phases'][ph]['served']}")
        emit(f"{tag}/scale_events", len(events),
             f"grow={grows};shrink={shrinks};"
             f"pause_max={point['migration_pause_ms_max']:.2f}ms;"
             f"shards={sorted(shards_seen)}")
        emit(f"{tag}/violations", int(violation),
             f"cert={cert_bad};audited={audited};"
             f"conservation_ok={conserve_ok}")
        if violation:
            print(f"# DIURNAL VIOLATION [{kind}]: cert={cert_bad} "
                  f"conservation={conserve_ok} grow={grows} "
                  f"shrink={shrinks} peak_p99={peak_p99:.3f}s "
                  f"static_peak_p99={static_peak_p99}")
        res[(qps_low, qps_peak, kind)] = point
    return res


def _diurnal_payload(res: dict) -> dict:
    """Point key: ``diurnal@qps<low>-<peak>@<elastic|static>`` — the
    elastic run and its static small-mesh twin at the same arrival
    schedule sit side by side."""
    return {f"diurnal@qps{lo:g}-{hi:g}@{kind}": point
            for (lo, hi, kind), point in sorted(res.items())}


# -------------------------------------------------------------- trend json --

BENCH_SCHEMA = 2

_SKEWED_KEYS = ("p50_latency", "p99_latency", "p50_wait", "p99_wait",
                "throughput", "fairness", "certified_frac", "signatures")


def write_trend_json(path: str, mode: str, payload: dict) -> None:
    """Merge one mode's summary into the stable-schema trend file.

    The full field map lives in ``docs/BENCH_SCHEMA.md`` (version 2 since
    PR 5; ``schema_version`` gates compat — a file written under a
    different version is reset, never half-merged). Top-level ``modes``
    maps a bench mode to its summary dict — ``skewed`` keys the two
    admission regimes plus ``parity_violations``; ``open`` keys
    ``<kind>@qps<q>[@<policy>]`` load points, each with p50/p99/p99_wait
    seconds, served/shed counts, throughput, the expansion counters
    (``expansions_total``, ``rounds_total``, ``expansions_per_round``)
    that separate sharded-scratch from sharded-beam, and — for
    multi-tenant/policy points — ``policy``, ``deferred``, per-``tenants``
    latency/fairness, ``tenant_fairness`` and the cost model's
    ``calibration_error``. Repeated invocations with the same path
    accumulate modes, and points within a mode merge by key, so one
    artifact carries fifo and drr runs of the same load point side by side.
    """
    doc = {"schema_version": BENCH_SCHEMA, "bench": "batch_bench",
           "modes": {}}
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        if old.get("schema_version") == BENCH_SCHEMA:
            doc = old
    doc["modes"].setdefault(mode, {}).update(payload)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path} (modes: {sorted(doc['modes'])})", flush=True)


def _skewed_payload(res: dict) -> dict:
    out = {adm: {key: res[adm][key] for key in _SKEWED_KEYS}
           for adm in ("lockstep", "continuous")}
    out["parity_violations"] = res["parity_violations"]
    return out


def _open_payload(res: dict) -> dict:
    """Point key: ``<kind>@qps<q>``, suffixed ``@<policy>`` for
    multi-tenant/policy runs so fifo/drr runs of the same load point
    coexist in one file (re-running the same policy overwrites its key).
    Zipf-duplicated traces append ``@zipf<S>`` and cache-enabled runs
    ``@cache<size>``, so the cache point and its no-cache baseline at the
    same offered load sit side by side."""
    def key(kind, qps, point):
        k = f"{kind}@qps{qps:g}"
        if "policy" in point:
            k += f"@{point['policy']}"
        if point.get("zipf"):
            k += f"@zipf{point['zipf']:g}"
        if point.get("cache_size"):
            k += f"@cache{point['cache_size']}"
        return k
    return {key(kind, qps, point): point
            for (kind, qps), point in sorted(res.items())}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="engine",
                    choices=["engine", "skewed", "open", "kernel",
                             "quantized", "churn", "diurnal"])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (small n, few requests)")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="request count (all modes)")
    ap.add_argument("--lanes", type=int, default=None)
    ap.add_argument("--ef", type=int, default=10)
    ap.add_argument("--parity", default=None,
                    choices=["full", "sample", "off"])
    ap.add_argument("--qps", default=None,
                    help="offered load for --mode open (comma-separated "
                         "sweep, e.g. 2,8,32)")
    ap.add_argument("--backend", default="engine",
                    choices=["engine", "sharded", "both"],
                    help="LaneBackend(s) for --mode open")
    ap.add_argument("--slo", type=float, default=None,
                    help="latency SLO in seconds: installs the shed-at-"
                         "submit callback, or — with --policy slo_cost — "
                         "the per-tenant latency budget (default 2.0 "
                         "for slo_cost; --mode open)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="tenant count for --mode open: >1 switches to the "
                         "skewed heavy/light tenant mix and per-tenant "
                         "fairness reporting")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "drr", "slo_cost"],
                    help="admission policy for --mode open "
                         "(serve.policies)")
    ap.add_argument("--heavy-frac", type=float, default=1 / 16,
                    help="heavy tenant's request-rate share of the "
                         "multi-tenant mix (--mode open --tenants >1)")
    ap.add_argument("--zipf", type=float, default=0.0,
                    help="switch --mode open to the Zipf-duplicated query "
                         "trace with this skew parameter (1.0 ~ classic "
                         "web skew; usable with and without --cache-size)")
    ap.add_argument("--cache-size", type=int, default=0,
                    help="semantic result cache capacity for --mode open "
                         "(0 = no cache); reports hit-rate / hit_p50 and "
                         "gates on revalidation soundness + zero-duplicate "
                         "parity")
    ap.add_argument("--write-frac", type=float, default=0.1,
                    help="write fraction for --mode churn: one write "
                         "(alternating 2-row upsert / 1-row delete) per "
                         "1/frac offered reads")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="merge this run's summary into a stable-schema "
                         "trend JSON (skewed/open modes)")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.mode == "engine":
        kwargs = {}
        if args.n:
            kwargs["n"] = args.n
        if args.batch:
            kwargs["batch"] = args.batch
        run(**kwargs)
        return 0
    n = args.n or (2000 if args.tiny else D.N_DEFAULT)
    requests = args.batch or (16 if args.tiny else 64)
    lanes = args.lanes or (4 if args.tiny else 16)
    if args.mode == "quantized":
        res = run_quantized(n=n, B=(8 if args.tiny else 16),
                            ks=((5,) if args.tiny else (5, 10)),
                            reps=(3 if args.tiny else 10), seed=args.seed)
        if args.json:
            write_trend_json(args.json, "quantized",
                             _quantized_payload(res))
        return 1 if res["parity_violations"] else 0
    if args.mode == "kernel":
        res = run_kernel(n=n, B=(8 if args.tiny else 16),
                         widths=((128,) if args.tiny else (128, 256)),
                         reps=(5 if args.tiny else 20), seed=args.seed)
        if args.json:
            write_trend_json(args.json, "kernel", _kernel_payload(res))
        return 1 if res["parity_violations"] else 0
    if args.mode == "diurnal":
        # the peak must SATURATE the small mesh (arrivals well above its
        # lane throughput) so queueing dominates peak latency — that is
        # the regime where the grow pays; an unsaturated peak makes both
        # runs idle-bound and the comparison pure host noise
        qs = [float(q) for q in
              (args.qps or ("2,48" if args.tiny else "2,48")).split(",")]
        if len(qs) != 2 or qs[0] >= qs[1]:
            raise SystemExit("--mode diurnal takes --qps low,peak "
                             "(low < peak)")
        res = run_diurnal(n=n, lanes=lanes, ef=args.ef, qps_low=qs[0],
                          qps_peak=qs[1],
                          phase_requests=((4, 24, 4) if args.tiny
                                          else (8, 32, 8)),
                          seed=args.seed)
        if args.json:
            write_trend_json(args.json, "diurnal", _diurnal_payload(res))
        return 1 if any(v.get("violation") for v in res.values()) else 0
    if args.mode == "churn":
        qps = float((args.qps or ("4" if args.tiny else "8")).split(",")[0])
        res = run_churn(n=n, requests=requests, lanes=lanes, ef=args.ef,
                        qps=qps, write_frac=args.write_frac,
                        cache_size=args.cache_size,
                        oracle_samples=(4 if args.tiny else 8),
                        seed=args.seed)
        if args.json:
            write_trend_json(args.json, "churn", _churn_payload(res))
        return 1 if any(v.get("violation") for v in res.values()) else 0
    if args.mode == "open":
        qps_list = [float(q) for q in
                    (args.qps or ("4" if args.tiny else "2,8,32")).split(",")]
        backends = (("engine", "sharded") if args.backend == "both"
                    else (args.backend,))
        res = run_open(n=n, requests=requests, lanes=lanes, ef=args.ef,
                       qps_list=qps_list, backends=backends, slo=args.slo,
                       tenants=args.tenants, policy=args.policy,
                       heavy_frac=args.heavy_frac, seed=args.seed,
                       zipf=args.zipf, cache_size=args.cache_size)
        if args.json:
            write_trend_json(args.json, "open", _open_payload(res))
        return 1 if any(v.get("violation") for v in res.values()) else 0
    parity = args.parity or ("full" if args.tiny else "sample")
    res = run_skewed(n=n, requests=requests, lanes=lanes, ef=args.ef,
                     parity=parity, seed=args.seed)
    if args.json:
        write_trend_json(args.json, "skewed", _skewed_payload(res))
    if res["parity_violations"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
