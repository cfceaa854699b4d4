"""Bring-up smoke: drive the serving path once on a TPU and check the answers.

One process, no children. It builds ``DiverseVectorDB`` through its public
constructor and serves through ``search_batch`` (facade -> ``LaneScheduler``
-> ``ProgressiveEngine`` or ``ShardedEngine`` -> ``kernels/ops`` on the
compiled Pallas rung), then checks every served result against a host
reference that never touches the device.

Phases (data from ``benchmarks.datasets.make_dataset`` at ``--seed``; the
paper's three embedding widths and metrics), all three in the default run:

* ``deep-like``    d=96,  l2,  n=32768
* ``laion-like``   d=512, cos, n=16384
* ``txt2img-like`` d=200, ip,  n=16384

A phase builds the index with ``num_lanes=8, max_k=16`` and prewarm on,
then serves 16 queries (``datasets.queries_for``) at k=10 for phi medium and
phi high (eps from ``datasets.calibrate_eps``, the paper's Table II
settings). The deep phase on one chip then upserts 256 vectors (they fill
the delta, which starts a background rebuild at once), deletes 64 served
ids, runs ``rebuild(wait=True)`` and serves again at phi medium.
``--chips 4`` runs the deep phase sharded over a 4-chip mesh, without the
writes, and no other phase.

Host reference, per query, in float64 numpy: exact top-X by brute force,
then ``core.div_astar_ref.div_astar_ref``, X doubled until Theorem 2 holds
(the rule of ``core.baselines.div_astar_oracle``, without the device).

The run fails (exit 1, no JSON line) if in any phase mean recall@k < 0.9, a
served pair is more similar than eps + 1e-4, a result the engine marks
certified fails the host recheck (its Theorem-2 certificate re-proved in
float64 over the candidate frontier it was issued on, allowing float32
rounding: ``recheck_frontier``), or a deleted id is served. It exits 2 when
JAX finds no TPU.

Cuts, forced by the run's 1200 s limit on a one-chip host, which a cold
run spends mostly compiling and serving (the engine compiles its signature
ladder per phase):

* corpora of 32768 (deep) and 16384 (laion, txt2img) vectors, not the
  paper's 10^6: the index graph is built on the host (``index/flat.py``)
  and took 261 s at 65536 deep vectors on that host;
* 16 queries per batch, not 64;
* the after-write batch runs at phi medium only.

Times printed here are a smoke, not measurements.

    python chip_smoke.py                        # one chip, three phases
    python chip_smoke.py --chips 4              # deep phase, 4-chip mesh
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks import datasets as D  # noqa: E402
from repro.core.div_astar_ref import div_astar_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

PHASES = {
    "deep-like": dict(d=96, n=32768),
    "laion-like": dict(d=512, n=16384),
    "txt2img-like": dict(d=200, n=16384),
}
K = 10
LEVELS = ("medium", "high")
AFTER_WRITES_LEVELS = ("medium",)
NUM_QUERIES = 16
CAPACITY0 = 1024
BAND = 1e-4
MIN_RECALL = 0.9
_START = time.perf_counter()
#: seconds and count of XLA backend compiles so far (jax.monitoring)
_COMPILES = {"s": 0.0, "n": 0}


def _count_compile(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["s"] += secs
        _COMPILES["n"] += 1


def log(msg: str) -> None:
    """One progress line, stamped with the seconds since start (a smoke,
    not a measurement)."""
    print(f"[{time.perf_counter() - _START:7.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------- reference

def _sims64(q, x, metric):
    """float64 similarity of q[d] to rows of x[m, d] (core/similarity.py)."""
    dots = x @ q
    if metric == "ip":
        return dots
    if metric == "cos":
        return dots / (np.maximum(np.linalg.norm(x, axis=1), 1e-6)
                       * max(np.linalg.norm(q), 1e-6))
    return 1.0 - np.sqrt(np.maximum(((x - q) ** 2).sum(axis=1), 0.0))


def _pair_sims64(a, metric):
    dots = a @ a.T
    if metric == "ip":
        return dots
    if metric == "cos":
        nrm = np.maximum(np.linalg.norm(a, axis=1), 1e-6)
        return dots / np.outer(nrm, nrm)
    sq = (a * a).sum(axis=1)
    return 1.0 - np.sqrt(np.maximum(sq[:, None] + sq[None, :] - 2.0 * dots,
                                    0.0))


def host_oracle(x64, live, metric, q, k, eps, X=256):
    """Globally optimal diverse top-k over the live rows of ``x64``:
    returns (ids, total)."""
    s = _sims64(np.asarray(q, np.float64), x64, metric)
    s = np.where(live, s, -np.inf)
    n = int(live.sum())
    order = np.lexsort((np.arange(len(s)), -s))[:n]
    while True:
        X = min(X, n)
        ids = order[:X]
        sc = s[ids]
        adj = _pair_sims64(x64[ids], metric) > eps
        np.fill_diagonal(adj, False)
        sets, best, _ = div_astar_ref(sc, adj, k)
        gaps = [(best[k - 1] - best[i]) / (k - 1 - i)
                for i in range(k - 1) if np.isfinite(best[i])]
        holds = np.isfinite(best[k - 1]) and min(gaps, default=np.inf) > sc[-1]
        if holds or X >= n:
            return ids[sets[k - 1]], float(best[k - 1])
        X *= 2


def f32_total_tol(total, k):
    """Float32 rounding allowed on an engine total of k scores: k ulps of
    the total's magnitude. The engine picks and certifies sets in float32;
    two sets whose float64 totals differ by less than that are a tie to
    it."""
    return k * float(np.spacing(np.float32(max(abs(total), 1.0))))


def f32_sim_tol(eps):
    """Float32 rounding allowed on one similarity near eps: 16 ulps of its
    magnitude (covers the cancellation in the l2 form)."""
    return 16 * float(np.spacing(np.float32(max(abs(eps), 1.0))))


MAX_AMBIGUOUS = 6


def recheck_frontier(frontier, q, x64, metric, k, eps, served_total):
    """The engine's Theorem-2 certificate, re-proved in float64 over the
    candidate frontier it was issued on. Returns ``(ok, margin)``.

    The frontier's optimal diverse total must equal the served total and
    pass Theorem 2 against its last score, ties allowed (minValue equals
    s_K when the K-th candidate completes the optimal set: no set beyond
    the frontier is then better), both within ``f32_total_tol``. A pair
    whose float64 similarity lies within ``f32_sim_tol`` of eps may be an
    edge of the engine's float32 G^eps or not: the certificate passes if
    it holds for some choice of those pairs (every choice is tried up to
    ``MAX_AMBIGUOUS`` such pairs; beyond that, all edges or none).
    ``margin`` is the best choice's smaller slack (negative: how far it
    failed)."""
    ids = np.asarray(frontier[0])
    ids = ids[ids >= 0]
    if ids.size == 0:
        return False, -np.inf
    s = _sims64(np.asarray(q, np.float64), x64[ids], metric)
    order = np.lexsort((ids, -s))
    ids, s = ids[order], s[order]
    pair = _pair_sims64(x64[ids], metric)
    np.fill_diagonal(pair, -np.inf)
    band = f32_sim_tol(eps)
    sure = pair > eps + band
    amb = np.argwhere(np.triu(np.abs(pair - eps) <= band, 1))
    choices = (itertools.product((False, True), repeat=len(amb))
               if len(amb) <= MAX_AMBIGUOUS
               else [(False,) * len(amb), (True,) * len(amb)])
    tol = f32_total_tol(served_total, k)
    margin = -np.inf
    for edges in choices:
        adj = sure.copy()
        for (i, j), e in zip(amb, edges):
            adj[i, j] = adj[j, i] = e
        _, best, _ = div_astar_ref(s, adj, k)
        if not np.isfinite(best[k - 1]):
            continue
        gaps = [(best[k - 1] - best[i]) / (k - 1 - i)
                for i in range(k - 1) if np.isfinite(best[i])]
        margin = max(margin, min(min(gaps, default=np.inf) - s[-1] + 2 * tol,
                                 tol - abs(served_total - best[k - 1])))
    return bool(margin >= 0), float(margin)


def check_results(results, frontiers, queries, x64, live, metric, k, eps,
                  deleted):
    """Host checks of one served batch; returns a dict of counts."""
    recalls, violations, band, cert, del_hits = [], 0, 0, 0, 0
    fail_margins = []
    for res, frontier, q in zip(results, frontiers, queries):
        ids = np.asarray(res.ids)
        ids = ids[ids >= 0]
        del_hits += int(np.isin(ids, deleted).sum())
        truth, opt = host_oracle(x64, live, metric, q, k, eps)
        recalls.append(len(set(ids.tolist()) & set(truth.tolist()))
                       / max(len(truth), 1))
        pair = _pair_sims64(x64[ids], metric)[np.triu_indices(len(ids), 1)]
        violations += int((pair > eps + BAND).sum())
        band += int(((pair > eps) & (pair <= eps + BAND)).sum())
        if res.stats.certified:
            cert += 1
            got = float(_sims64(np.asarray(q, np.float64), x64[ids],
                                metric).sum())
            if frontier is None:
                # no frontier recorded: hold the claim to the global optimum
                margin = (got - opt + f32_total_tol(opt, k)
                          if len(ids) == len(truth) else -np.inf)
            else:
                _, margin = recheck_frontier(frontier, q, x64, metric, k,
                                             eps, got)
            if margin < 0:
                fail_margins.append(margin)
    return dict(recall=float(np.mean(recalls)), certified=cert / len(results),
                violations=violations, band_pairs=band,
                recheck_failures=len(fail_margins),
                recheck_failure_margins=fail_margins, deleted_hits=del_hits)


# -------------------------------------------------------------------- phases

def record_frontiers(db) -> dict:
    """Keep each harvested result's certificate frontier (the backend's
    ``last_candidates`` row at harvest), keyed by the result's id, so the
    host can re-prove served certificates."""
    frontiers: dict = {}
    harvest = db.backend.harvest

    def recording_harvest():
        out = harvest()
        for lane, result in out:
            frontiers[id(result)] = db.backend.last_candidates[lane]
        return out

    db.backend.harvest = recording_harvest
    return frontiers


def serve_and_check(db, tag, x, live, metric, queries, eps_by_level,
                    deleted, frontiers, levels=LEVELS):
    failures, served_ids = [], []
    x64 = x.astype(np.float64)
    for level in levels:
        eps = eps_by_level[level]
        t0 = time.perf_counter()
        c0 = dict(_COMPILES)
        frontiers.clear()
        results = db.search_batch(queries, k=K, eps=eps)
        serve_s = time.perf_counter() - t0
        compiles = (f"compiles={_COMPILES['n'] - c0['n']} "
                    f"compile_s={_COMPILES['s'] - c0['s']!r}")
        sigs = len(db.engine.signature_log)
        if any(r is None for r in results):
            failures.append(f"{tag}/{level}: a request was shed")
            continue
        served_ids.append(np.concatenate([np.asarray(r.ids) for r in results]))
        t0 = time.perf_counter()
        c = check_results(results, [frontiers.get(id(r)) for r in results],
                          queries, x64, live, metric, K, eps, deleted)
        log(f"{tag} phi={level} eps={eps!r} serve_s={serve_s!r} {compiles} "
            f"host_reference_s={time.perf_counter() - t0!r} "
            f"(smoke, not measured) signatures={sigs} "
            f"recall@{K}={c['recall']!r} "
            f"certified_share={c['certified']!r} "
            f"violations={c['violations']} band_pairs={c['band_pairs']} "
            f"recheck_failures={c['recheck_failures']} "
            f"deleted_hits={c['deleted_hits']}")
        if c["recheck_failure_margins"]:
            log(f"{tag} phi={level} recheck failure margins "
                f"{c['recheck_failure_margins']!r}")
        if c["recall"] < MIN_RECALL:
            failures.append(f"{tag}/{level}: recall {c['recall']!r} < "
                            f"{MIN_RECALL}")
        for key in ("violations", "recheck_failures", "deleted_hits"):
            if c[key]:
                failures.append(f"{tag}/{level}: {key}={c[key]}")
    return failures, served_ids


def run_phase(name, *, d, n, seed, shards=None, writes=False,
              impl="pallas"):
    """Build, serve, check one phase; returns a list of failure strings.
    ``impl`` is the kernel rung (``main`` sets the process default too)."""
    from repro.db import DiverseVectorDB
    from repro.index.flat import build_knn_graph

    x, metric = D.make_dataset(name, n=n, d=d, seed=seed)
    queries = D.queries_for(x, NUM_QUERIES, seed=seed + 7)
    eps_by_level = {lv: D.calibrate_eps(x, metric, D.PHI_TARGETS[lv])
                    for lv in LEVELS}
    tag = name if shards is None else f"{name}/shards={shards}"
    common = dict(num_lanes=8, max_k=16, prewarm=True, seed=seed)
    t0 = time.perf_counter()
    if shards is None:
        graph = build_knn_graph(x, metric, M=16, seed=seed)
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        # prewarm compiles the lanes' first capacity rung (k=10 at the
        # default ef=40 starts every lane at 1024); growth rungs compile on
        # first use
        db = DiverseVectorDB(
            index=graph, backend_kw={"kernel_impl": impl,
                                     "capacity0": CAPACITY0},
            scheduler_kw={"prewarm_capacity": CAPACITY0}, **common)
        log(f"{tag} n={n} d={d} metric={metric} build_s={build_s!r} "
            f"compile_s={time.perf_counter() - t0!r} (smoke, not measured)")
    else:
        db = DiverseVectorDB(x, metric, shards=shards,
                             scheduler_kw={"prewarm_ks": (K,)}, **common)
        log(f"{tag} n={n} d={d} metric={metric} build_and_compile_s="
            f"{time.perf_counter() - t0!r} (smoke, not measured)")
    frontiers = record_frontiers(db)
    live = np.ones(n, bool)
    failures, served = serve_and_check(db, tag, x, live, metric, queries,
                                       eps_by_level, np.zeros(0, np.int64),
                                       frontiers)
    if not writes:
        return failures

    # upsert 256 near-copies of corpus rows (they fill the delta, which
    # starts a rebuild at once), then delete 64 served ids: the rebuild
    # must honour the deletes that land after its snapshot
    rng = np.random.default_rng(seed + 1)
    base = x[rng.integers(0, n, 256)]
    new = (base + rng.normal(size=base.shape) * 0.05
           * np.abs(base).mean()).astype(np.float32)
    served = np.unique(np.concatenate(served))
    deleted = rng.choice(served[served >= 0], 64, replace=False)
    t0 = time.perf_counter()
    new_ids = db.upsert(new)
    db.delete(deleted)
    swapped = db.rebuild(wait=True)
    log(f"{tag} upserted={len(new_ids)} deleted={len(deleted)} "
        f"rebuild_s={time.perf_counter() - t0!r} swapped={swapped} "
        f"(smoke, not measured)")
    x_all = np.concatenate([x, new])
    live = np.ones(len(x_all), bool)
    live[deleted] = False
    more, _ = serve_and_check(db, f"{tag}/after-writes", x_all, live, metric,
                              queries, eps_by_level, deleted, frontiers,
                              AFTER_WRITES_LEVELS)
    if not swapped:
        more.append(f"{tag}: rebuild(wait=True) installed no swap")
    return failures + more


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the three phases on one chip; 4: the deep "
                         "phase only, sharded over a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    import jax
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    from repro.kernels import ops as kops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{devs[0].platform!r}; this smoke runs only on a TPU",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": args.chips}
    print(f"device platform={device['platform']} kind={device['kind']} "
          f"visible={len(devs)} used={args.chips}", flush=True)
    # every op on the compiled Pallas rung: no phase can take the jnp oracle
    kops.set_default_impl("pallas")

    failures = []
    if args.chips == 4:
        failures += run_phase("deep-like", **PHASES["deep-like"],
                              seed=args.seed, shards=4)
    else:
        for name in PHASES:
            failures += run_phase(name, **PHASES[name], seed=args.seed,
                                  writes=(name == "deep-like"))
    if failures:
        for f in failures:
            print(f"FAIL {f}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
