"""Elastic mesh serving on a forced 4-device host (contract 16).

Asserts the PR-10 acceptance criteria:

1. straddle parity — engine-direct lanes admitted mid-ladder straddle a
   grow (2 -> 4 shards) and a shrink (4 -> 2): every straddling lane
   finishes bit-identical to a fixed-mesh run of the final topology at the
   same final K-budget, or certified with an independent Theorem-2 recheck
   over its recorded candidate frontier (0 violations), and mean oracle
   recall is no worse than the fixed-mesh twin that never migrated;
2. elastic scheduling — a DiverseVectorDB with an ElasticPolicy under a
   traffic burst performs >= 1 grow and >= 1 shrink, admits at least one
   queued request into a lane on the NEW mesh mid-run, and completes every
   request certified;
3. recompile budget — with both targets prepared at construction, the
   frozen SignatureLog stays clean across the scale events (a scale event
   adds only planned signatures) and ``resume_jit_cache_sizes()`` is flat
   between the post-prewarm audit and the end of serving.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core.backend import LaneRequest, is_rescalable
from repro.core.baselines import div_astar_oracle
from repro.core.theorems import theorem2_recheck
from repro.db import DiverseVectorDB
from repro.serve.scheduler import ElasticPolicy
from repro.sharded_search import (ShardedEngine, build_sharded_index,
                                  sharded_diverse_search)
from repro.sharded_search.engine import LANE_RUN
from repro.sharded_search.search import resume_jit_cache_sizes

rng = np.random.default_rng(0)
N, d, k, eps = 2048, 16, 5, 4.0
X = rng.normal(size=(N, d)).astype(np.float32)
qs = rng.normal(size=(8, d)).astype(np.float32)
mesh2 = make_mesh((2,), ("data",))
mesh4 = make_mesh((4,), ("data",))
index2 = build_sharded_index(X, 2, "ip", M=8)

# --- 1. engine-direct straddles: grow 2 -> 4, then shrink 4 -> 2 ------------


def drive_straddle(start_index, start_mesh, to_shards, to_mesh):
    eng = ShardedEngine(start_index, jnp.asarray(X), start_mesh, num_lanes=4,
                        K0=16, max_k=8, resume="beam", record_candidates=True)
    eng.prepare_rescale(to_shards, to_mesh, prewarm=False)
    for lane in range(4):
        eng.admit(lane, LaneRequest(q=qs[lane], k=k, eps=eps,
                                    method="sharded"))
    eng.step()                                   # round 1 on the old mesh
    eng.harvest()
    straddled = [int(x) for x in np.flatnonzero(eng.status == LANE_RUN)]
    assert eng.rescale(to_shards), "rescale must report a topology change"
    assert eng.num_shards == to_shards
    out = {}
    while eng.active_count():
        eng.step()
        for lane, res in eng.harvest():
            out[lane] = res
            # lane stays un-recycled so last_candidates survives below
    return eng, out, straddled


def check_straddle(eng, out, straddled, final_index, final_mesh):
    """Every straddling lane: bit-match with the fixed final mesh at the
    same budget, or a certified result whose recorded frontier re-verifies
    under Theorem 2 (resharding is a capacity knob, never a results knob)."""
    violations = 0
    for lane in straddled:
        r = out[lane]
        Kf = int(r.stats.K_final)
        ids, sc, cert = sharded_diverse_search(
            final_index, jnp.asarray(X), jnp.asarray(qs[lane][None]),
            k, eps, Kf, final_mesh)
        bit_match = (np.array_equal(np.asarray(ids)[0], r.ids)
                     and np.array_equal(np.asarray(sc)[0], r.scores))
        if not bit_match:
            cand_ids, cand_sc = eng.last_candidates[lane]
            ok, sel_ids = theorem2_recheck(X, "ip", cand_ids, cand_sc,
                                           eps, k)
            if not (r.stats.certified and ok
                    and np.array_equal(sel_ids, r.ids)):
                violations += 1
    assert violations == 0, f"{violations} straddle parity violations"
    return [out[lane] for lane in straddled]


eng_g, out_g, straddled_g = drive_straddle(index2, mesh2, 4, mesh4)
assert len(straddled_g) >= 2, "grow straddle needs in-flight lanes"
index4 = eng_g.index
grow_res = check_straddle(eng_g, out_g, straddled_g, index4, mesh4)

eng_s, out_s, straddled_s = drive_straddle(index4, mesh4, 2, mesh2)
assert len(straddled_s) >= 2, "shrink straddle needs in-flight lanes"
shrink_res = check_straddle(eng_s, out_s, straddled_s, eng_s.index, mesh2)
assert any(r.stats.certified for r in grow_res + shrink_res)

# recall vs a fixed-mesh twin that never migrated: no worse
fixed = ShardedEngine(index2, jnp.asarray(X), mesh2, num_lanes=4, K0=16,
                      max_k=8, resume="beam")
for lane in range(4):
    fixed.admit(lane, LaneRequest(q=qs[lane], k=k, eps=eps,
                                  method="sharded"))
fixed_out = {}
while fixed.active_count():
    fixed.step()
    for lane, res in fixed.harvest():
        fixed_out[lane] = res
        fixed.recycle(lane)


def mean_recall(out):
    recs = []
    for lane, r in out.items():
        o = div_astar_oracle(X, "ip", qs[lane], k, eps, X=512)
        truth = set(int(i) for i in o.ids if i >= 0)
        got = set(int(i) for i in r.ids if i >= 0)
        recs.append(len(got & truth) / max(len(truth), 1))
    return float(np.mean(recs))


r_elastic, r_fixed = mean_recall(out_g), mean_recall(fixed_out)
assert r_elastic >= r_fixed, (r_elastic, r_fixed)
print(f"straddles: grow={len(straddled_g)} shrink={len(straddled_s)} lanes, "
      f"recall elastic {r_elastic:.3f} vs fixed {r_fixed:.3f}")

# --- 2. scheduler-driven scale events through the facade --------------------

policy = ElasticPolicy(grow_depth=2, shrink_depth=0, sustain=2,
                       shrink_sustain=3, cooldown=3)
db = DiverseVectorDB(X, "ip", shards="auto", elastic=policy, num_lanes=2,
                     max_k=8, M=8, prewarm=True,
                     backend_kw=dict(K0=16, resume="beam"),
                     scheduler_kw=dict(max_pending=32, prewarm_capacity=N,
                                       prewarm_ks=(k,)))
assert is_rescalable(db.backend)
assert db.backend.num_shards == 2 and set(db.backend.rescale_options()) == \
    {2, 4}

# 3. recompile-budget audit: freeze now — every signature a scale event
# needs must already be planned, and the resume dispatch cache must not
# grow once both targets are prewarmed
sig = db.engine.signature_log
sig.freeze()
sizes0 = resume_jit_cache_sizes()

sched = db.scheduler
burst = rng.normal(size=(24, d)).astype(np.float32)
reqs, i, admitted_on_new = [], 0, False
while i < len(burst) or sched.pending or sched.inflight:
    while i < len(burst) and len(sched.pending) < 4:
        reqs.append(sched.submit(burst[i], k, eps))
        i += 1
    before = {lane: r.rid for lane, r in sched.inflight.items()}
    sched.pump()
    if db.backend.num_shards == 4 and sched.scale_events:
        if any(before.get(lane) != r.rid
               for lane, r in sched.inflight.items()):
            admitted_on_new = True   # refilled AFTER the grow, on the new mesh
for _ in range(24):                  # idle pumps: let the shrink trigger fire
    sched.pump()
    if any(e["to_shards"] < e["from_shards"] for e in sched.scale_events):
        break

grows = [e for e in sched.scale_events if e["to_shards"] > e["from_shards"]]
shrinks = [e for e in sched.scale_events if e["to_shards"] < e["from_shards"]]
assert grows, "burst never triggered a grow"
assert shrinks, "idle queue never triggered a shrink"
assert admitted_on_new, "no request was admitted into a lane on the new mesh"
assert all(r.result is not None for r in reqs)
assert all(r.result.stats.certified for r in reqs)
stats = sched.latency_stats()
assert stats["completed"] == len(burst) and stats["inflight"] == 0
assert stats["shards"] == db.backend.num_shards
assert stats["scale_events"] == len(grows) + len(shrinks)

assert sig.unplanned == [], f"unplanned signatures: {sig.unplanned}"
sizes1 = resume_jit_cache_sizes()
assert sizes1 == sizes0, f"resume jit cache grew: {sizes0} -> {sizes1}"
print(f"scale events: {len(grows)} grow + {len(shrinks)} shrink, "
      f"pause p max {max(e['pause_s'] for e in sched.scale_events):.3f}s, "
      f"jit cache {sizes1}")
print("OK")
