"""Batched progressive engine (paper Alg. 2-4 over a lane batch).

This is the middle layer of the serving stack's three-way split:

* ``core.lane_state`` — pure fixed-shape per-lane state (queue/beam pytrees,
  ``extract_lane`` / ``inject_lane`` / ``recycle_lane``).
* this module — the **engine**: one-dispatch search bursts, bucketed exact
  queue growth, batched diversify/verify kernels, and a per-lane state
  machine (``ProgressiveEngine.step()``) that advances every occupied lane
  one progressive round. Lanes are independent: each carries its own
  ``(k, eps, ef)`` and its own method (PGS / PDS / PSS), and a certified
  lane's slot can be recycled for a new query between steps.
* ``serve.scheduler`` — continuous-batching admission on top of ``step()``:
  a request queue feeds freed lanes so one heavy-tailed query never stalls
  the batch (see that module for the latency story). The scheduler drives
  the engine through the backend-neutral ``core.backend.LaneBackend``
  protocol, which this engine implements for the single-host case
  (``sharded_search.engine.ShardedEngine`` is the mesh case).

Device-side structure (unchanged from the original engine):

* **One-dispatch bursts** — a single ``lax.map`` dispatch advances every
  lane's beam-search ``while_loop`` to that lane's own stop condition;
  lanes run lane-serial on device, paying the sum of per-lane work with
  none of the per-query dispatch overhead (see ``_batched_search_loop``).
* **Per-lane logical capacity** — all lanes share one fixed-shape state at
  the physical capacity, but each lane's queue is clamped to its own
  logical capacity after every insert, so per-lane semantics are *bit-exact*
  with a solo ``ProgressiveDriver`` at that capacity.
* **Bucketed growth** — lanes whose candidate budget outgrows their capacity
  are rebuilt together per power-of-two target with the exact rebuild of
  ``beam_search.rebuild_for_growth`` (one vmapped rebuild per bucket).
* **Batched diversify + verify** — the PGS/warm-start round is ONE fused
  dispatch per (prefix width, k) group (``kops.fused_round_batch``: prefix
  masking, candidate gather, G^eps adjacency, greedy selection and output
  extraction in a single ``pallas_call`` on the kernel paths — see
  ``kernels/fused_round.py``); the remaining verify stages (Theorem-1
  degree schedules, div-A*) run per-group from masked prefixes, with
  Theorem-2 certificates coming back per lane.

Compile-signature discipline: every jitted call site is logged in a
``SignatureLog`` keyed by its shape/static signature — ``(lane count,
physical capacity)`` for bursts, ``(group size, prefix width[, k])`` for the
diversify stages — and group sizes / widths / capacities are all padded to
powers of two, so the number of distinct signatures is logarithmic in batch
size and capacity. ``ProgressiveEngine.prewarm()`` compiles the capacity
ladder up front (the scheduler calls it at start) and the log exposes any
signature first seen after ``freeze()`` as *unplanned*.

Entry points: ``batch_pgs`` (Alg. 2), ``batch_pds`` (Alg. 3), ``batch_pss``
(Alg. 4, the default serving path) — lockstep wrappers that admit the whole
batch and step the engine until every lane finishes, returning a
``BatchDiverseResult`` whose per-lane ids/scores match the per-query drivers
exactly.

Parity scope: every per-lane decision replicates the per-query driver's
formulas, queue-score computations are batch-invariant by construction
(``query_sim``'s reduce form, the rank-merge insert, top_k rebuilds), and
``tests/test_batch_progressive.py`` enforces bit-equality on the CPU
reference path — including for recycled lanes, which must match a fresh solo
driver for the new query. The one caveat is the adjacency build: ``sims >
eps`` edges come from matmuls whose accumulation order XLA may vary across
batch shapes and backends, so a pair landing within one rounding step of
``eps`` could in principle flip an edge relative to the solo driver (which
additionally uses ``extend_adjacency``'s different-shaped matmul). Measured
bit-stable across vmap/widths on CPU; re-validate the parity suite before
relying on bit-equality on a new backend.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant
from repro.core import beam_search as bs
from repro.core import div_astar as da
from repro.core import lane_state
from repro.core import queue as qmod
from repro.core.backend import LaneRequest
from repro.core.bucketing import (next_pow2 as _next_pow2, pow2_group_sizes,
                                  pow2_padded_indices)
from repro.core.diversity_graph import degrees as _degrees
from repro.core.graph import FlatGraph
from repro.core.pgs import DiverseResult
from repro.core.progressive import SearchStats
from repro.core.theorems import theorem1_K, theorem2_min_value
from repro.kernels import ops as kops
from repro.obs import span


# --------------------------------------------------------------- results ----

@dataclasses.dataclass
class BatchSearchStats:
    """Per-lane counters mirroring ``progressive.SearchStats``."""
    expansions: np.ndarray
    growths: np.ndarray
    search_calls: np.ndarray
    div_calls: np.ndarray
    certified: np.ndarray
    exhausted: np.ndarray
    K_final: np.ndarray

    @classmethod
    def zeros(cls, b: int) -> "BatchSearchStats":
        return cls(expansions=np.zeros(b, np.int64),
                   growths=np.zeros(b, np.int64),
                   search_calls=np.zeros(b, np.int64),
                   div_calls=np.zeros(b, np.int64),
                   certified=np.zeros(b, bool),
                   exhausted=np.zeros(b, bool),
                   K_final=np.zeros(b, np.int64))

    def reset_lane(self, lane: int) -> None:
        for f in dataclasses.fields(self):
            getattr(self, f.name)[lane] = 0

    def lane_view(self, lane: int) -> SearchStats:
        return SearchStats(expansions=int(self.expansions[lane]),
                           growths=int(self.growths[lane]),
                           search_calls=int(self.search_calls[lane]),
                           div_calls=int(self.div_calls[lane]),
                           certified=bool(self.certified[lane]),
                           exhausted=bool(self.exhausted[lane]),
                           K_final=int(self.K_final[lane]))


class BatchDiverseResult(NamedTuple):
    ids: np.ndarray      # int32[B, k], -1 padded
    scores: np.ndarray   # f32[B, k]
    totals: np.ndarray   # f32[B]
    stats: BatchSearchStats


# ------------------------------------------------------ signature logging ----

class SignatureBudgetExceeded(RuntimeError):
    """The engine would compile more distinct signatures than allowed."""


class SignatureLog:
    """Registry of jit call signatures the engine has issued.

    A *signature* is the (call site, shape/static args) tuple that determines
    whether XLA reuses a compilation: e.g. ``("search", B, C)`` for the burst
    loop or ``("div_astar", group, width, k)`` for verification. ``note``
    raises ``SignatureBudgetExceeded`` once more than ``limit`` distinct
    signatures exist — the compile-budget backstop. After ``freeze()``
    (scheduler prewarm done), first-seen signatures are additionally recorded
    in ``unplanned`` so tests can assert the ladder was fully pre-warmed.

    Each call of a jitted program on the step path is noted once, so the
    counts are also the dispatch counter: ``total`` is the running number
    of program calls, and its difference across a span of serving is the
    number made in it. Eager ops (a prefix group's row slices, growth's
    gathers and scatters) are not counted.
    """

    def __init__(self, limit: int | None = 1024):
        self.limit = limit
        self.counts: dict[tuple, int] = {}
        self.total = 0
        self.frozen = False
        self.unplanned: list[tuple] = []

    def note(self, kind: str, *shape) -> None:
        sig = (kind, *(int(s) for s in shape))
        if sig not in self.counts:
            if self.limit is not None and len(self.counts) >= self.limit:
                raise SignatureBudgetExceeded(
                    f"signature {sig} would exceed the compile budget of "
                    f"{self.limit} distinct signatures")
            self.counts[sig] = 0
            if self.frozen:
                self.unplanned.append(sig)
        self.counts[sig] += 1
        self.total += 1

    def freeze(self) -> None:
        self.frozen = True

    def __len__(self) -> int:
        return len(self.counts)


# ------------------------------------------------------- device functions ----

def _merge_insert(queue: qmod.Queue, new_ids: jnp.ndarray,
                  new_scores: jnp.ndarray, new_mask: jnp.ndarray) -> qmod.Queue:
    """Bit-identical replacement for ``queue.insert`` on an already-sorted
    queue. ``queue.insert`` re-sorts all C+M entries with an O(C log C)
    *comparator* sort per expansion step — the dominant cost of the burst
    at (B, C) shapes. Here each entry's merged position is its rank
    under the same (score desc, id asc) order, computed from an O(C*M)
    vectorized comparison matrix (M = M0 graph degree, so this is the same
    cost class as the dedup matrix insert already builds). Ties (only the
    empty-slot sentinel) resolve queue-first / index-order, matching the
    stable lexsort exactly."""
    cap = queue.capacity
    m = new_ids.shape[0]
    b_ids, b_scores, b_stable = qmod.dedup_candidates(
        queue, new_ids, new_scores, new_mask)
    a_ids, a_scores = queue.ids, queue.scores

    def before(s1, i1, s2, i2):
        # strict (score desc, id asc) precedence
        return (s1 > s2) | ((s1 == s2) & (i1 < i2))

    # a entries keep their rank among a (queue is sorted); b entries ahead
    # of a_i push it back. Full ties (empty sentinels) resolve a-first.
    # rank of each b among b (strict order; sentinel ties resolve by index)
    bb = before(b_scores[:, None], b_ids[:, None],
                b_scores[None, :], b_ids[None, :])
    tie_bb = (b_scores[:, None] == b_scores[None, :]) & \
        (b_ids[:, None] == b_ids[None, :]) & (
        jnp.arange(m)[:, None] < jnp.arange(m)[None, :])
    rank_b = jnp.sum(bb | tie_bb, axis=0)
    inv_rank = jnp.argmax(rank_b[:, None] == jnp.arange(m)[None, :], axis=0)
    bs_ids, bs_scores = b_ids[inv_rank], b_scores[inv_rank]
    bs_stable = b_stable[inv_rank]
    # merged slot of each sorted-b element: a entries ahead of it (ties:
    # queue entries first, matching the stable concat-lexsort), plus its
    # own rank among b
    a_before_b = before(a_scores[:, None], a_ids[:, None],
                        bs_scores[None, :], bs_ids[None, :]) | (
        (a_scores[:, None] == bs_scores[None, :])
        & (a_ids[:, None] == bs_ids[None, :]))
    pos_b = jnp.sum(a_before_b, axis=0) + jnp.arange(m)
    # slot-wise gather (no scatter, no comparator sort): slot r holds
    # b_sorted[cb[r]] if some b lands at r, else a[r - cb[r]]
    slots = jnp.arange(cap)
    cb = jnp.sum(pos_b[None, :] < slots[:, None], axis=1)
    is_b = jnp.any(pos_b[None, :] == slots[:, None], axis=1)
    ai = jnp.minimum(slots - cb, cap - 1)
    bi = jnp.minimum(cb, m - 1)
    return qmod.Queue(
        ids=jnp.where(is_b, bs_ids[bi], a_ids[ai]),
        scores=jnp.where(is_b, bs_scores[bi], a_scores[ai]),
        stable=jnp.where(is_b, bs_stable[bi], queue.stable[ai]),
    )


@functools.partial(jax.jit, static_argnames=("graph_metric",))
def _batched_search_loop(vectors, neighbors, qs, state, caps, stable_limits,
                         min_values, max_steps, graph_metric: str):
    """One-dispatch burst: every lane runs its own beam-search while_loop.

    Identical to ``beam_search._search_loop`` per lane, plus the logical
    capacity clamp: entries at positions >= cap are forced back to the empty
    sentinel after each insert, which is exactly a capacity-``cap`` queue
    stored in a wider array.

    Lanes run lane-serial on device (``lax.map``): lane step counts vary
    several-fold, so a vmapped while_loop would charge every lane the
    straggler's trip count, while ``lax.map`` pays exactly the sum of
    per-lane work with none of the per-call dispatch overhead the per-query
    driver loop pays (measured ~2x faster than the vmapped variant on CPU
    even before straggler effects; revisit per-backend — on TPU the lockstep
    vmap variant may win back).
    """
    C = state.queue.ids.shape[-1]
    pos = jnp.arange(C)

    def one(args):
        q, st, cap, sl, mv, ms = args

        def clamp(queue: qmod.Queue) -> qmod.Queue:
            live = pos < cap
            return qmod.Queue(jnp.where(live, queue.ids, -1),
                              jnp.where(live, queue.scores, qmod.NEG_INF),
                              jnp.where(live, queue.stable, True))

        # the frontier pointer rides in the carry so the queue is scanned
        # once per expansion, not once in cond and again in body
        def cond(c):
            st, p, exists = c
            score_ok = st.queue.scores[p] >= mv
            return exists & score_ok & (st.steps < ms)

        def body(c):
            st, p, _ = c
            queue, visited, steps = st
            node = queue.ids[p]
            queue = qmod.Queue(queue.ids, queue.scores,
                               queue.stable.at[p].set(True))
            visited = visited.at[node].set(True)
            nbrs = neighbors[node]
            safe = jnp.maximum(nbrs, 0)
            fresh = (nbrs >= 0) & ~visited[safe]
            sims = kops.batch_similarity(q, vectors[safe], graph_metric)
            queue = clamp(_merge_insert(queue, nbrs, sims, fresh))
            p2, exists2 = qmod.first_unstable(queue, sl)
            return bs.SearchState(queue, visited, steps + 1), p2, exists2

        p0, exists0 = qmod.first_unstable(st.queue, sl)
        out, _, _ = jax.lax.while_loop(cond, body, (st, p0, exists0))
        return out

    return jax.lax.map(
        one, (qs, state, caps, stable_limits, min_values, max_steps))


@functools.partial(jax.jit, static_argnames=("new_capacity",))
def _rebuild_lanes(graph: FlatGraph, qs, state, new_capacity: int):
    """Exact rebuild of a growth bucket's lanes.

    Same construction as ``beam_search.rebuild_for_growth`` — rescore
    (visited ∪ queue), rebuild the queue — but the new queue is selected
    with ``lax.top_k`` instead of a full N-entry comparator sort: entries
    are indexed by node id, and top_k's documented lower-index-first tie
    rule is exactly the queue's (score desc, id asc) order, so the result
    is bit-identical at a fraction of the cost. Bit-parity of the rescoring
    itself holds because ``query_sim`` uses a batch-invariant reduce (see
    ``similarity.query_sim``).

    The caller slices the input queue to ``new_capacity`` (entries past a
    lane's logical capacity are padding sentinels), so the compile signature
    depends only on (group size, target capacity), not on the batch's
    physical capacity.
    """
    n = graph.size
    k0 = min(new_capacity, n)
    pad = new_capacity - k0

    def one(q, st):
        vis_scores = kops.batch_similarity(q, graph.vectors, graph.metric)
        safe = jnp.maximum(st.queue.ids, 0)
        # membership via add-scatter: duplicate target slots (several empty
        # sentinels all map to node 0) accumulate instead of racing, which
        # .set would leave order-undefined
        in_queue = jnp.zeros((n,), jnp.int32).at[safe].add(
            (st.queue.ids >= 0).astype(jnp.int32)) > 0
        frontier_unstable = jnp.zeros((n,), jnp.int32).at[safe].add(
            ((st.queue.ids >= 0) & ~st.queue.stable).astype(jnp.int32)) > 0
        member = st.visited | in_queue
        scores = jnp.where(member, vis_scores, qmod.NEG_INF)
        top_scores, sel = jax.lax.top_k(scores, k0)
        valid = top_scores > qmod.NEG_INF  # similarities are always finite
        queue = lane_state.pad_queue(qmod.Queue(
            ids=jnp.where(valid, sel.astype(jnp.int32), -1),
            scores=jnp.where(valid, top_scores, qmod.NEG_INF),
            stable=jnp.where(valid, ~frontier_unstable[sel], True)), pad)
        return bs.SearchState(queue, st.visited, st.steps)

    return jax.vmap(one)(qs, state)


_batched_stable_count = jax.jit(jax.vmap(qmod.stable_count))


@jax.jit
def _set_row(a, i, row):
    return a.at[i].set(row)


@functools.partial(jax.jit, static_argnames=("metric",))
def _batched_adjacency(vectors, ids, eps, metric: str):
    """Per-lane G^eps adjacency; ``eps`` is a per-lane f32 vector so lanes
    with different diversification levels share one compilation."""
    vecs = vectors[jnp.maximum(ids, 0)]
    valid = ids >= 0
    return jax.vmap(
        lambda v, m, e: kops.pairwise_adjacency(v, e, metric, m)
    )(vecs, valid, eps)


@functools.partial(jax.jit, static_argnames=("k", "max_expansions"))
def _batched_div_astar(scores, ids, adj, k: int, max_expansions: int):
    """Batched div-A* + Theorem-2 minValue per lane, over the rows' scores
    with the empty slots (``ids < 0``) masked out.

    Lane-serial on device (``lax.map``) rather than vmapped: div-A* trip
    counts are heavy-tailed (the paper's §IV hard cases run 10-100x the
    median), and a vmapped while_loop would make every lane pay the
    straggler's trips with both cond branches materialized. ``lax.map``
    keeps the per-query cost profile — one dispatch for the whole batch,
    branch-and-bound pruning intact per lane."""
    def one(s, a):
        r = da.div_astar(s, a, k, max_expansions)
        return r, theorem2_min_value(r.best_scores, k)
    masked = jnp.where(ids >= 0, scores, -jnp.inf)
    return jax.lax.map(lambda args: one(*args), (masked, adj))


@functools.partial(jax.jit, static_argnames=("k",))
def _batched_theorem1(adj, valid, k: int):
    """Theorem-1 sufficient candidate count per lane (PDS degree schedule)."""
    deg = jax.vmap(_degrees)(adj, valid)
    return jax.vmap(lambda d: theorem1_K(d, k))(deg)


@jax.jit
def _mask_prefix(ids, scores, Ks):
    keep = jnp.arange(ids.shape[-1])[None, :] < Ks[:, None]
    return (jnp.where(keep, ids, -1),
            jnp.where(keep, scores, -jnp.inf))


# ----------------------------------------------------------------- driver ----

class BatchProgressiveDriver:
    """Owns a whole batch's lane state across pause/resume (lower engine half).

    Mirrors ``progressive.ProgressiveDriver`` lane-for-lane: the same
    capacity policy, growth thresholds, and stop conditions are applied to
    every lane individually (as host-side numpy vectors), so each lane's
    trajectory is identical to a solo driver on the same query. State lives
    in ``core.lane_state`` pytrees; ``recycle`` re-initializes one lane slot
    for a new query without disturbing siblings.
    """

    def __init__(self, graph: FlatGraph, qs, ef: int, k: int,
                 capacity0: int | None = None,
                 max_capacity: int | None = None,
                 max_signatures: int | None = 1024):
        self.graph = graph
        self.qs = jnp.asarray(qs, jnp.float32)
        self.B = int(self.qs.shape[0])
        self.ef = ef
        self.k = k
        n = graph.size
        if capacity0 is None:
            capacity0 = min(_next_pow2(max(2 * k * ef, 256)), _next_pow2(n))
        self.max_capacity = max_capacity or _next_pow2(n)
        self.caps = np.full(self.B, capacity0, np.int64)
        self.signatures = SignatureLog(max_signatures)
        self.signatures.note("init", self.B, capacity0)
        self.state = lane_state.init_lanes(graph, self.qs, capacity0)
        self.stats = BatchSearchStats.zeros(self.B)

    # -- capacity management ------------------------------------------------
    @property
    def physical_capacity(self) -> int:
        return lane_state.physical_capacity(self.state)

    def _ensure_physical(self, cap: int) -> None:
        C = self.physical_capacity
        if cap > C:
            with span("engine.pad", capacity=C, to=cap):
                self.signatures.note("pad", self.B, C, cap)
                self.state = lane_state.pad_lanes(self.state, cap)

    def recycle(self, lane: int, q, capacity0: int) -> None:
        """Hand lane ``lane`` to a new query: fresh solo-equivalent state at
        logical capacity ``capacity0``, stats zeroed, siblings untouched."""
        self._ensure_physical(capacity0)
        self.signatures.note("recycle", self.B, self.physical_capacity)
        self.state = lane_state.recycle_lane(self.graph, self.state, lane, q)
        self.signatures.note("set_query", self.B)
        self.qs = _set_row(self.qs, lane, jnp.asarray(q, jnp.float32))
        self.caps[lane] = capacity0
        self.stats.reset_lane(lane)

    def _grow_lanes(self, req: np.ndarray, mask: np.ndarray) -> None:
        """Grow each masked lane to next_pow2(req) (clamped), per-bucket.

        Same policy as ``ProgressiveDriver._grow_to`` per lane; lanes landing
        on the same power-of-two bucket are rebuilt together in one vmapped
        exact rebuild, with the bucket padded to a power-of-two lane count so
        rebuild signatures stay logarithmic in batch size.
        """
        targets = np.array([min(_next_pow2(int(r)), self.max_capacity)
                            for r in req])
        grow = mask & (targets > self.caps)
        if not grow.any():
            return
        top = int(targets[grow].max())
        with span("engine.grow", capacity=self.physical_capacity, to=top,
                  lanes=int(grow.sum())):
            self._ensure_physical(top)
            C = self.physical_capacity
            for cap in sorted(set(int(c) for c in targets[grow])):
                idx = np.flatnonzero(grow & (targets == cap))
                m = len(idx)
                padded = pow2_padded_indices(idx)
                g = len(padded)
                jidx = jnp.asarray(padded)
                sub = lane_state.select_lanes(self.state, jidx)
                sub = lane_state.slice_queue_capacity(sub, cap)
                self.signatures.note("rebuild", g, cap)
                rebuilt = _rebuild_lanes(self.graph, self.qs[jidx], sub, cap)
                q = lane_state.pad_queue(rebuilt.queue, C - cap)
                ridx = jnp.asarray(idx)
                bq = self.state.queue
                self.state = bs.SearchState(
                    qmod.Queue(bq.ids.at[ridx].set(q.ids[:m]),
                               bq.scores.at[ridx].set(q.scores[:m]),
                               bq.stable.at[ridx].set(q.stable[:m])),
                    self.state.visited, self.state.steps)
                self.caps[idx] = cap
                self.stats.growths[idx] += 1

    # -- search bursts ------------------------------------------------------
    def ensure_stable(self, targets: np.ndarray,
                      min_values: np.ndarray | None = None,
                      active: np.ndarray | None = None) -> np.ndarray:
        """Resume every active lane until its first ``targets[i]`` candidates
        are stable (or its frontier drops below ``min_values[i]``).
        Returns the per-lane stable prefix length."""
        n = self.graph.size
        if active is None:
            active = np.ones(self.B, bool)
        if not active.any():
            return self.stable_prefix_len()
        targets = np.minimum(np.asarray(targets, np.int64), n)
        need = active & (targets + 8 > self.caps)
        self._grow_lanes((targets * 1.5).astype(np.int64) + 64, need)
        if min_values is None:
            min_values = np.full(self.B, -np.inf, np.float32)
        sl = np.where(active, np.minimum(targets, self.caps), 0)
        ms = 4 * self.caps + 64
        C = self.physical_capacity
        with span("engine.search", capacity=C, lanes=int(active.sum())):
            self.signatures.note("search", self.B, C)
            self.state = _batched_search_loop(
                self.graph.vectors, self.graph.neighbors, self.qs, self.state,
                jnp.asarray(self.caps, jnp.int32), jnp.asarray(sl, jnp.int32),
                jnp.asarray(min_values, jnp.float32),
                jnp.asarray(ms, jnp.int32), self.graph.metric)
            self.stats.search_calls[active] += 1
            with span("engine.sync", site="steps"):
                self.stats.expansions = np.asarray(self.state.steps,
                                                   np.int64).copy()
            return self.stable_prefix_len()

    def expand_until_below(self, min_values: np.ndarray,
                           active: np.ndarray) -> np.ndarray:
        """PSS's ProgressiveBeamSearch* per lane: expand while the frontier
        score is >= minValue, growing capacity as needed."""
        stable = np.zeros(self.B, np.int64)
        remaining = active.copy()
        while remaining.any():
            got = self.ensure_stable(np.where(remaining, self.caps, 0),
                                     min_values, remaining)
            stable[remaining] = got[remaining]
            done = (stable < self.caps) | (self.caps >= self.max_capacity)
            remaining = remaining & ~done
            if remaining.any():
                self._grow_lanes(self.caps * 2, remaining)
        return stable

    def stable_prefix_len(self) -> np.ndarray:
        self.signatures.note("stable_count", self.B, self.physical_capacity)
        counts = _batched_stable_count(self.state.queue)
        with span("engine.sync", site="stable_count"):
            return np.asarray(counts, np.int64)

    # -- candidate prefixes -------------------------------------------------
    def _buckets(self, Ks: np.ndarray) -> np.ndarray:
        return np.minimum(
            np.maximum(64, np.array([_next_pow2(int(K)) for K in Ks])),
            self.caps)

    def _group_lanes(self, Ks: np.ndarray, active: np.ndarray, ks=None):
        """Group active lanes by (width bucket[, k]) — shared by the masked
        and raw prefix generators. Yields (lane_indices, width,
        padded_jnp_indices, Ks_pad): groups are padded to a power-of-two
        lane count (pad rows keep K=0 -> all-sentinel) so compile
        signatures stay bounded; only the first ``len(lane_indices)`` rows
        are real."""
        Ks = np.minimum(np.asarray(Ks, np.int64), self.caps)
        buckets = self._buckets(Ks)
        groups: dict[tuple, list[int]] = {}
        for i in np.flatnonzero(active):
            key = (int(buckets[i]), -1 if ks is None else int(ks[i]))
            groups.setdefault(key, []).append(i)
        for (width, _k), idx in sorted(groups.items()):
            idx = np.asarray(idx)
            padded = pow2_padded_indices(idx)
            Ks_pad = np.zeros(len(padded), np.int64)
            Ks_pad[:len(idx)] = Ks[idx]
            yield idx, width, jnp.asarray(padded), Ks_pad

    def prefix_groups(self, Ks: np.ndarray, active: np.ndarray, ks=None):
        """Yield (lane_indices, ids, scores) per (width bucket[, k]) group.

        The multi-dispatch diversify/verify stages (PDS, PDS-final, PSS)
        consume prefixes through this: lanes whose prefix lands in the same
        power-of-two bucket (and, when ``ks`` is given, share the same
        ``k``) are processed together at exactly that width. Width changes
        div-A*'s cursor-step accounting (padding slots consume budget), so
        running each lane at its own per-query bucket width — not the batch
        max — is what keeps div-A* results identical to the per-query
        driver. Rows are ``_mask_prefix``-masked: positions >= K carry the
        id=-1 / -inf sentinels.
        """
        for idx, width, jidx, Ks_pad in self._group_lanes(Ks, active, ks):
            self.signatures.note("prefix", len(jidx), width)
            ids, scores = _mask_prefix(
                self.state.queue.ids[jidx, :width],
                self.state.queue.scores[jidx, :width],
                jnp.asarray(Ks_pad, jnp.int32))
            yield idx, ids, scores

    def prefix_groups_raw(self, Ks: np.ndarray, active: np.ndarray, ks=None):
        """Like ``prefix_groups`` but yields the *raw* queue rows plus the
        per-lane budgets: (lane_indices, ids, scores, Ks_pad).

        For consumers that fold the prefix masking into their own dispatch —
        the fused round kernel (``kops.fused_round_batch``) takes the raw
        sorted rows and ``Ks`` and performs masking, gather, adjacency and
        greedy diversification in one call, so a separate ``_mask_prefix``
        launch here would be a wasted round trip.
        """
        for idx, width, jidx, Ks_pad in self._group_lanes(Ks, active, ks):
            yield (idx, self.state.queue.ids[jidx, :width],
                   self.state.queue.scores[jidx, :width], Ks_pad)


# ----------------------------------------------------------------- engine ----

LANE_FREE, LANE_PGS, LANE_PSS, LANE_PDS, LANE_PDS_FIN, LANE_DONE = range(6)

_METHOD_STATUS = {"pss": LANE_PGS, "pgs": LANE_PGS, "pds": LANE_PDS}


class ProgressiveEngine:
    """Per-lane progressive state machine over a ``BatchProgressiveDriver``.

    Each lane independently runs one of the paper's methods with its own
    ``(k, eps, ef)``:

    * ``pgs``  — Alg. 2 rounds: stabilize K*ef, greedy-diversify, grow K.
    * ``pss``  — Alg. 4: the PGS warm start, then div-A* + Theorem-2
      certificate rounds with ProgressiveBeamSearch* resumption.
    * ``pds``  — Alg. 3: Theorem-1 degree schedule rounds, then one
      certified div-A*.

    ``step()`` advances every occupied lane one round (search bursts batched
    across lanes in one dispatch, diversify/verify batched per (width, k)
    group) and returns the lanes that finished. Finished lanes can be
    re-admitted with a **new query** via ``admit`` (lane recycling) — the
    continuous-batching hook the serving scheduler drives. Per-lane results
    are bit-identical to the per-query drivers regardless of admission
    order, because every device op is lane-separable and batch-invariant.

    This is the single-host implementation of the ``core.backend.LaneBackend``
    protocol (``admit``/``step``/``harvest``/``recycle``/``prewarm``/
    ``signature_log``); ``sharded_search.engine.ShardedEngine`` is the mesh
    one, and ``serve.scheduler.LaneScheduler`` drives either.
    """

    methods = ("pss", "pgs", "pds")

    def __init__(self, graph: FlatGraph, num_lanes: int | None = None, *,
                 driver: BatchProgressiveDriver | None = None,
                 max_k: int = 16, default_ef: int = 40,
                 capacity0: int | None = None,
                 max_capacity: int | None = None,
                 max_iters: int = 64, max_expansions: int = 400_000,
                 max_signatures: int | None = 1024,
                 kernel_impl: str | None = None):
        self.graph = graph
        # backend for the fused PGS round ("auto"/"ref"/"interpret"/
        # "pallas"); None defers to kops.set_default_impl / "auto".
        self.kernel_impl = kernel_impl
        if driver is None:
            if num_lanes is None:
                raise ValueError("need num_lanes or driver")
            d = int(graph.vectors.shape[1])
            base_cap = capacity0 or min(256, _next_pow2(graph.size))
            driver = BatchProgressiveDriver(
                graph, jnp.zeros((num_lanes, d), jnp.float32),
                ef=default_ef, k=1, capacity0=base_cap,
                max_capacity=max_capacity, max_signatures=max_signatures)
        self.driver = driver
        self.B = driver.B
        self.max_k = max_k
        self.default_ef = default_ef
        self._capacity0 = capacity0
        self._max_capacity = max_capacity
        self._max_signatures = max_signatures
        self.max_iters = max_iters
        self.max_expansions = max_expansions
        self.status = np.full(self.B, LANE_FREE, np.int8)
        self.to_pss = np.zeros(self.B, bool)
        self.ks = np.full(self.B, 1, np.int64)
        self.epss = np.zeros(self.B, np.float64)
        self.efs = np.full(self.B, default_ef, np.int64)
        self.K = np.zeros(self.B, np.int64)
        self.iters = np.zeros(self.B, np.int64)
        self.maxK = np.full(self.B, graph.size, np.int64)
        self.out_ids = np.full((self.B, max_k), -1, np.int32)
        self.out_sc = np.zeros((self.B, max_k), np.float32)
        self._unharvested: list[int] = []
        self.steps = 0
        #: when True, each certificate-bearing round keeps the lane's sorted
        #: candidate frontier host-side (``last_candidates[lane]`` =
        #: ``(cand_ids, cand_scores, slack_or_None)``) so a result's
        #: Theorem-2 certificate can be audited or cached after harvest —
        #: the single-host mirror of ``ShardedEngine.record_candidates``
        self.record_candidates = False
        self.last_candidates: list = [None] * self.B
        # LaneBackend contract 13: the single-host engine always scores the
        # exact float corpus, so its certificates need no rerank stage
        self.compressed = bool(quant.is_quantized(graph.vectors))

    # -- admission ----------------------------------------------------------
    @property
    def num_lanes(self) -> int:
        return self.B

    @property
    def bytes_per_vector(self) -> float:
        """Stored corpus bytes per vector (f32 graph: ``4 * d``)."""
        return quant.corpus_bytes_per_vector(self.graph.vectors)

    @property
    def signatures(self) -> SignatureLog:
        return self.driver.signatures

    @property
    def signature_log(self) -> SignatureLog:
        return self.driver.signatures

    def free_lanes(self) -> np.ndarray:
        return np.flatnonzero((self.status == LANE_FREE)
                              | (self.status == LANE_DONE))

    def active_count(self) -> int:
        return int(((self.status != LANE_FREE)
                    & (self.status != LANE_DONE)).sum())

    def _set_lane(self, lane: int, k: int, eps: float, ef: int, method: str,
                  max_K: int | None) -> None:
        if method not in _METHOD_STATUS:
            raise ValueError(f"unknown progressive method {method!r}")
        if k > self.max_k:
            raise ValueError(f"k={k} exceeds engine max_k={self.max_k}")
        self.ks[lane] = k
        self.epss[lane] = eps
        self.efs[lane] = ef
        self.K[lane] = k
        self.iters[lane] = 0
        self.maxK[lane] = max_K or self.graph.size
        self.out_ids[lane] = -1
        self.out_sc[lane] = 0.0
        self.last_candidates[lane] = None
        self.to_pss[lane] = method == "pss"
        self.status[lane] = _METHOD_STATUS[method]

    def admit(self, lane: int, q, *, k: int | None = None,
              eps: float | None = None, ef: int | None = None,
              method: str = "pss", max_K: int | None = None) -> None:
        """Recycle lane ``lane`` for a new request (fresh solo-equivalent
        state; bit-identical trajectory to a fresh per-query driver).

        ``q`` is either a query vector with explicit ``k``/``eps`` keywords,
        or a ``core.backend.LaneRequest`` (the protocol form the scheduler
        uses) carrying all of them — in which case no keywords may be given.
        """
        if isinstance(q, LaneRequest):
            if (k, eps, ef, max_K) != (None,) * 4 or method != "pss":
                raise TypeError("pass parameters on the LaneRequest, not as "
                                "admit keywords")
            req = q
            q, k, eps = req.q, req.k, req.eps
            ef, method, max_K = req.ef, req.method, req.max_K
        elif k is None or eps is None:
            raise TypeError("admit needs k= and eps= (or a LaneRequest)")
        if self.status[lane] not in (LANE_FREE, LANE_DONE):
            raise RuntimeError(f"lane {lane} is still occupied")
        if lane in self._unharvested:     # direct re-admission skips harvest
            self._unharvested.remove(lane)
        ef = int(ef or self.default_ef)
        n = self.graph.size
        cap0 = self._capacity0 or min(_next_pow2(max(2 * k * ef, 256)),
                                      _next_pow2(n))
        self.driver.recycle(lane, q, cap0)
        self._set_lane(lane, k, eps, ef, method, max_K)

    def admit_in_place(self, lane: int, *, k: int, eps: float, ef: int,
                       method: str = "pss", max_K: int | None = None) -> None:
        """Admit a lane whose state the driver already initialized (lockstep
        wrappers: the driver was constructed over the real query batch)."""
        self._set_lane(lane, k, eps, ef, method, max_K)

    def harvest(self) -> list[tuple[int, DiverseResult]]:
        """Drain the lanes that finished since the last harvest (protocol
        form of ``step()``'s return + ``result()``); the lanes stay reserved
        until ``recycle``."""
        out = [(lane, self.result(lane)) for lane in self._unharvested]
        self._unharvested = []
        return out

    def recycle(self, lane: int) -> None:
        """Return a harvested lane's slot to the free pool."""
        if self.status[lane] != LANE_DONE:
            raise RuntimeError(f"lane {lane} is not finished")
        self.status[lane] = LANE_FREE

    def swap_graph(self, graph: FlatGraph) -> None:
        """Install a new epoch's graph (the mutable index's rebuild swap).

        Only legal with no occupied lane: per-lane search state (visited
        bitmaps, beam queues) is shaped by the corpus size, so an in-flight
        lane cannot survive a swap — the serving layer drains lanes first
        (contract 15; harvested-but-unrecycled lanes are fine, their
        results live host-side). A fresh driver is built over the new
        graph; the signature log carries across so recompile audits span
        epochs (a grown corpus legitimately traces new shapes).
        """
        if self.active_count():
            raise RuntimeError("cannot swap the graph under occupied lanes "
                               "— drain in-flight lanes first (contract 15)")
        log = self.driver.signatures
        d = int(self.driver.qs.shape[1])
        base_cap = self._capacity0 or min(256, _next_pow2(graph.size))
        self.driver = BatchProgressiveDriver(
            graph, jnp.zeros((self.B, d), jnp.float32),
            ef=self.default_ef, k=1, capacity0=base_cap,
            max_capacity=self._max_capacity,
            max_signatures=self._max_signatures)
        log.note("swap", self.B, graph.size)
        self.driver.signatures = log
        self.graph = graph
        self.compressed = bool(quant.is_quantized(graph.vectors))

    # -- results ------------------------------------------------------------
    def result(self, lane: int) -> DiverseResult:
        """Solo-driver-compatible result for a finished lane."""
        k = int(self.ks[lane])
        ids = self.out_ids[lane, :k].copy()
        sc = self.out_sc[lane, :k].copy()
        return DiverseResult(ids.astype(np.int32), sc.astype(np.float32),
                             float(sc.sum()), self.driver.stats.lane_view(lane))

    def gather(self, k: int) -> BatchDiverseResult:
        """All-lane result at a uniform ``k`` (lockstep wrappers)."""
        ids = self.out_ids[:, :k].copy()
        sc = self.out_sc[:, :k].copy()
        return BatchDiverseResult(ids, sc, sc.sum(axis=1), self.driver.stats)

    # -- the state machine --------------------------------------------------
    def step(self) -> list[int]:
        """Advance every occupied lane one progressive round.

        Stage order (each stage batched over the lanes in that phase, masks
        recomputed between stages so same-step transitions flow downward —
        matching the solo drivers, which run e.g. the first PSS verification
        immediately after the PGS warm start with no search in between):

        1. search burst — PGS/PDS lanes stabilize their first K*ef.
        2. PGS round    — one fused diversify dispatch per group; grow K /
           warm-start PSS / finish.
        3. PDS round    — Theorem-1 degree schedule; update K / go final.
        4. PDS final    — one certified div-A*.
        5. PSS round    — div-A* + Theorem-2 certificate; uncertified lanes
           resume ProgressiveBeamSearch* below their minValue.

        Returns the lane indices that finished during this step.
        """
        self.steps += 1
        with span("engine.step", step=self.steps, lanes=self.active_count()):
            finished: list[int] = []
            smask = (self.status == LANE_PGS) | (self.status == LANE_PDS)
            stable = np.zeros(self.B, np.int64)
            if smask.any():
                targets = np.where(smask, self.K * self.efs, 0)
                stable = self.driver.ensure_stable(targets, active=smask)
            gmask = self.status == LANE_PGS
            if gmask.any():
                with span("diversify.pgs_round", lanes=int(gmask.sum())):
                    self._pgs_round(gmask, stable, finished)
            pmask = self.status == LANE_PDS
            if pmask.any():
                with span("verify.pds_round", lanes=int(pmask.sum())):
                    self._pds_round(pmask, stable)
            fmask = self.status == LANE_PDS_FIN
            if fmask.any():
                with span("verify.pds_final", lanes=int(fmask.sum())):
                    self._pds_final(fmask, finished)
            vmask = self.status == LANE_PSS
            if vmask.any():
                with span("verify.pss_round", lanes=int(vmask.sum())):
                    self._pss_round(vmask, finished)
            return finished

    def run_to_completion(self) -> None:
        while self.active_count():
            self.step()

    def _group_eps(self, idx: np.ndarray, g: int) -> jnp.ndarray:
        e = np.zeros(g, np.float32)
        e[:len(idx)] = self.epss[idx]
        return jnp.asarray(e)

    def _finish(self, lane: int, finished: list[int]) -> None:
        self.driver.stats.K_final[lane] = self.K[lane]
        self.status[lane] = LANE_DONE
        self._unharvested.append(int(lane))
        finished.append(int(lane))

    # Alg. 2 round: one fused diversification dispatch over the stabilized
    # prefix — masking, gather, G^eps adjacency, greedy selection and output
    # extraction all inside kops.fused_round_batch (a single pallas_call on
    # the kernel paths; see kernels/fused_round.py).
    def _pgs_round(self, gmask, stable, finished) -> None:
        d, n = self.driver, self.graph.size
        exhausted = gmask & (stable < np.minimum(self.K * self.efs, n))
        self.K = np.where(exhausted, np.maximum(self.K, stable), self.K)
        count = np.zeros(self.B, np.int64)
        for idx, ids, scores, Ks_pad in d.prefix_groups_raw(self.K, gmask,
                                                            ks=self.ks):
            k_g = int(self.ks[idx[0]])
            g, width = ids.shape
            d.signatures.note("fused_round", g, width, k_g)
            sel_ids, sel_sc, cnt, _cert = kops.fused_round_batch(
                self.graph.vectors, ids, scores, Ks_pad,
                self._group_eps(idx, g), k_g, self.graph.metric,
                impl=self.kernel_impl)
            with span("engine.sync", site="pgs_round"):
                cnt_np = np.asarray(cnt)
                sid_np, ssc_np = np.asarray(sel_ids), np.asarray(sel_sc)
            for gi, lane in enumerate(idx):
                count[lane] = cnt_np[gi]
                self.out_ids[lane, :k_g] = sid_np[gi]
                self.out_sc[lane, :k_g] = ssc_np[gi]
        d.stats.div_calls[gmask] += 1
        success = gmask & (count >= self.ks)
        ex_term = gmask & ~success & exhausted
        d.stats.exhausted |= ex_term
        cont = gmask & ~success & ~ex_term
        self.K = np.where(cont, self.K + self.ks, self.K)
        self.iters[cont] += 1
        iter_term = cont & (self.iters >= self.max_iters)
        for lane in np.flatnonzero(success | ex_term | iter_term):
            if self.to_pss[lane]:
                d.stats.K_final[lane] = self.K[lane]
                self.status[lane] = LANE_PSS
                self.iters[lane] = 0
            else:
                self._finish(lane, finished)

    # Alg. 3 round: Theorem-1 degree schedule for the next K.
    def _pds_round(self, pmask, stable) -> None:
        d, n = self.driver, self.graph.size
        K_new = np.zeros(self.B, np.int64)
        for idx, ids, scores in d.prefix_groups(self.K, pmask, ks=self.ks):
            k_g = int(self.ks[idx[0]])
            g, width = ids.shape
            d.signatures.note("adjacency", g, width)
            adj = _batched_adjacency(self.graph.vectors, ids,
                                     self._group_eps(idx, g),
                                     self.graph.metric)
            d.signatures.note("theorem1", g, width, k_g)
            kn = _batched_theorem1(adj, ids >= 0, k_g)
            with span("engine.sync", site="pds_round"):
                kn = np.asarray(kn)
            K_new[idx] = kn[:len(idx)]
        K_new = np.minimum(K_new, n)
        ex = pmask & (K_new > self.maxK)
        d.stats.exhausted |= ex
        fin_stable = pmask & ~ex & (stable >= np.minimum(K_new * self.efs, n))
        cont = pmask & ~ex & ~fin_stable
        self.K = np.where(fin_stable | cont, K_new, self.K)
        # (the per-query driver's third break — stable < min(K*ef, n) while
        # stable >= n — is vacuous and intentionally not replicated)
        self.iters[cont] += 1
        iter_term = cont & (self.iters >= self.max_iters)
        self.status[ex | fin_stable | iter_term] = LANE_PDS_FIN

    # Alg. 3 final: one certified div-A* over the scheduled prefix.
    def _pds_final(self, fmask, finished) -> None:
        d = self.driver
        for idx, ids, scores in d.prefix_groups(self.K, fmask, ks=self.ks):
            k_g = int(self.ks[idx[0]])
            g, width = ids.shape
            d.signatures.note("adjacency", g, width)
            adj = _batched_adjacency(self.graph.vectors, ids,
                                     self._group_eps(idx, g),
                                     self.graph.metric)
            d.signatures.note("div_astar", g, width, k_g)
            res, _ = _batched_div_astar(scores, ids, adj, k_g,
                                        self.max_expansions)
            with span("engine.sync", site="pds_final"):
                sets_np = np.asarray(res.best_sets)
                complete_np = np.asarray(res.complete)
                ids_np, sc_np = np.asarray(ids), np.asarray(scores)
            for gi, lane in enumerate(idx):
                s = sets_np[gi, k_g - 1]
                self.out_ids[lane, :k_g] = np.where(
                    s >= 0, ids_np[gi][np.maximum(s, 0)], -1)
                self.out_sc[lane, :k_g] = np.where(
                    s >= 0, sc_np[gi][np.maximum(s, 0)], 0.0)
                d.stats.certified[lane] = (bool(complete_np[gi])
                                           and not bool(d.stats.exhausted[lane]))
                if self.record_candidates:
                    # pds certificates are Theorem-1-shaped: no minValue
                    # slack to hand over — consumers must re-audit
                    Kl = int(min(self.K[lane], width))
                    self.last_candidates[lane] = (
                        ids_np[gi, :Kl].astype(np.int32).copy(),
                        sc_np[gi, :Kl].astype(np.float32).copy(), None)
        d.stats.div_calls[fmask] += 1
        for lane in np.flatnonzero(fmask):
            self._finish(lane, finished)

    # Alg. 4 round: div-A* + Theorem-2 certificate, then resumption.
    def _pss_round(self, vmask, finished) -> None:
        d, n = self.driver, self.graph.size
        over = vmask & (self.iters >= self.max_iters)
        for lane in np.flatnonzero(over):
            self._finish(lane, finished)
        mask = vmask & ~over
        if not mask.any():
            return
        self.iters[mask] += 1
        self.K = np.where(mask, np.maximum(self.ks, np.minimum(self.K, n)),
                          self.K)
        min_values = np.full(self.B, -np.inf)
        s_K = np.full(self.B, -np.inf)
        complete = np.zeros(self.B, bool)
        for idx, ids, scores in d.prefix_groups(self.K, mask, ks=self.ks):
            k_g = int(self.ks[idx[0]])
            g, width = ids.shape
            d.signatures.note("adjacency", g, width)
            adj = _batched_adjacency(self.graph.vectors, ids,
                                     self._group_eps(idx, g),
                                     self.graph.metric)
            d.signatures.note("div_astar", g, width, k_g)
            res, mv = _batched_div_astar(scores, ids, adj, k_g,
                                         self.max_expansions)
            with span("engine.sync", site="pss_round"):
                best_scores_np = np.asarray(res.best_scores)
                sets_np = np.asarray(res.best_sets)
                complete_np = np.asarray(res.complete)
                mv_np = np.asarray(mv, np.float64)
                ids_np, sc_np = np.asarray(ids), np.asarray(scores)
            for gi, lane in enumerate(idx):
                complete[lane] = complete_np[gi]
                min_values[lane] = mv_np[gi]
                if np.isfinite(best_scores_np[gi, k_g - 1]):
                    s = sets_np[gi, k_g - 1]
                    self.out_ids[lane, :k_g] = np.where(
                        s >= 0, ids_np[gi][np.maximum(s, 0)], -1)
                    self.out_sc[lane, :k_g] = np.where(
                        s >= 0, sc_np[gi][np.maximum(s, 0)], 0.0)
                s_K[lane] = (sc_np[gi, self.K[lane] - 1]
                             if self.K[lane] <= width else -np.inf)
                if self.record_candidates:
                    Kl = int(min(self.K[lane], width))
                    self.last_candidates[lane] = (
                        ids_np[gi, :Kl].astype(np.int32).copy(),
                        sc_np[gi, :Kl].astype(np.float32).copy(),
                        float(min_values[lane] - s_K[lane]))
        d.stats.div_calls[mask] += 1
        certified = mask & (min_values > s_K)
        d.stats.certified |= certified & complete
        stop = mask & ~certified & (d.stats.exhausted | (self.K >= n))
        for lane in np.flatnonzero(certified | stop):
            self._finish(lane, finished)
        rem = mask & ~certified & ~stop
        if not rem.any():
            return
        stable_before = d.stable_prefix_len()
        stable = d.expand_until_below(np.asarray(min_values, np.float32), rem)
        no_prog = rem & (stable <= stable_before)
        d.stats.exhausted |= no_prog
        hard = no_prog & ((stable >= n) | (d.caps >= d.max_capacity))
        self.K = np.where(rem & hard, np.minimum(stable, n), self.K)
        self.K = np.where(rem & ~hard,
                          np.maximum(self.ks, stable // self.efs), self.K)

    # -- prewarm ------------------------------------------------------------
    def prewarm(self, *, max_capacity: int | None = None,
                ks: tuple = (), widths: tuple = ()) -> list[tuple]:
        """Compile the capacity ladder ahead of serving.

        Walks the power-of-two physical capacities from the current one up to
        ``max_capacity`` (default: the driver's max) and compiles the search
        burst and its stable count, lane recycle, the pad to each higher
        rung, and every power-of-two growth-bucket rebuild at each rung, and
        the query write, using throwaway states (the live lane state is untouched
        and the physical capacity is NOT grown — growth stays on-demand; this
        only fills XLA's compile cache so mid-serving growth never pays a
        trace). Optionally pre-compiles the diversify/verify stages for the
        given ``ks`` x ``widths`` grids. Returns the signatures warmed.
        """
        d = self.driver
        top = min(max_capacity or d.max_capacity, d.max_capacity)
        dim = int(self.graph.vectors.shape[1])
        qs0 = jnp.zeros((self.B, dim), jnp.float32)
        caps_ladder = []
        c = d.physical_capacity
        while True:
            caps_ladder.append(c)
            if c >= top:
                break
            c *= 2
        group_sizes = pow2_group_sizes(self.B)
        warmed: list[tuple] = []

        def note(kind, *shape):
            d.signatures.note(kind, *shape)
            warmed.append((kind, *shape))

        zeros_b = jnp.zeros(self.B, jnp.int32)
        _set_row(qs0, 0, jnp.zeros(dim, jnp.float32))
        note("set_query", self.B)
        for i, cap in enumerate(caps_ladder):
            state = lane_state.init_lanes(self.graph, qs0, cap)
            note("init", self.B, cap)
            # zero step budget: compiles the burst, executes nothing
            _batched_search_loop(
                self.graph.vectors, self.graph.neighbors, qs0, state,
                jnp.full(self.B, cap, jnp.int32), zeros_b,
                jnp.zeros(self.B, jnp.float32), zeros_b, self.graph.metric
            ).queue.ids.block_until_ready()
            note("search", self.B, cap)
            _batched_stable_count(state.queue)
            note("stable_count", self.B, cap)
            for to in caps_ladder[i + 1:]:
                lane_state.pad_lanes(state, to)
                note("pad", self.B, cap, to)
            lane_state.recycle_lane(self.graph, state, 0,
                                    np.zeros(dim, np.float32))
            note("recycle", self.B, cap)
            for g in group_sizes:
                sub = lane_state.select_lanes(state,
                                              jnp.zeros(g, jnp.int32))
                sub = lane_state.slice_queue_capacity(sub, cap)
                _rebuild_lanes(self.graph, jnp.zeros((g, dim), jnp.float32),
                               sub, cap)
                note("rebuild", g, cap)
        for k in ks:
            for width in widths:
                for g in group_sizes:
                    ids = jnp.full((g, width), -1, jnp.int32)
                    sc = jnp.full((g, width), -jnp.inf, jnp.float32)
                    note("prefix", g, width)
                    _mask_prefix(ids, sc, jnp.zeros(g, jnp.int32))
                    note("adjacency", g, width)
                    adj = _batched_adjacency(self.graph.vectors, ids,
                                             jnp.zeros(g, jnp.float32),
                                             self.graph.metric)
                    note("greedy", g, width, k)
                    kops.greedy_diversify_batch(sc, adj, k, valid=ids >= 0)
                    note("fused_round", g, width, k)
                    kops.fused_round_batch(self.graph.vectors, ids, sc,
                                           np.zeros(g, np.int64),
                                           jnp.zeros(g, jnp.float32),
                                           k, self.graph.metric,
                                           impl=self.kernel_impl)
                    note("theorem1", g, width, k)
                    _batched_theorem1(adj, ids >= 0, k)
                    note("div_astar", g, width, k)
                    _batched_div_astar(sc, ids, adj, k, self.max_expansions)
        return warmed


# ------------------------------------------------------- lockstep wrappers --

def _run_lockstep(graph: FlatGraph, qs, k: int, eps: float, ef: int,
                  method: str, max_iters: int, max_expansions: int,
                  driver: BatchProgressiveDriver | None = None,
                  max_K: int | None = None,
                  kernel_impl: str | None = None
                  ) -> tuple[BatchDiverseResult, ProgressiveEngine]:
    qs = jnp.asarray(qs, jnp.float32)
    if driver is None:
        driver = BatchProgressiveDriver(graph, qs, ef, k)
    engine = ProgressiveEngine(graph, driver=driver, max_k=k, default_ef=ef,
                               max_iters=max_iters,
                               max_expansions=max_expansions,
                               kernel_impl=kernel_impl)
    for lane in range(driver.B):
        engine.admit_in_place(lane, k=k, eps=eps, ef=ef, method=method,
                              max_K=max_K)
    engine.run_to_completion()
    return engine.gather(k), engine


def batch_pgs(graph: FlatGraph, qs, k: int, eps: float, ef: int = 40,
              driver: BatchProgressiveDriver | None = None,
              max_iters: int = 64
              ) -> tuple[BatchDiverseResult, BatchProgressiveDriver, np.ndarray]:
    """Batched Alg. 2: returns (result, driver, K_final) — batch_pss reuses
    the driver and per-lane K exactly like the per-query pgs/pss pair."""
    res, engine = _run_lockstep(graph, qs, k, eps, ef, "pgs", max_iters,
                                400_000, driver=driver)
    return res, engine.driver, engine.K.copy()


def batch_pds(graph: FlatGraph, qs, k: int, eps: float, ef: int = 40,
              max_K: int | None = None, max_iters: int = 64,
              max_expansions: int = 400_000) -> BatchDiverseResult:
    """Batched Alg. 3 (Theorem-1 degree schedule): per-lane results identical
    to the per-query ``pds`` driver."""
    res, _ = _run_lockstep(graph, qs, k, eps, ef, "pds", max_iters,
                           max_expansions, max_K=max_K)
    return res


def _concat_results(parts: list[BatchDiverseResult]) -> BatchDiverseResult:
    stats = BatchSearchStats(*[
        np.concatenate([getattr(p.stats, f.name) for p in parts])
        for f in dataclasses.fields(BatchSearchStats)])
    return BatchDiverseResult(np.vstack([p.ids for p in parts]),
                              np.vstack([p.scores for p in parts]),
                              np.concatenate([p.totals for p in parts]),
                              stats)


def batch_pss(graph: FlatGraph, qs, k: int, eps: float, ef: int = 40,
              max_iters: int = 64, max_expansions: int = 400_000,
              streams: int = 1,
              kernel_impl: str | None = None) -> BatchDiverseResult:
    """Batched Alg. 4 — the lockstep engine entry point.

    Phase 1 runs batched PGS (warm start + a size-k diverse set exists among
    the candidates). Each round then builds every active lane's G^eps, runs
    batched div-A*, applies the Theorem-2 certificate per lane, and resumes
    ProgressiveBeamSearch* only for the uncertified lanes. Per-lane results
    are identical to the per-query ``pss`` driver. (For continuous batching —
    new queries admitted into lanes freed by certified ones — drive
    ``ProgressiveEngine`` through ``serve.scheduler.LaneScheduler``.)

    ``streams > 1`` splits the batch into that many sub-batches driven from
    worker threads, overlapping host orchestration with device work (jax
    dispatch releases the GIL). Every lane's trajectory is independent of
    its batch, so streaming changes nothing about the results; ``streams=2``
    is the measured sweet spot on CPU hosts.
    """
    qs = jnp.asarray(qs, jnp.float32)
    if streams > 1 and qs.shape[0] > 1:
        parts = np.array_split(np.arange(qs.shape[0]),
                               min(streams, qs.shape[0]))
        with concurrent.futures.ThreadPoolExecutor(len(parts)) as ex:
            futs = [ex.submit(batch_pss, graph, qs[jnp.asarray(c)], k, eps,
                              ef, max_iters, max_expansions, 1, kernel_impl)
                    for c in parts]
            return _concat_results([f.result() for f in futs])
    res, _ = _run_lockstep(graph, qs, k, eps, ef, "pss", max_iters,
                           max_expansions, kernel_impl=kernel_impl)
    return res


def batch_progressive_search(graph: FlatGraph, qs, k: int, eps: float,
                             method: str = "pss", ef: int = 40,
                             **kwargs) -> BatchDiverseResult:
    """One entry point for the batched progressive engine."""
    if method == "pss":
        return batch_pss(graph, qs, k, eps, ef, **kwargs)
    if method == "pds":
        return batch_pds(graph, qs, k, eps, ef, **kwargs)
    if method == "pgs":
        res, _, _ = batch_pgs(graph, qs, k, eps, ef, **kwargs)
        return res
    raise ValueError(f"unknown batched progressive method {method!r}")
