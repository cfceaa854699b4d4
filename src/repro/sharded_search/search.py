"""Distributed ADk-NNS: the paper's technique mapped onto a device mesh.

Scale-out story (DESIGN.md §2/§5): the database is partitioned into P shards
along the mesh's data axis (pod x data at multi-pod scale). Each device owns
one shard's proximity graph and runs the *same* fixed-shape beam search as
the single-device path (shard-local candidates carry global ids). Results
combine via a **tournament merge**: log2(P) butterfly rounds of
``ppermute`` + bitonic ``topk_merge``, so each device moves O(L log P) bytes
instead of the O(L * P) an all-gather-then-sort would ship. Diversification
(greedy or div-A*) then runs on the replicated merged candidates — its cost
is independent of N, exactly the paper's candidates-then-diversify split.

Naive all-gather merge is kept as ``merge="allgather"`` for the §Perf
baseline/optimized comparison.

Progressive resumption (the paper's pause/inspect/resume at mesh scale):
the budget-doubling ladder used to re-run every shard-local beam from
scratch at each rung. ``ShardedSearchState`` now carries each lane's
per-shard queue + visited set across rounds — ``sharded_topk_resume``
re-enters ``beam_search.resume_search`` under the widened stable limit, so
a doubled budget continues expanding from the previous frontier.
``sharded_topk`` / ``sharded_diverse_search`` remain the scratch halves
(one fixed budget, no state) and stay the bit-parity reference; both paths
share the same tournament merge over harvested frontiers and the same
replicated diversify stage.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import quant
from repro.core import beam_search as bs
from repro.core import div_astar as da
from repro.core import queue as qmod
from repro.core.bucketing import next_pow2
from repro.core.graph import make_flat_graph
from repro.core.theorems import theorem2_min_value
from repro.kernels import ops as kops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    """Per-shard graphs stacked on a leading shard axis.

    Float corpora live in ``vectors``. Quantized corpora (``scheme`` set by
    ``build_sharded_index(quantized=...)``) instead carry ``codes`` — plus
    ``scales`` (int8) or ``codebooks`` (pq, replicated, trained at index
    build) — sharded alongside the graph; ``vectors`` is then None and the
    float rows are retained *host-side by the caller* for the exact rerank
    stage (quantization is a memory knob, never a certificate knob:
    ``docs/ARCHITECTURE.md`` contract 13).
    """
    vectors: jnp.ndarray | None      # f32[P, Ns, d]; None when quantized
    neighbors: jnp.ndarray           # int32[P, Ns, M0]
    entries: jnp.ndarray             # int32[P] or int32[P, E] candidates
    bases: jnp.ndarray               # int32[P] global-id base of each shard
    codes: jnp.ndarray | None = None       # int8[P, Ns, d] | uint8[P, Ns, M]
    scales: jnp.ndarray | None = None      # f32[P, nb]   (int8 scheme)
    codebooks: jnp.ndarray | None = None   # f32[M, C, ds] (pq, replicated)
    metric: str = dataclasses.field(metadata=dict(static=True), default="l2")
    scheme: str | None = dataclasses.field(metadata=dict(static=True),
                                           default=None)
    scale_rows: int = dataclasses.field(metadata=dict(static=True), default=8)

    @property
    def num_shards(self) -> int:
        return self.neighbors.shape[0]

    @property
    def shard_size(self) -> int:
        return self.neighbors.shape[1]

    @property
    def dim(self) -> int:
        if self.scheme == "pq":
            m, _, ds = self.codebooks.shape
            return m * ds
        if self.scheme == "int8":
            return self.codes.shape[-1]
        return self.vectors.shape[-1]

    def corpus_bytes_per_vector(self) -> float:
        """Stored corpus bytes per vector on a device (graph excluded;
        replicated PQ codebooks amortized over one shard — the honest
        per-device number)."""
        ns = self.shard_size
        if self.scheme == "int8":
            return (ns * self.codes.shape[-1] + self.scales.shape[-1] * 4) / ns
        if self.scheme == "pq":
            return (ns * self.codes.shape[-1] + self.codebooks.size * 4) / ns
        return 4.0 * self.dim


def _corpus_parts(index: ShardedIndex):
    """The corpus operands a shard_map dispatch needs.

    Returns ``(arrays, kinds, make)``: operand arrays, a "shard"/"repl"
    placement per operand, and a closure rebuilding the device-local corpus
    (float array or quantized corpus object) from the mapped blocks. Both
    the scratch and the resume dispatch build their operand list from this
    one helper, so the two paths cannot drift.
    """
    if index.scheme is None:
        return (index.vectors,), ("shard",), lambda a: a[0][0]
    if index.scheme == "int8":
        sr = index.scale_rows
        return ((index.codes, index.scales), ("shard", "shard"),
                lambda a: quant.Int8Corpus(codes=a[0][0], scales=a[1][0],
                                           scale_rows=sr))
    return ((index.codes, index.codebooks), ("shard", "repl"),
            lambda a: quant.PQCorpus(codes=a[0][0], codebooks=a[1]))


def _stack_entries(entries) -> jnp.ndarray:
    """Stack per-shard entry candidates into one [P, E] array; a shard with
    fewer candidates repeats its first (the medoid), which changes no
    search's start."""
    entries = [np.atleast_1d(np.asarray(e, np.int32)) for e in entries]
    width = max(e.size for e in entries)
    return jnp.asarray(np.stack([
        np.concatenate([e, np.full(width - e.size, e[0], np.int32)])
        for e in entries]))


def build_sharded_index(vectors: np.ndarray, num_shards: int, metric: str,
                        M: int = 16, builder="knng",
                        quantized: str | None = None, scale_rows: int = 8,
                        pq_m: int | None = None, pq_codes: int = 256,
                        pq_iters: int = 10, pq_sample: int = 16384,
                        seed: int = 0, dead: np.ndarray | None = None
                        ) -> ShardedIndex:
    """Partition the database round-robin and build one graph per shard.

    ``quantized`` in {None, "int8", "pq"} selects the on-device corpus
    representation: graphs are always built from the float rows, but with a
    scheme set each shard stores only compressed codes (int8: one f32 scale
    per ``scale_rows`` rows; pq: uint8 codebook indices, codebooks k-means
    trained here on the full corpus and replicated; ``pq_m=None`` picks
    ``quant.default_pq_m`` for the corpus width). Callers keep the float
    ``vectors`` host-side for the exact rerank stage. ``dead`` (bool[n],
    tombstones) keeps those rows in place but links only the live rows of
    each shard's graph (``core.graph.embed_live_graph``).
    """
    from repro.core.graph import embed_live_graph
    from repro.index.flat import build_knn_graph
    from repro.index.hnsw import build_hnsw

    n = vectors.shape[0]
    ns = n // num_shards
    assert ns * num_shards == n, "dataset must split evenly across shards"
    pq_global = None
    if quantized == "pq":
        if pq_m is None:
            pq_m = quant.default_pq_m(int(vectors.shape[-1]))
        pq_global = quant.train_pq(np.asarray(vectors, np.float32), m=pq_m,
                                   codes=pq_codes, iters=pq_iters, seed=seed,
                                   sample=pq_sample)
    elif quantized is not None and quantized not in quant.QUANT_SCHEMES:
        raise ValueError(f"unknown quantized scheme {quantized!r}; "
                         f"expected one of {quant.QUANT_SCHEMES} or None")
    vecs, nbrs, entries, bases = [], [], [], []
    codes, scales = [], []
    for s in range(num_shards):
        chunk = np.asarray(vectors[s * ns:(s + 1) * ns], np.float32)
        live = (None if dead is None or not dead[s * ns:(s + 1) * ns].any()
                else np.flatnonzero(~dead[s * ns:(s + 1) * ns]))
        if live is not None and live.size <= M + 1:
            live = None   # too few live rows for a graph: link them all
        rows = chunk if live is None else chunk[live]
        if builder == "hnsw":
            g = build_hnsw(rows, metric=metric, M=M)
        else:
            g = build_knn_graph(rows, metric=metric, M=M)
        if live is not None:
            g = embed_live_graph(g, live, chunk)
        vecs.append(np.asarray(g.vectors))
        nbrs.append(np.asarray(g.neighbors))
        entries.append(np.asarray(g.entry))
        bases.append(s * ns)
        if quantized == "int8":
            c = quant.quantize_int8(chunk, scale_rows=scale_rows)
            codes.append(np.asarray(c.codes))
            scales.append(np.asarray(c.scales))
        elif quantized == "pq":
            codes.append(quant.pq_encode(chunk,
                                         np.asarray(pq_global.codebooks)))
    m0 = max(a.shape[1] for a in nbrs)
    nbrs = [np.pad(a, ((0, 0), (0, m0 - a.shape[1])), constant_values=-1)
            for a in nbrs]
    return ShardedIndex(
        vectors=None if quantized else jnp.asarray(np.stack(vecs)),
        neighbors=jnp.asarray(np.stack(nbrs)),
        entries=_stack_entries(entries),
        bases=jnp.asarray(np.array(bases, np.int32)),
        codes=jnp.asarray(np.stack(codes)) if quantized else None,
        scales=jnp.asarray(np.stack(scales)) if quantized == "int8" else None,
        codebooks=(jnp.asarray(pq_global.codebooks)
                   if quantized == "pq" else None),
        metric=metric,
        scheme=quantized,
        scale_rows=int(scale_rows),
    )


def reshard_index(index: ShardedIndex, num_shards: int,
                  all_vectors=None, *, M: int | None = None,
                  builder: str = "knng") -> ShardedIndex:
    """Repartition a ``ShardedIndex`` across a new power-of-two shard count.

    The partition is round-robin contiguous (shard ``s`` owns global rows
    ``[s*ns, (s+1)*ns)``), so repartitioning is a pure re-blocking of the
    stacked row arrays: global ids never move, and a quantized corpus's
    codes/scales are re-blocked **exactly** — no requantization (int8 scale
    blocks are ``scale_rows``-row aligned, which must divide the new shard
    size; PQ codebooks are replicated and untouched). Per-shard proximity
    graphs are shard-local structures and are rebuilt deterministically
    over each new partition from the float rows — resharding is a capacity
    knob, never a results knob (``docs/ARCHITECTURE.md`` contract 16), so
    a reshard round trip (4 -> 8 -> 4 with the same build parameters) is
    bit-identical to the original.

    ``all_vectors`` is the host-retained float corpus, required when the
    index is quantized (``vectors`` is None); ``M``/``builder`` must match
    the original build (``M`` defaults to the stored neighbor width
    divided by 2 — ``build_knn_graph``'s ``M0 = 2 * M`` — which is only
    correct for the default ``knng`` builder).
    """
    p_old, ns_old = index.num_shards, index.shard_size
    n = p_old * ns_old
    if num_shards & (num_shards - 1) or num_shards < 1:
        raise ValueError(f"num_shards={num_shards} must be a power of two "
                         "(tournament merge)")
    if n % num_shards:
        raise ValueError(f"corpus of {n} rows does not split across "
                         f"{num_shards} shards")
    if num_shards == p_old:
        return index
    ns_new = n // num_shards
    if index.scheme == "int8" and (ns_old % index.scale_rows
                                   or ns_new % index.scale_rows):
        raise ValueError(
            f"int8 scale blocks ({index.scale_rows} rows) must divide both "
            f"shard sizes ({ns_old} -> {ns_new}); rebuild instead of "
            "resharding")
    if index.vectors is not None:
        flat = np.asarray(index.vectors).reshape(n, -1)
    elif all_vectors is not None:
        flat = np.asarray(all_vectors, np.float32)[:n]
    else:
        raise ValueError("resharding a quantized index needs the "
                         "host-retained float corpus (all_vectors=)")
    if M is None:
        M = index.neighbors.shape[-1] // 2

    from repro.index.flat import build_knn_graph
    from repro.index.hnsw import build_hnsw

    vecs, nbrs, entries = [], [], []
    for s in range(num_shards):
        chunk = flat[s * ns_new:(s + 1) * ns_new]
        if builder == "hnsw":
            g = build_hnsw(chunk, metric=index.metric, M=M)
        else:
            g = build_knn_graph(chunk, metric=index.metric, M=M)
        vecs.append(np.asarray(g.vectors))
        nbrs.append(np.asarray(g.neighbors))
        entries.append(np.asarray(g.entry))
    m0 = max(a.shape[1] for a in nbrs)
    nbrs = [np.pad(a, ((0, 0), (0, m0 - a.shape[1])), constant_values=-1)
            for a in nbrs]
    codes = scales = None
    if index.codes is not None:
        c = np.asarray(index.codes)
        codes = jnp.asarray(c.reshape(n, *c.shape[2:])
                            .reshape(num_shards, ns_new, *c.shape[2:]))
    if index.scales is not None:
        sc = np.asarray(index.scales)
        scales = jnp.asarray(sc.reshape(-1).reshape(num_shards, -1))
    return ShardedIndex(
        vectors=None if index.scheme else jnp.asarray(np.stack(vecs)),
        neighbors=jnp.asarray(np.stack(nbrs)),
        entries=_stack_entries(entries),
        bases=jnp.asarray(np.arange(num_shards, dtype=np.int32) * ns_new),
        codes=codes,
        scales=scales,
        codebooks=index.codebooks,
        metric=index.metric,
        scheme=index.scheme,
        scale_rows=index.scale_rows,
    )


def _local_topk(vectors, neighbors, entry, base, qs, metric: str,
                k: int, L: int):
    """Shard-local beam search for a query batch; returns GLOBAL ids plus
    the per-lane expansion (step) counts."""
    graph = make_flat_graph(vectors, neighbors, None, entry, metric)

    def one(q):
        state = bs.init_state(graph, q, L, use_descent=False)
        state = bs.run_search(graph, q, state, stable_limit=L)
        ids = state.queue.ids[:k]
        return (jnp.where(ids >= 0, ids + base, -1),
                state.queue.scores[:k], state.steps)

    return jax.vmap(one)(qs)


def _tournament_merge(ids, scores, axis: str, p: int):
    """Butterfly merge: after log2(p) rounds every device holds global top-k."""
    assert p & (p - 1) == 0, "tournament merge needs power-of-two shards"
    rounds = p.bit_length() - 1
    for r in range(rounds):
        stride = 1 << r
        perm = [(i, i ^ stride) for i in range(p)]
        other_ids = jax.lax.ppermute(ids, axis, perm)
        other_scores = jax.lax.ppermute(scores, axis, perm)
        merged = jax.vmap(kops.topk_merge)(ids, scores, other_ids, other_scores)
        ids, scores = merged
    return ids, scores


def _allgather_merge(ids, scores, axis: str, k: int):
    all_ids = jax.lax.all_gather(ids, axis, axis=1)       # [B, P, k]
    all_scores = jax.lax.all_gather(scores, axis, axis=1)
    b = ids.shape[0]
    flat_ids = all_ids.reshape(b, -1)
    flat_scores = all_scores.reshape(b, -1)

    def pick(i, s):
        order = jnp.lexsort((i, -s))[:k]
        return i[order], s[order]

    return jax.vmap(pick)(flat_ids, flat_scores)


def sharded_topk(index: ShardedIndex, qs: jnp.ndarray, k: int, L: int,
                 mesh: Mesh, axis: str = "data", merge: str = "tournament",
                 with_expansions: bool = False):
    """Global top-k over all shards; output replicated on every device.

    This is the *scratch* half: every call restarts each shard-local beam at
    its entry point (see ``sharded_topk_resume`` for the stateful half).
    With ``with_expansions`` the per-lane expansion counts summed over
    shards come back as a third output. Quantized indexes score compressed
    codes inside the shard_map — same loop, same merge; only the scoring
    representation changes.
    """
    p = index.num_shards
    arrays, kinds, make = _corpus_parts(index)
    nc = len(arrays)

    def shard_fn(*args):
        corpus = make(args[:nc])
        neighbors, entries, bases, qs = args[nc:]
        ids, scores, steps = _local_topk(corpus, neighbors[0], entries[0],
                                         bases[0], qs, index.metric, k, L)
        if p > 1:
            if merge == "tournament":
                ids, scores = _tournament_merge(ids, scores, axis, p)
            else:
                ids, scores = _allgather_merge(ids, scores, axis, k)
        return ids, scores, jax.lax.psum(steps, axis)

    shard_spec = P(axis)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(shard_spec if kd == "shard" else P() for kd in kinds)
        + (shard_spec, shard_spec, shard_spec, P()),
        out_specs=(P(), P(), P()), check_vma=False,
    )
    ids, scores, expansions = fn(*arrays, index.neighbors,
                                 index.entries, index.bases, qs)
    if with_expansions:
        return ids, scores, expansions
    return ids, scores


# ------------------------------------------------- resumable shard beams ----

class ShardedSearchState(NamedTuple):
    """Fixed-shape per-lane, per-shard beam state carried across budget
    rounds (leading axis sharded along the mesh's data axis).

    One lane's slice ``(ids[s, b], scores[s, b], stable[s, b], visited[s, b],
    steps[s, b])`` is exactly a ``beam_search.SearchState`` for that lane's
    beam on shard ``s``. Capacity is sized once, at the lane's max beam
    width (``beam_state_capacity``), so the queue never changes shape as the
    budget ladder doubles — the "wider queue" of each rung is the same
    queue under a wider stable limit.
    """
    ids: jnp.ndarray      # int32[P, B, C] shard-local candidate ids
    scores: jnp.ndarray   # f32[P, B, C]
    stable: jnp.ndarray   # bool[P, B, C]
    visited: jnp.ndarray  # bool[P, B, Ns]
    steps: jnp.ndarray    # int32[P, B]

    @property
    def capacity(self) -> int:
        return self.ids.shape[-1]


def beam_state_capacity(index: ShardedIndex, K_max: int,
                        L_factor: int = 4) -> int:
    """Queue width for resumable shard-local beams.

    Wide enough that either no dispatched rung's beam (``K * L_factor``)
    ever drops a candidate, or the whole shard fits — the precondition for
    the first round being bit-exact with the scratch search at the narrow
    width (see ``beam_search.resume_search``'s widening contract).
    """
    return min(next_pow2(max(int(K_max) * int(L_factor), 1)),
               next_pow2(index.shard_size))


def init_sharded_state(index: ShardedIndex, num_lanes: int, capacity: int,
                       mesh: Mesh | None = None,
                       axis: str = "data") -> ShardedSearchState:
    """Empty (all lanes unseeded) state, device-sharded along ``axis``."""
    p, ns = index.num_shards, index.shard_size
    leaves = ShardedSearchState(
        ids=jnp.full((p, num_lanes, capacity), -1, jnp.int32),
        scores=jnp.full((p, num_lanes, capacity), -jnp.inf, jnp.float32),
        stable=jnp.ones((p, num_lanes, capacity), jnp.bool_),
        visited=jnp.zeros((p, num_lanes, ns), jnp.bool_),
        steps=jnp.zeros((p, num_lanes), jnp.int32),
    )
    if mesh is None:
        return leaves
    sharding = NamedSharding(mesh, P(axis))
    return ShardedSearchState(
        *(jax.device_put(leaf, sharding) for leaf in leaves))


def migrate_sharded_state(state: ShardedSearchState, num_shards: int,
                          capacity: int | None = None,
                          mesh: Mesh | None = None,
                          axis: str = "data",
                          num_lanes: int | None = None) -> ShardedSearchState:
    """Re-bucket in-flight per-lane beam state onto a new shard layout.

    The contiguous partition makes every queue entry's global id
    ``local + s * ns``; migration maps each entry to its new shard, re-sorts
    every (lane, shard) queue under the canonical (score desc, id asc)
    order, and re-blocks the visited bitmap — set bits follow their global
    row, so no expansion is ever redone after a scale event. ``steps``
    preserves each lane's per-shard totals (a split shard's counter rides
    on its first child; merged shards sum), which keeps both the engine's
    cumulative-expansion counters and ``resume_search``'s relative step
    budget exact. With the engine-default capacity
    (``beam_state_capacity``) no entry can be dropped: a new shard holds at
    most ``ns_new <= capacity`` distinct ids.

    ``num_lanes`` resizes the lane axis alongside the shard axis (serving
    capacity follows the mesh): extra lanes are appended empty (unseeded),
    a smaller count keeps lanes ``[:num_lanes]`` verbatim and drops the
    tail — the caller is responsible for only dropping lanes whose beams
    are dead (the engine drops ``LANE_FREE`` tails only).

    Host-side by design — scale events are rare, and the migrated pytree is
    ``device_put`` onto ``mesh`` exactly like ``init_sharded_state``.
    """
    ids = np.asarray(state.ids)
    scores = np.asarray(state.scores)
    stable = np.asarray(state.stable)
    visited = np.asarray(state.visited)
    steps = np.asarray(state.steps)
    p_old, B, C_old = ids.shape
    ns_old = visited.shape[-1]
    n = p_old * ns_old
    if num_shards & (num_shards - 1) or n % num_shards:
        raise ValueError(f"cannot migrate {p_old}x{ns_old} beam state to "
                         f"{num_shards} shards")
    ns_new = n // num_shards
    C_new = int(capacity or C_old)

    # queue entries -> global ids, flattened over the old shard axis
    bases_old = (np.arange(p_old, dtype=np.int64) * ns_old)[:, None, None]
    gids = np.where(ids >= 0, ids.astype(np.int64) + bases_old, -1)
    gids = gids.transpose(1, 0, 2).reshape(B, -1)       # [B, p_old*C_old]
    sc = scores.transpose(1, 0, 2).reshape(B, -1)
    st = stable.transpose(1, 0, 2).reshape(B, -1)

    new_ids = np.full((num_shards, B, C_new), -1, np.int32)
    new_sc = np.full((num_shards, B, C_new), -np.inf, np.float32)
    new_st = np.ones((num_shards, B, C_new), np.bool_)
    for s in range(num_shards):
        lo, hi = s * ns_new, (s + 1) * ns_new
        for b in range(B):
            sel = (gids[b] >= lo) & (gids[b] < hi)
            g, s_b, t_b = gids[b][sel], sc[b][sel], st[b][sel]
            if len(g) > C_new:
                # silently dropping beam candidates would void the widening
                # contract the same way an under-floor state_capacity does
                raise ValueError(
                    f"capacity {C_new} cannot hold the {len(g)} migrated "
                    f"candidates of lane {b} shard {s}; size the target "
                    "state with beam_state_capacity")
            order = np.lexsort((g, -s_b))
            m = len(order)
            new_ids[s, b, :m] = (g[order] - lo).astype(np.int32)
            new_sc[s, b, :m] = s_b[order]
            new_st[s, b, :m] = t_b[order]

    new_vis = (visited.transpose(1, 0, 2).reshape(B, n)
               .reshape(B, num_shards, ns_new).transpose(1, 0, 2))
    if num_shards >= p_old:
        f = num_shards // p_old
        new_steps = np.zeros((num_shards, B), np.int32)
        new_steps[::f] = steps
    else:
        f = p_old // num_shards
        new_steps = steps.reshape(num_shards, f, B).sum(axis=1,
                                                        dtype=np.int32)
    B_new = int(num_lanes or B)
    if B_new != B:
        def _lanes(a, fill):
            out = np.full(a.shape[:1] + (B_new,) + a.shape[2:], fill,
                          a.dtype)
            out[:, :min(B, B_new)] = a[:, :min(B, B_new)]
            return out
        new_ids = _lanes(new_ids, -1)
        new_sc = _lanes(new_sc, -np.inf)
        new_st = _lanes(new_st, True)
        new_vis = _lanes(new_vis, False)
        new_steps = _lanes(new_steps, 0)
    leaves = ShardedSearchState(
        ids=jnp.asarray(new_ids), scores=jnp.asarray(new_sc),
        stable=jnp.asarray(new_st), visited=jnp.asarray(new_vis),
        steps=jnp.asarray(new_steps))
    if mesh is None:
        return leaves
    sharding = NamedSharding(mesh, P(axis))
    return ShardedSearchState(
        *(jax.device_put(leaf, sharding) for leaf in leaves))


_RESUME_DISPATCH_FNS: dict[tuple, object] = {}


def _resume_dispatch_fn(index: ShardedIndex, mesh: Mesh, axis: str, K: int,
                        C: int, merge: str):
    """Jitted shard_map dispatch for one (mesh, K-harvest, capacity) rung.

    Cached on its static key — which includes the corpus scheme, so float
    and quantized indexes never share a rung — so repeat traffic re-enters
    the same jit callable; the resume path's equivalent of the single-host
    engine's module-level jits (``resume_jit_cache_sizes`` audits these).
    """
    metric, p = index.metric, index.num_shards
    key = (mesh, axis, metric, p, K, C, merge, index.scheme,
           index.scale_rows)
    fn = _RESUME_DISPATCH_FNS.get(key)
    if fn is not None:
        return fn
    _, kinds, make = _corpus_parts(index)
    nc = len(kinds)

    def shard_fn(*args):
        corpus = make(args[:nc])
        (neighbors, entries, bases, s_ids, s_sc, s_st, s_vis, s_steps,
         qs, idx, fresh, limit, budget) = args[nc:]
        graph = make_flat_graph(corpus, neighbors[0], None, entries[0],
                                metric)
        base = bases[0]
        ids_b, sc_b, st_b = s_ids[0], s_sc[0], s_st[0]       # [B, C]
        vis_b, steps_b = s_vis[0], s_steps[0]                # [B, Ns], [B]

        def one(q, f, ids, sc, st, vis, steps):
            cur = bs.SearchState(qmod.Queue(ids, sc, st), vis, steps)
            seeded = bs.init_state(graph, q, C, use_descent=False)
            cur = jax.tree_util.tree_map(
                lambda a, b: jnp.where(f, a, b), seeded, cur)
            cur = bs.resume_search(graph, q, cur, stable_limit=limit,
                                   step_budget=budget)
            h = min(K, C)
            hid = cur.queue.ids[:h]
            out_ids = jnp.where(hid >= 0, hid + base, -1)
            out_sc = cur.queue.scores[:h]
            if h < K:              # budget exceeds the shard's own content
                pad = K - h
                out_ids = jnp.concatenate(
                    [out_ids, jnp.full((pad,), -1, jnp.int32)])
                out_sc = jnp.concatenate(
                    [out_sc, jnp.full((pad,), qmod.NEG_INF, jnp.float32)])
            return out_ids, out_sc, cur

        out_ids, out_sc, new = jax.vmap(one)(
            qs, fresh, ids_b[idx], sc_b[idx], st_b[idx], vis_b[idx],
            steps_b[idx])
        # scatter the group's rows back; padded duplicate indices recompute
        # the same lane from the same state, so duplicate writes carry
        # identical values and the scatter stays deterministic
        ids_b = ids_b.at[idx].set(new.queue.ids)
        sc_b = sc_b.at[idx].set(new.queue.scores)
        st_b = st_b.at[idx].set(new.queue.stable)
        vis_b = vis_b.at[idx].set(new.visited)
        steps_b = steps_b.at[idx].set(new.steps)
        if p > 1:
            if merge == "tournament":
                out_ids, out_sc = _tournament_merge(out_ids, out_sc, axis, p)
            else:
                out_ids, out_sc = _allgather_merge(out_ids, out_sc, axis, K)
        return (out_ids, out_sc, ids_b[None], sc_b[None], st_b[None],
                vis_b[None], steps_b[None])

    sspec = P(axis)
    mapped = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(sspec if kd == "shard" else P() for kd in kinds)
        + (sspec, sspec, sspec,
           sspec, sspec, sspec, sspec, sspec,
           P(), P(), P(), P(), P()),
        out_specs=(P(), P(), sspec, sspec, sspec, sspec, sspec),
        check_vma=False,
    )
    fn = jax.jit(mapped)
    _RESUME_DISPATCH_FNS[key] = fn
    return fn


def resume_jit_cache_sizes() -> dict[str, int]:
    """Compile-cache audit for the resume dispatch ladder (test hook): the
    number of distinct dispatch rungs and the total jit traces behind them. A serving
    pass that recompiles shows up as either number growing."""
    traces = sum(int(f._cache_size()) for f in _RESUME_DISPATCH_FNS.values()
                 if hasattr(f, "_cache_size"))
    return dict(dispatch_fns=len(_RESUME_DISPATCH_FNS), traces=traces)


def sharded_topk_resume(index: ShardedIndex, state: ShardedSearchState,
                        qs: jnp.ndarray, lane_idx, fresh, K: int, L: int,
                        mesh: Mesh, axis: str = "data",
                        merge: str = "tournament"):
    """Resume (or seed) the shard-local beams of the lanes in ``lane_idx``.

    ``qs``/``fresh`` are the group's query rows and seed flags (``fresh``
    is traced, so seeding vs resuming shares one compilation). Expands each
    selected lane's beam until its first ``L`` entries are stable —
    continuing from the carried frontier, never redoing prior expansions —
    then harvests each shard's top-``K`` prefix and runs the same
    tournament merge as the scratch path. Returns
    ``(ids[g, K], scores[g, K], new_state)``; lanes outside ``lane_idx``
    keep their bits. A freshly seeded lane's round is bit-exact with
    ``sharded_topk`` at the same ``(K, L)``.
    """
    fn = _resume_dispatch_fn(index, mesh, axis, int(K), state.capacity,
                             merge)
    arrays, _, _ = _corpus_parts(index)
    out = fn(*arrays, index.neighbors, index.entries, index.bases,
             state.ids, state.scores, state.stable, state.visited,
             state.steps, jnp.asarray(qs, jnp.float32),
             jnp.asarray(lane_idx, jnp.int32),
             jnp.asarray(fresh, jnp.bool_),
             jnp.asarray(L, jnp.int32),
             jnp.asarray(4 * int(L) + 64, jnp.int32))
    ids, scores, *leaves = out
    return ids, scores, ShardedSearchState(*leaves)


def _diversify_one(vecs, cand_ids, cand_scores, eps_q, metric: str, k: int,
                   K: int, method: str, max_expansions: int):
    """One lane's diversify over an already-gathered candidate tile."""
    adj = kops.pairwise_adjacency(vecs, eps_q, metric, cand_ids >= 0)
    if method == "greedy":
        sel, count = kops.greedy_diversify(cand_scores, adj, k,
                                           valid=cand_ids >= 0)
        certified = count >= k
    else:
        res = da.div_astar(
            jnp.where(cand_ids >= 0, cand_scores, -jnp.inf), adj, k,
            max_expansions=max_expansions)
        sel = res.best_sets[k - 1]
        min_value = theorem2_min_value(res.best_scores, k)
        certified = (min_value > cand_scores[K - 1]) & res.complete
    out_ids = jnp.where(sel >= 0, cand_ids[jnp.maximum(sel, 0)], -1)
    out_sc = jnp.where(sel >= 0, cand_scores[jnp.maximum(sel, 0)], 0.0)
    return out_ids, out_sc, certified


def _diversify_batch(all_vectors, metric: str, ids, scores, epss, k: int,
                     K: int, method: str, max_expansions: int):
    """Replicated diversify over merged candidates — the single stage both
    the scratch and the resume paths run, so a freshly seeded resume round
    stays bit-exact with ``sharded_diverse_search`` end to end."""

    def diversify(cand_ids, cand_scores, eps_q):
        vecs = all_vectors[jnp.maximum(cand_ids, 0)]
        return _diversify_one(vecs, cand_ids, cand_scores, eps_q, metric, k,
                              K, method, max_expansions)

    return jax.vmap(diversify)(ids, scores, epss)


def _diversify_batch_gathered(cand_vecs, metric: str, ids, scores, epss,
                              k: int, K: int, method: str,
                              max_expansions: int):
    """Same stage over pre-gathered candidate vectors [B, K, d] — the
    quantized path's variant: candidate float rows were already gathered
    host-side by the exact rerank, so the device never needs the full
    float corpus."""

    def diversify(vecs, cand_ids, cand_scores, eps_q):
        return _diversify_one(vecs, cand_ids, cand_scores, eps_q, metric, k,
                              K, method, max_expansions)

    return jax.vmap(diversify)(cand_vecs, ids, scores, epss)


@functools.lru_cache(maxsize=None)
def _replicated_diversify(mesh: Mesh, gathered: bool, metric: str, k: int,
                          K: int, method: str, max_expansions: int):
    """The diversify stage as one program run on every device of ``mesh``
    over replicated inputs. The merged candidates come back replicated
    across the mesh, and Mosaic kernels cannot be partitioned by the
    compiler, so the stage runs inside a shard_map, not on mesh-sharded
    arrays."""
    stage = _diversify_batch_gathered if gathered else _diversify_batch

    def run(vecs, ids, scores, epss):
        return stage(vecs, metric, ids, scores, epss, k, K, method,
                     max_expansions)

    return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False))


def exact_rerank_frontier(all_vectors, qs, ids, metric: str):
    """Host-side exact float rerank of merged frontiers (quantized path).

    Same candidate *set*, re-scored with exact float similarity and
    re-sorted (descending score, ascending-id ties) via
    ``index.flat.exact_rerank``, so everything downstream — greedy/div-A*
    diversification, the ``cand_scores[K-1]`` certificate threshold, and
    any ``theorem2_recheck`` a caller runs on the returned frontier — sees
    only true float scores. Returns ``(ids, scores, vecs)`` with ``vecs``
    the gathered candidate float rows for the adjacency build.
    """
    from repro.index.flat import exact_rerank

    xs = np.asarray(all_vectors, np.float32)
    ids_r, sc_r = exact_rerank(np.asarray(qs, np.float32),
                               np.asarray(ids), xs, metric)
    vecs = xs[np.maximum(ids_r, 0)]
    return jnp.asarray(ids_r), jnp.asarray(sc_r), jnp.asarray(vecs)


def sharded_diverse_search(index: ShardedIndex, all_vectors: jnp.ndarray,
                           qs: jnp.ndarray, k: int, eps, K: int,
                           mesh: Mesh, axis: str = "data",
                           L_factor: int = 4, merge: str = "tournament",
                           method: str = "div_astar",
                           max_expansions: int = 100_000,
                           with_expansions: bool = False):
    """Distributed diverse search: sharded candidates + replicated diversify.

    Returns (ids[B, k], scores[B, k], certified[B]) — plus the per-lane
    shard-expansion totals as a fourth output with ``with_expansions``.
    ``all_vectors`` [N, d] is the global database used to gather candidate
    vectors for the adjacency build (replicated or resharded by the caller).
    ``eps`` may be a scalar or a per-query ``[B]`` vector (the scheduler's
    query-owned diversification level): lanes with different eps share one
    dispatch because eps is traced, never baked into the compilation.

    Quantized indexes (``index.scheme`` set) search and merge over
    compressed scores, then run the host-side exact float rerank on the
    merged frontier before diversification (``all_vectors`` is the
    host-retained float corpus) — contract 13.
    """
    ids, scores, expansions = sharded_topk(index, qs, K, K * L_factor, mesh,
                                           axis, merge, with_expansions=True)
    epss = jnp.broadcast_to(jnp.asarray(eps, jnp.float32), (qs.shape[0],))
    vecs = all_vectors
    if index.scheme is not None:
        ids, scores, vecs = exact_rerank_frontier(all_vectors, qs, ids,
                                                   index.metric)
    out = _replicated_diversify(mesh, index.scheme is not None, index.metric,
                                k, K, method, max_expansions)(
        vecs, ids, scores, epss)
    if with_expansions:
        return (*out, expansions)
    return out


def sharded_diverse_resume(index: ShardedIndex, all_vectors: jnp.ndarray,
                           state: ShardedSearchState, qs: jnp.ndarray,
                           lane_idx, fresh, k: int, eps, K: int,
                           mesh: Mesh, axis: str = "data",
                           L_factor: int = 4, merge: str = "tournament",
                           method: str = "div_astar",
                           max_expansions: int = 100_000):
    """One resumable budget round: continue the selected lanes' shard-local
    beams to the ``K * L_factor`` stable limit, merge, diversify.

    Returns (ids[g, k], scores[g, k], cand_ids[g, K], cand_scores[g, K],
    certified[g], new_state). The candidate frontier comes back so callers
    can re-verify the Theorem-2 certificate independently of the engine —
    on a quantized index it is the *reranked* frontier (exact float scores,
    re-sorted), so ``theorem2_recheck`` against the float corpus sees the
    very scores that produced the certificate. Lanes dispatched with
    ``fresh`` seeds are bit-exact with ``sharded_diverse_search`` at the
    same budget; resumed lanes instead satisfy the certificate-soundness +
    recall contract (their candidate frontier is at least as deep as a
    scratch one, but expansion order — hence near-tie content — may
    differ).
    """
    ids, scores, new_state = sharded_topk_resume(
        index, state, qs, lane_idx, fresh, K, K * L_factor, mesh, axis,
        merge)
    epss = jnp.broadcast_to(jnp.asarray(eps, jnp.float32), (qs.shape[0],))
    vecs = all_vectors
    if index.scheme is not None:
        ids, scores, vecs = exact_rerank_frontier(all_vectors, qs, ids,
                                                   index.metric)
    out_ids, out_sc, cert = _replicated_diversify(
        mesh, index.scheme is not None, index.metric, k, K, method,
        max_expansions)(vecs, ids, scores, epss)
    return out_ids, out_sc, ids, scores, cert, new_state


def sharded_progressive_diverse(index: ShardedIndex, all_vectors: jnp.ndarray,
                                qs: jnp.ndarray, k: int, eps,
                                mesh: Mesh, axis: str = "data",
                                K0: int = 32, L_factor: int = 4,
                                merge: str = "tournament",
                                max_expansions: int = 100_000,
                                max_rounds: int = 8,
                                resume: str = "beam"):
    """Progressive distributed diverse search (the paper's loop at mesh scale).

    The fixed-budget ``sharded_diverse_search`` can return uncertified lanes
    (Theorem-2 check fails: the optimal diverse set may extend past the K
    merged candidates). This entry point is a thin lockstep wrapper over
    ``sharded_search.engine.ShardedEngine`` — the mesh implementation of the
    ``core.backend.LaneBackend`` protocol: every lane carries its *own*
    candidate budget, a certified lane leaves the working set immediately,
    and each round re-dispatches only the uncertified lanes, bucketed by
    budget and padded to power-of-two sub-batch sizes so compile signatures
    stay logarithmic. (For continuous admission — new queries entering freed
    mesh lanes mid-run — drive the engine through
    ``serve.scheduler.LaneScheduler`` instead.)

    Returns (ids[B, k], scores[B, k], certified[B], K_final[B]) with
    ``K_final`` the per-lane budget at which each lane stopped — always a
    budget that was actually dispatched.

    Resumption contract (``resume``): with the default ``"beam"`` each
    budget-doubling round *continues* the shard-local beams from the
    previous round's frontier (``ShardedSearchState``), so a lane that
    finishes in its first round still equals ``sharded_diverse_search`` at
    its ``K_final`` bit-exactly, while a multi-round lane reuses its prior
    expansions and instead carries the certificate-soundness + recall
    contract (see ``ShardedEngine``). ``resume="scratch"`` restarts every
    round cold — the lockstep-parity mode in which *every* lane equals
    ``sharded_diverse_search`` at its ``K_final``.
    """
    from repro.core.backend import LaneRequest
    from repro.sharded_search.engine import ShardedEngine

    B = int(qs.shape[0])
    eng = ShardedEngine(index, all_vectors, mesh, num_lanes=B, axis=axis,
                        K0=K0, L_factor=L_factor, merge=merge,
                        max_expansions=max_expansions, max_rounds=max_rounds,
                        max_k=k, resume=resume)
    qs_np = np.asarray(qs, np.float32)
    epss = np.broadcast_to(np.asarray(eps, np.float64), (B,))
    for lane in range(B):
        eng.admit(lane, LaneRequest(q=qs_np[lane], k=k, eps=float(epss[lane]),
                                    method="sharded"))
    out_ids = np.full((B, k), -1, np.int32)
    out_sc = np.zeros((B, k), np.float32)
    out_cert = np.zeros(B, bool)
    K_final = np.zeros(B, np.int64)
    while eng.active_count():
        eng.step()
        for lane, res in eng.harvest():
            out_ids[lane], out_sc[lane] = res.ids, res.scores
            out_cert[lane] = res.stats.certified
            K_final[lane] = res.stats.K_final
            eng.recycle(lane)
    return out_ids, out_sc, out_cert, K_final
