"""ShardedEngine: the mesh-sharded implementation of ``LaneBackend``.

A *lane* here is one query row of the replicated batch that rides over the
device mesh: the database is sharded P ways along the mesh's data axis, every
dispatch runs the shard-local beam search + tournament merge + replicated
div-A* of ``sharded_search.sharded_diverse_search``, and each lane carries
its own ``(k, eps, K-budget)`` — the paper's query-owned diversification
level at mesh scale.

Round structure (one ``step()``):

1. Occupied lanes are bucketed by their current ``(K-budget, k)`` and each
   bucket is dispatched at exactly that budget, padded to a power-of-two
   lane count (``core.bucketing``) so compile signatures stay logarithmic in
   batch size. ``eps`` is traced per lane, so mixed-eps traffic shares one
   compilation per bucket shape.
2. A lane whose Theorem-2 certificate fires (or whose budget hit the corpus
   / its ``max_K`` cap) finishes and its mesh slot is freed — the serving
   scheduler admits the next queued request into it *between rounds*, while
   sibling lanes keep their budgets. This is the request-queue half that
   ``sharded_progressive_diverse`` alone never had (per-lane budgets only).
3. Surviving lanes double their budget (clamped) for the next round; a lane
   that exhausts ``max_rounds`` finishes uncertified with its last results.

Resumption contract (``resume=``):

* ``"beam"`` (default) — truly progressive: a fixed-shape
  ``ShardedSearchState`` pytree (per-lane, per-shard beam queue + visited
  set, capacity sized once to the lane's max beam width) is carried across
  rounds, so a doubled budget *continues* each shard-local beam from the
  previous round's frontier instead of restarting ``_local_topk`` cold.
  A lane that finishes in its **first** round is bit-exact with
  ``sharded_diverse_search`` at its final K-budget (a fresh seed's round is
  the scratch computation). A **multi-round** lane reuses its expansions —
  its candidate frontier may differ from a cold run near score ties — and
  instead carries the soundness contract: a certified lane's result passes
  an independent Theorem-2 re-check against its final candidate frontier
  (``last_candidates``), and recall vs the exact diverse oracle is no worse
  than the scratch path (tested on the 10k graph), at strictly fewer
  cumulative shard expansions.
* ``"scratch"`` — the lockstep-parity escape hatch: every round re-runs the
  beams from scratch at ``K * L_factor``; every harvested lane (single- or
  multi-round) equals ``sharded_diverse_search`` at its final K-budget,
  bit-exact on the CPU host mesh.
  ``tests/dist_scripts/sharded_scheduler_check.py`` enforces this on a
  4-device host mesh, plus mid-run admission into a freed lane.

Either way ``result()`` reports *real* per-lane counters: ``expansions`` is
the lane's cumulative shard-local expansion count (summed over shards; under
``"beam"`` expansions are counted once, under ``"scratch"`` every round's
restart re-counts its redone work — the measured difference is exactly what
resumption saves), ``growths`` the budget doublings actually applied, and
``exhausted`` marks a lane whose ladder hit its ``max_K``/corpus cap without
certifying (a round-limited retirement is truncated, not exhausted).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.backend import LaneRequest
from repro.core.batch_progressive import SignatureLog
from repro.core.bucketing import pow2_group_sizes, pow2_padded_indices
from repro.core.pgs import DiverseResult
from repro.core.progressive import SearchStats
from repro.sharded_search.search import (ShardedIndex, beam_state_capacity,
                                         init_sharded_state,
                                         migrate_sharded_state,
                                         reshard_index,
                                         sharded_diverse_resume,
                                         sharded_diverse_search)

LANE_FREE, LANE_RUN, LANE_DONE = range(3)


class ShardedEngine:
    """Per-lane progressive budgets over a sharded mesh index.

    Implements ``core.backend.LaneBackend``; drive it directly (the
    ``sharded_progressive_diverse`` wrapper does) or through
    ``serve.scheduler.LaneScheduler`` for continuous batching, backpressure
    and latency stats on an N-device mesh. ``resume="beam"`` carries each
    lane's shard-local beam state across budget rounds (see the module
    docstring for the contract); ``resume="scratch"`` is the lockstep
    bit-parity mode. ``record_candidates`` keeps each lane's last merged
    candidate frontier host-side (``last_candidates``) so certificates can
    be re-verified independently.
    """

    methods = ("sharded",)
    rescalable = True

    def __init__(self, index: ShardedIndex, all_vectors, mesh,
                 num_lanes: int = 8, *, axis: str = "data",
                 K0: int = 32, L_factor: int = 4, merge: str = "tournament",
                 max_expansions: int = 100_000, max_rounds: int = 8,
                 max_k: int = 16, default_ef: int = 0,
                 max_signatures: int | None = 1024,
                 resume: str = "beam", state_capacity: int | None = None,
                 record_candidates: bool = False):
        if resume not in ("beam", "scratch"):
            raise ValueError(f"unknown resume mode {resume!r}")
        self.index = index
        #: True when the index stores compressed codes — search rounds then
        #: score quantized, and the float corpus stays HOST-side, touched
        #: only by the exact rerank of each merged frontier (contract 13)
        self.compressed = index.scheme is not None
        self.all_vectors = (np.asarray(all_vectors, np.float32)
                            if self.compressed else jnp.asarray(all_vectors))
        self.mesh = mesh
        self.axis = axis
        self.K0 = K0
        self.L_factor = L_factor
        self.merge = merge
        self.max_expansions = max_expansions
        self.max_rounds = max_rounds
        self.max_k = max_k
        # the mesh backend has no beam-ef knob (beam width = K * L_factor);
        # kept so the scheduler's ef plumbing is backend-neutral
        self.default_ef = default_ef
        self.resume = resume
        self.record_candidates = record_candidates
        self._state_capacity = state_capacity
        self._max_signatures = max_signatures
        self.B = int(num_lanes)
        self.n_total = index.num_shards * index.shard_size
        d = int(index.dim)
        self.qs = np.zeros((self.B, d), np.float32)
        self.status = np.full(self.B, LANE_FREE, np.int8)
        self.ks = np.ones(self.B, np.int64)
        self.epss = np.zeros(self.B, np.float64)
        self.K = np.zeros(self.B, np.int64)
        self.maxK = np.full(self.B, self.n_total, np.int64)
        self.rounds = np.zeros(self.B, np.int64)
        self.out_ids = np.full((self.B, max_k), -1, np.int32)
        self.out_sc = np.zeros((self.B, max_k), np.float32)
        self.cert = np.zeros(self.B, bool)
        self.expansions = np.zeros(self.B, np.int64)
        self.fresh = np.ones(self.B, bool)
        #: per-lane (cand_ids, cand_scores) of the last dispatched round,
        #: populated when ``record_candidates`` — the frontier a Theorem-2
        #: re-check verifies the certificate against
        self.last_candidates: list = [None] * self.B
        if resume == "beam":
            floor = beam_state_capacity(index, self.n_total, L_factor)
            cap = state_capacity or floor
            if cap < floor:
                # a narrower queue silently drops beam candidates: harvest
                # pads with -inf rows, which trivially satisfies the
                # certificate's min_value > s_K and voids both the parity
                # and the soundness contract — refuse at construction
                raise ValueError(
                    f"state_capacity={cap} is below the resumable-beam "
                    f"floor {floor} (beam_state_capacity); the widening "
                    "contract needs the queue to hold every rung's beam "
                    "or the whole shard")
            self.beam_state = init_sharded_state(index, self.B, cap, mesh,
                                                 axis)
        else:
            self.beam_state = None
        self.signatures = SignatureLog(max_signatures)
        self._unharvested: list[int] = []
        #: prepared elastic targets: shard count -> (mesh, index), built and
        #: prewarmed ahead of the scale event by ``prepare_rescale``
        self._rescale_targets: dict[int, tuple] = {}

    # -- protocol surface ---------------------------------------------------
    @property
    def num_lanes(self) -> int:
        return self.B

    @property
    def num_shards(self) -> int:
        return self.index.num_shards

    @property
    def bytes_per_vector(self) -> float:
        """Stored corpus bytes per vector on a device (f32: ``4 * d``;
        quantized: codes + amortized scale/codebook sidecars)."""
        return float(self.index.corpus_bytes_per_vector())

    @property
    def signature_log(self) -> SignatureLog:
        return self.signatures

    def free_lanes(self) -> np.ndarray:
        return np.flatnonzero(self.status == LANE_FREE)

    def active_count(self) -> int:
        return int((self.status == LANE_RUN).sum())

    def admit(self, lane: int, request: LaneRequest) -> None:
        """Hand a free mesh lane to ``request``: fresh budget ladder from
        ``K0``; sibling lanes keep their in-flight budgets (and, under
        ``resume="beam"``, their in-flight beam frontiers)."""
        if self.status[lane] != LANE_FREE:
            raise RuntimeError(f"mesh lane {lane} is still occupied")
        k = int(request.k)
        if k > self.max_k:
            raise ValueError(f"k={k} exceeds engine max_k={self.max_k}")
        if request.method not in self.methods:
            raise ValueError(
                f"unknown sharded method {request.method!r}")
        self.qs[lane] = np.asarray(request.q, np.float32)
        self.ks[lane] = k
        self.epss[lane] = float(request.eps)
        self.maxK[lane] = min(request.max_K or self.n_total, self.n_total)
        self.K[lane] = min(max(self.K0, 2 * k), self.maxK[lane])
        self.rounds[lane] = 0
        self.out_ids[lane] = -1
        self.out_sc[lane] = 0.0
        self.cert[lane] = False
        self.expansions[lane] = 0
        self.fresh[lane] = True   # first dispatch re-seeds the beam state
        self.last_candidates[lane] = None
        self.status[lane] = LANE_RUN

    def recycle(self, lane: int) -> None:
        """Return a harvested lane's mesh slot to the free pool; the lane's
        carried beam state is cleared (re-seeded on the next admit)."""
        if self.status[lane] != LANE_DONE:
            raise RuntimeError(f"mesh lane {lane} is not finished")
        self.fresh[lane] = True
        self.status[lane] = LANE_FREE

    # -- the round ----------------------------------------------------------
    def _dispatch(self, idx: np.ndarray, Kval: int, k_g: int) -> None:
        padded = pow2_padded_indices(idx)
        self.signatures.note("sharded", len(padded), Kval, k_g)
        m = len(idx)
        if self.resume == "beam":
            ids, scores, cand_ids, cand_sc, cert, self.beam_state = \
                sharded_diverse_resume(
                    self.index, self.all_vectors, self.beam_state,
                    jnp.asarray(self.qs[padded]), padded,
                    self.fresh[padded], k_g,
                    jnp.asarray(self.epss[padded], jnp.float32), Kval,
                    self.mesh, self.axis, self.L_factor, self.merge,
                    "div_astar", self.max_expansions)
            self.fresh[idx] = False
            # cumulative per-lane expansions since the lane's seed: the
            # carried state's step counters summed over shards
            steps = np.asarray(self.beam_state.steps).sum(axis=0)
            self.expansions[idx] = steps[idx]
        else:
            ids, scores, cert, exp = sharded_diverse_search(
                self.index, self.all_vectors, jnp.asarray(self.qs[padded]),
                k_g, jnp.asarray(self.epss[padded], jnp.float32), Kval,
                self.mesh, self.axis, self.L_factor, self.merge,
                "div_astar", self.max_expansions, with_expansions=True)
            cand_ids = cand_sc = None
            # every scratch round redoes (and re-counts) its prior work
            self.expansions[idx] += np.asarray(exp)[:m]
        self.out_ids[idx, :k_g] = np.asarray(ids)[:m]
        self.out_sc[idx, :k_g] = np.asarray(scores)[:m]
        self.cert[idx] = np.asarray(cert)[:m]
        if self.record_candidates and cand_ids is not None:
            cids, csc = np.asarray(cand_ids), np.asarray(cand_sc)
            for row, lane in enumerate(idx):
                self.last_candidates[int(lane)] = (cids[row].copy(),
                                                   csc[row].copy())

    def step(self) -> list[int]:
        """Advance every occupied mesh lane one budget round; returns the
        lanes that finished (also queued for ``harvest``)."""
        active = self.status == LANE_RUN
        if not active.any():
            return []
        buckets: dict[tuple, list[int]] = {}
        for i in np.flatnonzero(active):
            buckets.setdefault((int(self.K[i]), int(self.ks[i])), []).append(i)
        for (Kval, k_g), idx in sorted(buckets.items()):
            self._dispatch(np.asarray(idx), Kval, k_g)
        self.rounds[active] += 1
        finished = active & (self.cert | (self.K >= self.maxK))
        still = active & ~finished
        # a lane out of rounds retires uncertified at its *current* budget
        # (so K_final is always a budget that was actually dispatched — the
        # parity anchor); only true survivors double for the next round
        retired = still & (self.rounds >= self.max_rounds)
        cont = still & ~retired
        self.K[cont] = np.minimum(self.K[cont] * 2, self.maxK[cont])
        done = np.flatnonzero(finished | retired)
        for lane in done:
            self.status[lane] = LANE_DONE
            self._unharvested.append(int(lane))
        return [int(x) for x in done]

    def harvest(self) -> list[tuple[int, DiverseResult]]:
        """Drain finished lanes since the last harvest; each lane stays
        reserved until ``recycle``."""
        out = [(lane, self.result(lane)) for lane in self._unharvested]
        self._unharvested = []
        return out

    def result(self, lane: int) -> DiverseResult:
        """Solo-call-compatible result with the lane's real counters.

        Under ``resume="scratch"`` (or a single-round lane under
        ``resume="beam"``) the (ids, scores, certified) equal
        ``sharded_diverse_search`` for this query at ``stats.K_final``.
        """
        k = int(self.ks[lane])
        ids = self.out_ids[lane, :k].copy()
        sc = self.out_sc[lane, :k].copy()
        certified = bool(self.cert[lane])
        stats = SearchStats(
            expansions=int(self.expansions[lane]),
            growths=max(0, int(self.rounds[lane]) - 1),
            search_calls=int(self.rounds[lane]),
            div_calls=int(self.rounds[lane]),
            certified=certified,
            exhausted=bool(not certified
                           and int(self.K[lane]) >= int(self.maxK[lane])),
            K_final=int(self.K[lane]))
        return DiverseResult(ids.astype(np.int32), sc.astype(np.float32),
                             float(sc.sum()), stats)

    # -- epoch swap ----------------------------------------------------------
    def swap_index(self, index: ShardedIndex, all_vectors) -> None:
        """Install a new epoch's sharded index (the mutable index's rebuild
        swap). Only legal with no lane ``LANE_RUN``: the carried
        ``ShardedSearchState`` is laid out per shard of the *old* corpus,
        so it is re-initialized over the new one — the serving layer drains
        in-flight lanes first (contract 15; finished-but-unrecycled lanes
        keep their host-side results). The shard count and mesh are fixed
        across swaps (the rebuild pads the corpus to divisibility instead);
        the signature log carries across so recompile audits span epochs
        (a grown shard legitimately traces new shapes)."""
        if self.active_count():
            raise RuntimeError("cannot swap the index under occupied lanes "
                               "— drain in-flight lanes first (contract 15)")
        if index.num_shards != self.index.num_shards:
            raise ValueError(
                f"epoch swap cannot change the shard count "
                f"({self.index.num_shards} -> {index.num_shards}); pad the "
                "corpus to divisibility instead")
        self.index = index
        self.compressed = index.scheme is not None
        self.all_vectors = (np.asarray(all_vectors, np.float32)
                            if self.compressed else jnp.asarray(all_vectors))
        self.n_total = index.num_shards * index.shard_size
        self.maxK = np.minimum(self.maxK, self.n_total)
        if self.resume == "beam":
            floor = beam_state_capacity(self.index, self.n_total,
                                        self.L_factor)
            cap = self._state_capacity or floor
            if cap < floor:
                raise ValueError(
                    f"state_capacity={cap} is below the new epoch's "
                    f"resumable-beam floor {floor}")
            self.beam_state = init_sharded_state(self.index, self.B, cap,
                                                 self.mesh, self.axis)
            self.fresh[:] = True
        # prepared elastic targets hold the *old* epoch's rows — serving a
        # rescale onto one would resurrect the pre-swap corpus; the elastic
        # controller re-prepares its targets over the new epoch
        self._rescale_targets.clear()
        self.signatures.note("swap", self.B, self.n_total)

    # -- elastic rescale -----------------------------------------------------
    def _target_capacity(self, index: ShardedIndex) -> int:
        floor = beam_state_capacity(index, self.n_total, self.L_factor)
        cap = self._state_capacity or floor
        if cap < floor:
            # shrinking the mesh grows the shard size, which can RAISE the
            # resumable-beam floor past a pinned state_capacity — refuse at
            # prepare time, not mid-migration
            raise ValueError(
                f"state_capacity={cap} is below the {index.num_shards}-shard "
                f"resumable-beam floor {floor} (beam_state_capacity)")
        return cap

    def prepare_rescale(self, shards: int, mesh, index: ShardedIndex | None
                        = None, *, M: int | None = None,
                        builder: str = "knng", prewarm: bool = True,
                        max_capacity: int | None = None,
                        ks: tuple = (),
                        num_lanes: int | None = None) -> ShardedIndex:
        """Build (or adopt) and prewarm an elastic target mesh.

        Resharding and compilation are the expensive halves of a scale
        event, so both happen here, ahead of load: the corpus is
        repartitioned onto ``shards`` (``reshard_index`` — quantized codes
        re-blocked exactly, graphs rebuilt), and the target mesh's dispatch
        ladder is compiled by executing dummy rounds against a throwaway
        beam state at the *post-rescale* queue capacity, so post-scale
        traffic re-enters cached jit callables (``resume_jit_cache_sizes``
        stays flat — the zero-recompile discipline extends to the new
        mesh). Signatures are mesh-independent ``("sharded", …)`` tuples
        plus one planned ``("rescale", shards)`` marker, so preparing both
        targets before ``signature_log.freeze()`` keeps scale events off
        the unplanned list. The actual ``rescale`` is then only the
        in-flight state migration — milliseconds, not a rebuild.

        ``num_lanes`` gives the target its own lane count (default: keep
        the current one) — serving capacity follows the mesh, so a grow
        typically scales lanes with devices and the prewarmed ladder here
        covers the wider lane groups. A lane shrink is applied only when
        the tail lanes are free at rescale time (it never drops an
        occupied lane; the scheduler's elastic trigger only shrinks an
        idle engine).
        """
        if shards & (shards - 1) or shards < 1:
            raise ValueError(f"shards={shards} must be a power of two")
        B_t = int(num_lanes or self.B)
        if B_t < 1:
            raise ValueError(f"num_lanes={B_t} must be >= 1")
        if index is None:
            index = reshard_index(
                self.index, shards,
                self.all_vectors if self.compressed else None,
                M=M, builder=builder)
        if index.num_shards != shards:
            raise ValueError(f"prepared index has {index.num_shards} "
                             f"shards, expected {shards}")
        if index.num_shards * index.shard_size != self.n_total:
            raise ValueError("elastic targets must cover the same corpus "
                             "(resharding is a capacity knob)")
        self.signatures.note("rescale", shards)
        # preparing a target implies the return path: scaling back to the
        # current topology is planned too
        self.signatures.note("rescale", self.index.num_shards)
        if prewarm and shards != self.index.num_shards:
            cap = (self._target_capacity(index)
                   if self.resume == "beam" else 0)
            state = (init_sharded_state(index, B_t, cap, mesh, self.axis)
                     if self.resume == "beam" else None)
            d = int(index.dim)
            top = min(max_capacity or self.K0, self.n_total)
            for g in pow2_group_sizes(B_t):
                qs = jnp.zeros((g, d), jnp.float32)
                epss = jnp.zeros((g,), jnp.float32)
                for k in tuple(int(kk) for kk in ks) or (self.max_k,):
                    K = min(max(self.K0, 2 * k), self.n_total)
                    while True:
                        self.signatures.note("sharded", g, K, k)
                        if self.resume == "beam":
                            sharded_diverse_resume(
                                index, self.all_vectors, state, qs,
                                np.zeros(g, np.int64), np.ones(g, bool),
                                k, epss, K, mesh, self.axis, self.L_factor,
                                self.merge, "div_astar", self.max_expansions)
                        else:
                            sharded_diverse_search(
                                index, self.all_vectors, qs, k, epss, K,
                                mesh, self.axis, self.L_factor, self.merge,
                                "div_astar", self.max_expansions,
                                with_expansions=True)
                        if K >= top:
                            break
                        K = min(K * 2, self.n_total)
        self._rescale_targets[shards] = (mesh, index, B_t)
        return index

    def rescale_options(self) -> tuple[int, ...]:
        """Shard counts this engine can serve at right now: the current
        mesh plus every prepared elastic target."""
        return tuple(sorted(set(self._rescale_targets)
                            | {self.index.num_shards}))

    def rescale(self, shards: int) -> bool:
        """Quiesce-free scale event: move the corpus AND every in-flight
        lane to the prepared ``shards``-shard mesh, between rounds.

        Unlike ``swap_index`` (same corpus *content* change, which drains
        lanes first), a rescale migrates the carried ``ShardedSearchState``
        — each lane's per-shard queues re-bucket by global id, visited
        bits follow their rows, step counters keep their per-lane totals —
        so occupied lanes resume their budget ladder on the new topology
        without redoing expansions (contract 16). When the target was
        prepared with its own lane count, the lane axis scales too:
        serving capacity follows the mesh. Extra lanes are appended
        ``LANE_FREE``; a lane shrink is applied only if the tail lanes are
        free right now — an occupied lane is never dropped, the engine
        just keeps its current width until the tail drains. The outgoing
        configuration is remembered as a target, so scaling back is always
        one prepared ``rescale`` away. Returns False for a no-op (already
        at ``shards``); raises if the target was never prepared.
        """
        if shards == self.index.num_shards:
            return False
        target = self._rescale_targets.get(shards)
        if target is None:
            raise RuntimeError(
                f"no prepared target for {shards} shards — call "
                "prepare_rescale first (resharding + compilation are the "
                "expensive halves; the scale event itself must not pay "
                "them)")
        mesh, index, B_t = target
        # remember the outgoing config so the controller can scale back
        self._rescale_targets[self.index.num_shards] = (self.mesh,
                                                        self.index, self.B)
        B_new = B_t
        if B_new < self.B and (self.status[B_new:] != LANE_FREE).any():
            B_new = self.B   # occupied tail: keep width, shrink shards only
        if self.resume == "beam":
            self.beam_state = migrate_sharded_state(
                self.beam_state, shards, self._target_capacity(index),
                mesh=mesh, axis=self.axis, num_lanes=B_new)
        if B_new != self.B:
            self._resize_lanes(B_new)
        self.index = index
        self.mesh = mesh
        self.signatures.note("rescale", shards)
        return True

    def _resize_lanes(self, B_new: int) -> None:
        """Pad (grow) or slice (shrink) every per-lane host array to
        ``B_new`` lanes, preserving the surviving prefix verbatim. The
        caller guarantees dropped tail lanes are ``LANE_FREE``."""
        B = self.B

        def grow(a, fill):
            out = np.full((B_new,) + a.shape[1:], fill, a.dtype)
            out[:B] = a
            return out

        if B_new > B:
            self.qs = grow(self.qs, 0)
            self.status = grow(self.status, LANE_FREE)
            self.ks = grow(self.ks, 1)
            self.epss = grow(self.epss, 0)
            self.K = grow(self.K, 0)
            self.maxK = grow(self.maxK, self.n_total)
            self.rounds = grow(self.rounds, 0)
            self.out_ids = grow(self.out_ids, -1)
            self.out_sc = grow(self.out_sc, 0)
            self.cert = grow(self.cert, False)
            self.expansions = grow(self.expansions, 0)
            self.fresh = grow(self.fresh, True)
            self.last_candidates += [None] * (B_new - B)
        else:
            for name in ("qs", "status", "ks", "epss", "K", "maxK",
                         "rounds", "out_ids", "out_sc", "cert",
                         "expansions", "fresh"):
                setattr(self, name, getattr(self, name)[:B_new])
            self.last_candidates = self.last_candidates[:B_new]
        self.B = B_new

    # -- prewarm ------------------------------------------------------------
    def prewarm(self, *, max_capacity: int | None = None, ks: tuple = (),
                widths: tuple = ()) -> list[tuple]:
        """Compile the mesh dispatch ladder ahead of serving.

        Walks the power-of-two group sizes up to ``num_lanes`` crossed with
        the budget-doubling ladder from ``K0`` up to ``max_capacity``
        (default: one rung, ``K0`` only — mesh dispatches *execute* the
        search, so a full-corpus warmup is a real cost the caller opts into)
        for each ``k`` in ``ks`` (default: ``max_k``). Under
        ``resume="beam"`` the fresh/resumed distinction is traced, so the
        ladder covers both; signatures stay one per (group, K, k) rung.
        ``widths`` is accepted for signature-compatibility with the
        single-host backend and ignored (the mesh backend has no
        prefix-width stage).
        """
        del widths
        if (self.status != LANE_FREE).any():
            raise RuntimeError("prewarm before admitting requests (prewarm "
                               "dispatches scribble on lane 0's result row)")
        top = min(max_capacity or self.K0, self.n_total)
        ks = tuple(int(k) for k in ks) or (self.max_k,)
        warmed: list[tuple] = []
        for g in pow2_group_sizes(self.B):
            for k in ks:
                K = min(max(self.K0, 2 * k), self.n_total)
                self.fresh[0] = True   # each ladder seeds lane 0 afresh
                while True:
                    self._dispatch(np.zeros(g, np.int64), K, k)
                    warmed.append(("sharded", g, K, k))
                    if K >= top:
                        break
                    K = min(K * 2, self.n_total)
        # prewarm dispatches scribble on (free) lane 0's rows; wipe them
        self.out_ids[0] = -1
        self.out_sc[0] = 0.0
        self.cert[0] = False
        self.expansions[0] = 0
        self.fresh[0] = True
        self.last_candidates[0] = None
        return warmed
