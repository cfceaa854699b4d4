"""Beam search (paper Alg. 1) and progressive beam search (paper §III) in JAX.

Both are one ``lax.while_loop`` over a fixed-capacity queue:

  * ``beam_search``            — classic Alg. 1: stop when the first ``L``
                                  candidates are stable, return top-k.
  * ``progressive_beam_search`` — the paper's modification: stop when the
                                  first ``stable_limit`` (= K*ef) candidates
                                  are stable; the queue AND the visited set
                                  are threaded through calls so the search
                                  resumes instead of restarting (queue reuse).
  * the PSS variant (ProgressiveBeamSearch*, Alg. 4 line 6) is the same loop
    with ``min_value``: expansion stops once the best unexpanded candidate's
    score falls below ``min_value``.

TPU adaptation (DESIGN.md §2): neighbor scoring is one gathered (M0, d) block
scored in a single fused similarity op (the Pallas `batch_similarity` kernel
on TPU; its jnp oracle here), not one dot product at a time.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import quant
from repro.core import queue as qmod
from repro.core.graph import FlatGraph, entry_for
from repro.core.queue import Queue
from repro.kernels import ops as kops


class SearchState(NamedTuple):
    queue: Queue
    visited: jnp.ndarray   # bool[N] — nodes already EXPANDED
    steps: jnp.ndarray     # int32


@functools.partial(jax.jit, static_argnames=("capacity", "use_descent"))
def init_state(graph: FlatGraph, q: jnp.ndarray, capacity: int,
               use_descent: bool = True) -> SearchState:
    """Start state: queue seeded with the entry point (after HNSW descent,
    or the best of the graph's entry candidates)."""
    entry = (entry_for(graph, q) if use_descent or not graph.num_upper_levels
             else graph.entry)
    if quant.is_quantized(graph.vectors):
        qprep = quant.prepare_query(graph.vectors, q, graph.metric)
        s0 = quant.score_rows(qprep, graph.vectors,
                              entry.astype(jnp.int32)[None], graph.metric)[0]
    else:
        s0 = kops.batch_similarity(q, graph.vectors[entry][None, :],
                                   graph.metric)[0]
    queue = qmod.make_queue(capacity)
    queue = Queue(
        ids=queue.ids.at[0].set(entry.astype(jnp.int32)),
        scores=queue.scores.at[0].set(s0.astype(jnp.float32)),
        stable=queue.stable.at[0].set(False),
    )
    visited = jnp.zeros((graph.size,), dtype=jnp.bool_)
    return SearchState(queue, visited, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("graph_metric",))
def _search_loop(vectors, neighbors, qvec, state: SearchState,
                 stable_limit, min_value, max_steps, graph_metric: str):
    """Shared while-loop. ``stable_limit``/``min_value``/``max_steps`` traced.

    ``vectors`` is either the float corpus (scored by the batch-similarity
    kernel, byte-identical to the pre-quantization trace) or a quantized
    corpus (``quant.Int8Corpus``/``quant.PQCorpus``), in which case the
    per-search query view is prepared once here, outside the loop, and
    every expansion scores the gathered *compressed* neighbor block.
    The branch is resolved at trace time — the corpus type is part of the
    jit signature.
    """
    compressed = quant.is_quantized(vectors)
    qprep = (quant.prepare_query(vectors, qvec, graph_metric)
             if compressed else None)

    def cond(st: SearchState):
        p, exists = qmod.first_unstable(st.queue, stable_limit)
        score_ok = st.queue.scores[p] >= min_value
        return exists & score_ok & (st.steps < max_steps)

    def body(st: SearchState):
        queue, visited, steps = st
        p, _ = qmod.first_unstable(queue, stable_limit)
        node = queue.ids[p]
        queue = Queue(queue.ids, queue.scores, queue.stable.at[p].set(True))
        visited = visited.at[node].set(True)

        nbrs = neighbors[node]                       # int32[M0]
        safe = jnp.maximum(nbrs, 0)
        fresh = (nbrs >= 0) & ~visited[safe]
        if compressed:
            sims = quant.score_rows(qprep, vectors, safe, graph_metric)
        else:
            vecs = vectors[safe]                     # [M0, d]
            sims = kops.batch_similarity(qvec, vecs, graph_metric)
        queue = qmod.insert(queue, nbrs, sims, fresh)
        return SearchState(queue, visited, steps + 1)

    return jax.lax.while_loop(cond, body, state)


def run_search(graph: FlatGraph, q: jnp.ndarray, state: SearchState,
               stable_limit, min_value=-jnp.inf, max_steps=None) -> SearchState:
    if max_steps is None:
        max_steps = 4 * state.queue.capacity + 64
    return _search_loop(
        graph.vectors, graph.neighbors, q, state,
        jnp.asarray(stable_limit, jnp.int32),
        jnp.asarray(min_value, jnp.float32),
        jnp.asarray(max_steps, jnp.int32),
        graph.metric,
    )


def resume_search(graph: FlatGraph, q: jnp.ndarray, state: SearchState,
                  stable_limit, min_value=-jnp.inf,
                  step_budget=None) -> SearchState:
    """Resume a previous ``run_search`` under a *continued* stable limit.

    The queue and visited set carry over, so expansions from earlier calls
    are never redone: a wider ``stable_limit`` (the budget-doubling ladder's
    next rung) keeps expanding from the previous frontier instead of
    restarting at the entry point. ``step_budget`` is the per-call expansion
    allowance — unlike ``run_search``'s absolute ``max_steps``, it is added
    on top of the steps the state has already accumulated, so a resumed
    round gets the same allowance a fresh one would.

    Widening contract: a queue whose capacity is at least ``stable_limit``
    (or at least the graph's valid-node count) evolves its leading prefix
    identically to any wider queue — entries only ever drop *below* a full
    prefix of better-scored entries, and a dropped entry re-inserted later
    lands below that prefix again. The sharded resume path relies on this:
    it sizes the queue once at the lane's max beam width
    (``ShardedSearchState``), so the first round is bit-exact with a scratch
    search at the narrow width, and later rounds continue exactly where the
    previous rung stopped.
    """
    if step_budget is None:
        step_budget = 4 * state.queue.capacity + 64
    max_steps = state.steps + jnp.asarray(step_budget, jnp.int32)
    return run_search(graph, q, state, stable_limit, min_value,
                      max_steps=max_steps)


def beam_search(graph: FlatGraph, q: jnp.ndarray, k: int, L: int,
                capacity: int | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Paper Alg. 1: plain beam search; returns (ids[k], scores[k])."""
    if capacity is None:
        capacity = L
    state = init_state(graph, q, capacity)
    state = run_search(graph, q, state, stable_limit=L)
    return state.queue.ids[:k], state.queue.scores[:k]


def progressive_beam_search(graph: FlatGraph, q: jnp.ndarray,
                            state: SearchState, K, ef: int,
                            min_value=-jnp.inf) -> SearchState:
    """The paper's ProgressiveBeamSearch: resume until first K*ef stable."""
    return run_search(graph, q, state, stable_limit=K * ef, min_value=min_value)


@functools.partial(jax.jit, static_argnames=("new_capacity",))
def rebuild_for_growth(graph: FlatGraph, q: jnp.ndarray, state: SearchState,
                       new_capacity: int) -> SearchState:
    """Exact queue rebuild when the driver grows capacity.

    Fixed capacity can silently drop (a) unexpanded frontier nodes and
    (b) expanded nodes that fell below the old capacity boundary. Expanded
    nodes are exactly the ``visited`` set, so rebuilding from
    (current queue entries) ∪ (visited nodes, rescored) reproduces the
    unbounded-queue state of the paper exactly. O(|visited|) and only runs on
    the rare growth events.
    """
    visited = state.visited
    n = graph.size
    all_ids = jnp.arange(n, dtype=jnp.int32)
    if quant.is_quantized(graph.vectors):
        qprep = quant.prepare_query(graph.vectors, q, graph.metric)
        vis_scores = quant.score_rows(qprep, graph.vectors, all_ids,
                                      graph.metric)
    else:
        vis_scores = kops.batch_similarity(q, graph.vectors, graph.metric)
    # queue membership of every node (to keep 'unstable' flags of frontier);
    # add-scatter because several empty sentinels all map to slot 0, and a
    # .set scatter with duplicate indices has undefined winner order
    safe = jnp.maximum(state.queue.ids, 0)
    in_queue = jnp.zeros((n,), jnp.int32).at[safe].add(
        (state.queue.ids >= 0).astype(jnp.int32)) > 0
    frontier_unstable = jnp.zeros((n,), jnp.int32).at[safe].add(
        ((state.queue.ids >= 0) & ~state.queue.stable).astype(jnp.int32)) > 0
    member = visited | in_queue
    ids = jnp.where(member, all_ids, -1)
    scores = jnp.where(member, vis_scores, qmod.NEG_INF)
    stable = ~frontier_unstable
    new_queue = qmod.from_entries(ids, scores, stable, new_capacity)
    return SearchState(new_queue, visited, state.steps)
