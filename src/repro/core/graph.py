"""Flat, fixed-shape proximity-graph representation consumed by JAX search.

The numpy HNSW builder (``repro.index.hnsw``) emits:

  vectors    f32[N, d]          the database
  neighbors  int32[N, M0]       level-0 adjacency, -1 padded
  upper      int32[Lu, N, Mu]   upper-level adjacency (rows of non-member
                                nodes are all -1); may have Lu == 0
  entry      int32 | int32[P]   entry node at the top level, or P entry
                                candidates (level-0 graphs): each search
                                starts at the candidate most similar to
                                its query (``entry_for``)

``metric`` travels as static aux data so jitted searchers specialize on it.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant
from repro.core.similarity import query_sim
from repro.kernels import ops as kops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FlatGraph:
    vectors: jnp.ndarray
    neighbors: jnp.ndarray
    upper: jnp.ndarray
    entry: jnp.ndarray
    metric: str = dataclasses.field(metadata=dict(static=True), default="l2")

    @property
    def size(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def degree(self) -> int:
        return self.neighbors.shape[1]

    @property
    def num_upper_levels(self) -> int:
        return self.upper.shape[0]


def make_flat_graph(vectors: Any, neighbors: Any, upper: Any | None,
                    entry, metric: str) -> FlatGraph:
    """``vectors`` may be a float array OR a quantized corpus
    (``quant.Int8Corpus`` / ``quant.PQCorpus``): the beam search scores
    whichever representation the graph carries. Quantized graphs are
    level-0 only (the greedy upper-level descent reads float rows).
    ``entry`` is one node id, or a vector of entry candidates on a graph
    without upper levels."""
    if quant.is_quantized(vectors):
        if upper is not None and getattr(upper, "shape", (0,))[0] != 0:
            raise ValueError(
                "quantized corpora do not support upper HNSW levels; "
                "build a level-0 (knng) graph instead")
    else:
        vectors = jnp.asarray(vectors, dtype=jnp.float32)
    neighbors = jnp.asarray(neighbors, dtype=jnp.int32)
    if upper is None or (hasattr(upper, "shape") and upper.shape[0] == 0):
        upper = jnp.zeros((0, vectors.shape[0], 1), dtype=jnp.int32)
    else:
        upper = jnp.asarray(upper, dtype=jnp.int32)
        if np.ndim(entry):
            raise ValueError("entry candidates need a level-0 graph; the "
                             "upper-level descent starts at one node")
    return FlatGraph(vectors, neighbors, upper,
                     jnp.asarray(entry, dtype=jnp.int32), metric)


def embed_live_graph(g: FlatGraph, live: np.ndarray, vectors
                     ) -> FlatGraph:
    """Re-index a graph built over the rows ``live`` of ``vectors`` to
    positional ids: every other row keeps its vector and has no edges, in
    or out, at any level."""
    live = np.asarray(live)

    def glob(local):
        local = np.asarray(local)
        return np.where(local >= 0, live[np.maximum(local, 0)], -1)

    n = vectors.shape[0]
    nbrs = np.full((n, g.degree), -1, np.int32)
    nbrs[live] = glob(g.neighbors)
    upper = np.full((g.num_upper_levels, n, g.upper.shape[2]), -1, np.int32)
    upper[:, live] = glob(g.upper)
    return make_flat_graph(vectors, nbrs, upper, live[np.asarray(g.entry)],
                           g.metric)


def entry_for(graph: FlatGraph, q: jnp.ndarray) -> jnp.ndarray:
    """The level-0 start node for query ``q``: the upper-level descent's
    result, the graph's one entry node, or the entry candidate most
    similar to ``q`` (first index on ties)."""
    if graph.num_upper_levels:
        return descend(graph, q)
    if graph.entry.ndim == 0:
        return graph.entry
    cands = graph.entry
    if quant.is_quantized(graph.vectors):
        qprep = quant.prepare_query(graph.vectors, q, graph.metric)
        sims = quant.score_rows(qprep, graph.vectors, cands, graph.metric)
    else:
        sims = kops.batch_similarity(q, graph.vectors[cands], graph.metric)
    return cands[jnp.argmax(sims)]


def descend(graph: FlatGraph, q: jnp.ndarray) -> jnp.ndarray:
    """Greedy top-down descent through the upper HNSW levels.

    Returns the level-0 entry node for query ``q``. Each level runs a greedy
    walk: move to the best-scoring neighbor while it improves.
    """
    cur = graph.entry
    cur_sim = query_sim(q, graph.vectors[cur][None, :], graph.metric)[0]

    def level_walk(level_nbrs, cur, cur_sim):
        def cond(state):
            _, _, improved, steps = state
            return improved & (steps < graph.size)

        def body(state):
            cur, cur_sim, _, steps = state
            nbrs = level_nbrs[cur]
            valid = nbrs >= 0
            vecs = graph.vectors[jnp.maximum(nbrs, 0)]
            sims = query_sim(q, vecs, graph.metric)
            sims = jnp.where(valid, sims, -jnp.inf)
            j = jnp.argmax(sims)
            better = sims[j] > cur_sim
            new_cur = jnp.where(better, nbrs[j], cur)
            new_sim = jnp.where(better, sims[j], cur_sim)
            return new_cur, new_sim, better, steps + 1

        cur, cur_sim, _, _ = jax.lax.while_loop(
            cond, body, (cur, cur_sim, jnp.bool_(True), jnp.int32(0)))
        return cur, cur_sim

    for lvl in range(graph.num_upper_levels):
        # upper[0] is the TOP level; walk down.
        cur, cur_sim = level_walk(graph.upper[lvl], cur, cur_sim)
    return cur


def to_host(graph: FlatGraph) -> dict:
    return dict(
        vectors=np.asarray(graph.vectors),
        neighbors=np.asarray(graph.neighbors),
        upper=np.asarray(graph.upper),
        entry=np.asarray(graph.entry),
        metric=graph.metric,
    )
