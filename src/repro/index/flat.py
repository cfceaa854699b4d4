"""Exact (brute-force) index — the recall/ground-truth oracle.

Also provides the fast KNN-graph proximity index (``build_knn_graph``): exact
top-(M+1) neighbors via blocked matmul + Vamana-style alpha pruning + reverse
edges. Functionally comparable to HNSW level-0 but built in O(N^2 d / block)
vectorized work, which is what the 1-core container can afford at N >= 50k
(DESIGN.md §2). Both emit ``FlatGraph`` so every searcher runs on either.
"""
from __future__ import annotations

import numpy as np

from repro.core.graph import FlatGraph, make_flat_graph


#: entry candidates per graph: scoring them is one (1024, d) similarity per
#: search, and 1024 random rows cover every cluster of the benchmark corpora
#: (256 missed some of the 64 deep-like clusters at n=8192)
NUM_ENTRIES = 1024


def _sims_block(q_block: np.ndarray, x: np.ndarray, metric: str) -> np.ndarray:
    dots = q_block @ x.T
    if metric == "ip":
        return dots
    if metric == "cos":
        qn = np.maximum(np.linalg.norm(q_block, axis=1, keepdims=True), 1e-12)
        xn = np.maximum(np.linalg.norm(x, axis=1), 1e-12)
        return dots / (qn * xn[None, :])
    if metric == "l2":
        q2 = np.einsum("nd,nd->n", q_block, q_block)[:, None]
        x2 = np.einsum("nd,nd->n", x, x)[None, :]
        return 1.0 - np.sqrt(np.maximum(q2 + x2 - 2.0 * dots, 0.0))
    raise ValueError(metric)


def exact_topk(queries: np.ndarray, x: np.ndarray, k: int, metric: str,
               block: int = 256) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k (ids, scores) per query; deterministic id tie-break."""
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    out_ids = np.empty((queries.shape[0], k), np.int32)
    out_scores = np.empty((queries.shape[0], k), np.float32)
    for s in range(0, queries.shape[0], block):
        sims = _sims_block(queries[s:s + block], x, metric)
        # lexicographic: score desc, id asc
        order = np.lexsort((np.arange(x.shape[0])[None, :].repeat(
            sims.shape[0], 0), -sims), axis=1)[:, :k]
        out_ids[s:s + block] = order
        out_scores[s:s + block] = np.take_along_axis(sims, order, axis=1)
    return out_ids, out_scores


def exact_rerank(queries: np.ndarray, cand_ids: np.ndarray, x: np.ndarray,
                 metric: str) -> tuple[np.ndarray, np.ndarray]:
    """Exact float rerank of candidate frontiers (the quantized path's
    score-then-verify stage).

    ``queries`` f32[B, d], ``cand_ids`` int[B, K] (-1 padded), ``x``
    f32[N, d] the float corpus. Each row's valid candidates are re-scored
    with exact float similarity and re-sorted descending by score with
    ascending-id tie-break (the same order ``exact_topk`` and the
    tournament merge use); -1 entries keep score -inf and sink to the
    tail. Returns ``(ids int32[B, K], scores f32[B, K])``.
    """
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    cand_ids = np.asarray(cand_ids, np.int64)
    b, k = cand_ids.shape
    out_ids = np.full((b, k), -1, np.int32)
    out_scores = np.full((b, k), -np.inf, np.float32)
    for r in range(b):
        valid = cand_ids[r] >= 0
        ids = cand_ids[r][valid]
        if ids.size == 0:
            continue
        sims = _sims_block(queries[r][None], x[ids], metric)[0]
        order = np.lexsort((ids, -sims))
        out_ids[r, : ids.size] = ids[order]
        out_scores[r, : ids.size] = sims[order]
    return out_ids, out_scores


def build_knn_graph(vectors: np.ndarray, metric: str = "l2", M: int = 16,
                    alpha_sim: float = 1.0, block: int = 512,
                    seed: int = 0) -> FlatGraph:
    """Exact-KNN proximity graph with alpha pruning + reverse edges.

    The graph carries ``NUM_ENTRIES`` entry candidates: the medoid, then
    rows sampled at ``seed`` among those with a directed path to it (so
    every node is reachable from every candidate). A search starts at the
    candidate most similar to its query (``core.graph.entry_for``). kNN
    edges rarely leave a cluster, so from the medoid alone a beam search
    settles in the wrong cluster of a clustered corpus.

    Inner-product graphs link angular (``cos``) neighbours; the search
    still scores by inner product. An ip kNN graph links every node to the
    same few large-norm hubs, and beam search then misses a fifth of the
    top 300 on the txt2img-like corpus (recall 0.82 vs 0.999 over the
    angular graph, n=16384).
    """
    search_metric = metric
    if metric == "ip":
        metric = "cos"
    x = np.asarray(vectors, np.float32)
    n = x.shape[0]
    M0 = 2 * M
    overfetch = min(n - 1, 3 * M0)
    knn = np.empty((n, overfetch), np.int32)
    for s in range(0, n, block):
        sims = _sims_block(x[s:s + block], x, metric)
        rows = np.arange(s, min(s + block, n))
        sims[np.arange(rows.size), rows] = -np.inf  # drop self
        part = np.argpartition(-sims, overfetch, axis=1)[:, :overfetch]
        ps = np.take_along_axis(sims, part, axis=1)
        order = np.argsort(-ps, axis=1, kind="stable")
        knn[s:s + block] = np.take_along_axis(part, order, axis=1)

    neighbors = _alpha_prune(x, knn, metric, M0, alpha_sim, block)

    # reverse edges into free slots (connectivity)
    free = (neighbors < 0).sum(axis=1)
    for i in range(n):
        for j in neighbors[i]:
            if j < 0:
                break
            if free[j] > 0 and i not in neighbors[j]:
                neighbors[j, M0 - free[j]] = i
                free[j] -= 1

    # medoid entry point
    mean = x.mean(axis=0)
    entry = int(np.argmax(_sims_block(mean[None], x, metric)[0]))

    # --- connectivity repair -------------------------------------------
    # Pure nearest-neighbor edges fragment clustered data into islands
    # (every top-M neighbor is a cluster-mate). Stitch components together
    # through their closest cross-component pairs, bidirectionally, until
    # the graph is connected from the entry point.
    neighbors = _stitch_components(x, neighbors, entry, metric)
    for _ in range(4):
        neighbors = _directed_repair(x, neighbors, entry, knn, metric)
        if not _escape_repair(x, neighbors, entry, metric):
            break
    others = np.flatnonzero(_reaching(neighbors, entry))
    others = others[others != entry]
    picks = np.random.default_rng(seed).choice(
        others, min(others.size, NUM_ENTRIES - 1), replace=False)
    entries = np.concatenate([[entry], np.sort(picks)]).astype(np.int32)
    return make_flat_graph(x, neighbors, None, entries, search_metric)


def _batched_sims(q: np.ndarray, c: np.ndarray, metric: str) -> np.ndarray:
    """sims[b, i, j] = sim(q[b, i], c[b, j]) for q[b, m, d], c[b, k, d]."""
    dots = np.matmul(q, c.transpose(0, 2, 1))
    if metric == "ip":
        return dots
    qq = np.einsum("bmd,bmd->bm", q, q)[:, :, None]
    cc = np.einsum("bkd,bkd->bk", c, c)[:, None, :]
    if metric == "cos":
        return dots / (np.maximum(np.sqrt(qq), 1e-12)
                       * np.maximum(np.sqrt(cc), 1e-12))
    if metric == "l2":
        return 1.0 - np.sqrt(np.maximum(qq + cc - 2.0 * dots, 0.0))
    raise ValueError(metric)


def _alpha_prune(x: np.ndarray, knn: np.ndarray, metric: str, M0: int,
                 alpha_sim: float, block: int) -> np.ndarray:
    """Vamana-style alpha pruning of each node's sorted KNN candidates.

    Candidates are taken in order; one is kept unless an already kept
    candidate is at least as similar to it (times ``alpha_sim``) as the
    node is. Slots left after pruning are filled with the pruned candidates
    in order. Runs over blocks of nodes at once: one batched Gram per block,
    then one vectorized step per candidate position.
    """
    n, m = knn.shape
    width = min(M0, m)
    neighbors = np.full((n, M0), -1, np.int32)
    for s in range(0, n, block):
        cands = knn[s:s + block]                       # (b, m)
        cv = x[cands]                                  # (b, m, d)
        sims_q = _batched_sims(x[s:s + block, None], cv, metric)[:, 0]
        pair = _batched_sims(cv, cv, metric)           # (b, m, m)
        kept = np.zeros(cands.shape, bool)
        count = np.zeros(cands.shape[0], np.int64)
        for t in range(m):
            close = kept & (pair[:, t, :] * alpha_sim >= sims_q[:, t:t + 1])
            take = ~close.any(axis=1) & (count < M0)
            kept[:, t] = take
            count += take
        order = np.argsort(~kept, axis=1, kind="stable")[:, :width]
        neighbors[s:s + block, :width] = np.take_along_axis(cands, order, 1)
    return neighbors


def _directed_reachable(neighbors: np.ndarray, entry: int) -> np.ndarray:
    n = neighbors.shape[0]
    reached = np.zeros(n, bool)
    reached[entry] = True
    frontier = np.array([entry])
    while frontier.size:
        nxt = neighbors[frontier].ravel()
        nxt = nxt[nxt >= 0]
        nxt = np.unique(nxt)
        nxt = nxt[~reached[nxt]]
        if nxt.size == 0:
            break
        reached[nxt] = True
        frontier = nxt
    return reached


def _reaching(neighbors: np.ndarray, target: int) -> np.ndarray:
    """bool[n]: the nodes with a directed path to ``target``."""
    n = neighbors.shape[0]
    src = np.repeat(np.arange(n), neighbors.shape[1])
    dst = neighbors.ravel()
    src, dst = src[dst >= 0], dst[dst >= 0]
    reach = np.zeros(n, bool)
    reach[target] = True
    while True:
        new = np.unique(src[reach[dst]])
        new = new[~reach[new]]
        if new.size == 0:
            return reach
        reach[new] = True


def _escape_repair(x: np.ndarray, neighbors: np.ndarray, entry: int,
                   metric: str, max_rounds: int = 64) -> bool:
    """Give every node a directed path back to ``entry`` (in place), so a
    search that starts anywhere can reach the whole graph: while some nodes
    cannot reach it, link the closest (stuck, reaching) pair by an edge out
    of the stuck node (a free slot, else its last). One edge frees a whole
    sink cluster. Returns whether any edge was added."""
    m0 = neighbors.shape[1]
    changed = False
    for _ in range(max_rounds):
        reach = _reaching(neighbors, entry)
        stuck = np.flatnonzero(~reach)
        if stuck.size == 0:
            break
        reaching = np.flatnonzero(reach)
        best = (-np.inf, -1, -1)
        for s in range(0, stuck.size, 256):
            blk = stuck[s:s + 256]
            sims = _sims_block(x[blk], x[reaching], metric)
            i, j = divmod(int(np.argmax(sims)), reaching.size)
            if sims[i, j] > best[0]:
                best = (float(sims[i, j]), int(blk[i]), int(reaching[j]))
        _, u, v = best
        row = neighbors[u]
        slot = np.flatnonzero(row < 0)
        neighbors[u, slot[0] if slot.size else m0 - 1] = v
        changed = True
    return changed


def _directed_repair(x: np.ndarray, neighbors: np.ndarray, entry: int,
                     knn: np.ndarray, metric: str,
                     max_rounds: int = 32) -> np.ndarray:
    """Beam search follows directed edges; make every node entry-reachable.

    For each unreached node, add one in-edge from its nearest already
    reached KNN candidate (slot rotation spreads evictions); repeat until
    the directed BFS covers the graph.
    """
    n, m0 = neighbors.shape
    for _ in range(max_rounds):
        reached = _directed_reachable(neighbors, entry)
        missing = np.flatnonzero(~reached)
        if missing.size == 0:
            return neighbors
        # in-edge source per missing node: its first reached KNN candidate,
        # else its most similar reached node (blocked scan)
        src = np.full(missing.size, -1, np.int64)
        hit = reached[knn[missing]]
        has = hit.any(axis=1)
        src[has] = knn[missing[has], hit[has].argmax(axis=1)]
        lost = np.flatnonzero(~has)
        if lost.size:
            reached_ids = np.flatnonzero(reached)
            xr = x[reached_ids]
            for s in range(0, lost.size, 256):
                blk = lost[s:s + 256]
                sims = _sims_block(x[missing[blk]], xr, metric)
                src[blk] = reached_ids[sims.argmax(axis=1)]
        for u, v in zip(missing, src):
            row = neighbors[v]
            if u in row:
                continue
            slot = np.flatnonzero(row < 0)
            idx = slot[0] if slot.size else (int(u) % m0)
            neighbors[v, idx] = u
    return neighbors


def _components(neighbors: np.ndarray) -> np.ndarray:
    """Undirected connected components over the adjacency (union-find)."""
    n = neighbors.shape[0]
    parent = np.arange(n)

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for i in range(n):
        for j in neighbors[i]:
            if j >= 0:
                ra, rb = find(i), find(int(j))
                if ra != rb:
                    parent[ra] = rb
    return np.array([find(i) for i in range(n)])


def _stitch_components(x: np.ndarray, neighbors: np.ndarray, entry: int,
                       metric: str, max_rounds: int = 64) -> np.ndarray:
    n, m0 = neighbors.shape
    for _ in range(max_rounds):
        comp = _components(neighbors)
        main = comp[entry]
        others = np.unique(comp[comp != main])
        if others.size == 0:
            return neighbors
        in_main = np.flatnonzero(comp == main)
        for c in others:
            members = np.flatnonzero(comp == c)
            # closest (member, main) pair via blocked sims
            best = (-np.inf, -1, -1)
            for s in range(0, members.size, 128):
                blk = members[s:s + 128]
                sims = _sims_block(x[blk], x[in_main], metric)
                flat = int(np.argmax(sims))
                bi, bj = divmod(flat, in_main.size)
                val = float(sims[bi, bj])
                if val > best[0]:
                    best = (val, int(blk[bi]), int(in_main[bj]))
            _, a, b = best
            for (u, v) in ((a, b), (b, a)):
                row = neighbors[u]
                slot = np.flatnonzero(row < 0)
                if slot.size:
                    neighbors[u, slot[0]] = v
                else:
                    neighbors[u, m0 - 1] = v  # overwrite weakest slot
    return neighbors
