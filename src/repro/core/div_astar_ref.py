"""Pure-python div-A* oracle (Qin et al. [20], as adopted by the paper §II-B-1).

Exact max-total-score independent set of size k on a diversity graph, plus
the optimal sets of every size 1..k (needed by Theorem 2 / PSS).

Implementation: depth-first branch-and-bound over candidates in descending
score order with an admissible bound (current score + sum of the best
remaining scores, conflicts ignored). A state is pruned only when its bound
cannot improve the incumbent of ANY size m in (|S|, k] — pruning on size-k
alone could discard states that improve some smaller-size optimum, which
Theorem 2 consumes. Scores are sorted, so that bound only falls along a
level: the first sibling that fails it ends the level.

This file is the test oracle for ``repro.core.div_astar`` (the JAX version)
and the ground-truth generator for recall in the benchmarks.
"""
from __future__ import annotations

import numpy as np


def div_astar_ref(scores: np.ndarray, adj: np.ndarray, k: int,
                  node_budget: int | None = None):
    """Returns (best_sets, best_scores, complete).

    best_sets[m]   : list of local indices, the optimal diverse set of size
                     m+1 (or None if no independent set of that size exists).
    best_scores[m] : its total score (or -inf).
    complete       : False if the node budget was exhausted (results are then
                     best-so-far, not certified optimal).
    """
    scores = np.asarray(scores, np.float64)
    n = scores.shape[0]
    adj = np.asarray(adj, bool)
    k = min(k, n)
    order = np.lexsort((np.arange(n), -scores))  # score desc, id asc
    s_sorted = scores[order]
    adj_sorted = adj[np.ix_(order, order)]
    # suffix cumulative of sorted scores: cum[i] = sum of s_sorted[:i]
    cum = np.concatenate([[0.0], np.cumsum(s_sorted)])

    best_scores = np.full(k, -np.inf)
    best_sets: list[list[int] | None] = [None] * k

    def bound(score: float, cursor: int, add: int) -> float:
        """score + best `add` remaining scores from cursor on (admissible)."""
        hi = cursor + add
        if hi > n:
            return -np.inf
        return score + (cum[hi] - cum[cursor])

    # iterative DFS; frame = (chosen tuple, banned bitset, score, cursor)
    stack = [([], np.zeros(n, bool), 0.0, 0)]
    expansions = 0
    complete = True
    while stack:
        if node_budget is not None and expansions >= node_budget:
            complete = False
            break
        chosen, banned, score, cursor = stack[-1]
        if cursor >= n or len(chosen) >= k:
            stack.pop()
            continue
        stack[-1] = (chosen, banned, score, cursor + 1)
        if banned[cursor]:
            continue
        expansions += 1
        new_score = score + s_sorted[cursor]
        new_chosen = chosen + [cursor]
        m = len(new_chosen)
        if new_score > best_scores[m - 1]:
            best_scores[m - 1] = new_score
            best_sets[m - 1] = list(new_chosen)
        if m >= k:
            stack[-1] = (chosen, banned, score, n)   # sorted: none better
            continue
        # prune unless some size m' in (m, k] could improve
        new_banned = banned | adj_sorted[cursor]
        new_banned[cursor] = True
        promising = False
        for m2 in range(m + 1, k + 1):
            if bound(new_score, cursor + 1, m2 - m) > best_scores[m2 - 1]:
                promising = True
                break
        if promising:
            stack.append((new_chosen, new_banned, new_score, cursor + 1))
        else:
            # the bound only falls along a level: no later sibling can
            # improve any size either
            stack[-1] = (chosen, banned, score, n)

    # map sorted-local indices back to input-local indices
    out_sets = []
    for s in best_sets:
        out_sets.append(None if s is None else sorted(int(order[i]) for i in s))
    return out_sets, best_scores, complete


def brute_force_diverse(scores: np.ndarray, adj: np.ndarray, k: int):
    """Exponential exhaustive oracle for tiny instances (test-only)."""
    import itertools

    n = len(scores)
    best_score = -np.inf
    best = None
    for comb in itertools.combinations(range(n), k):
        ok = True
        for a in range(k):
            for b in range(a + 1, k):
                if adj[comb[a], comb[b]]:
                    ok = False
                    break
            if not ok:
                break
        if ok:
            sc = float(np.sum(np.asarray(scores)[list(comb)]))
            if sc > best_score:
                best_score, best = sc, list(comb)
    return best, best_score
