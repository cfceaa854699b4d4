"""The plain float64 reference the benchmark holds served answers to.

A copy, kept with the benchmark so that no change to ``src/`` can move the
yardstick: ``div_astar_ref`` is the exact div-A* of the paper (Qin et al.,
as adopted in §II-B-1), and ``host_oracle`` / ``recheck_frontier`` are the
host checks that the bring-up smoke used on the chip. Nothing here imports
the program or JAX: only numpy, in float64.

``compare`` turns a sample of served answers into the numbers that decide
``correct`` (each held to a limit of its own, see ``bench/configs``):

* ``score_err``    widest gap between a served id's reported score and its
                   float64 similarity to the query (search stage);
* ``div_excess``   widest excess of a served pair's float64 similarity over
                   eps (diversify stage: the served set is eps-diverse);
* ``recheck_fail`` certified answers whose Theorem-2 certificate fails when
                   re-proved in float64 over its frontier (verify stage);
* ``short``        answers with fewer than k ids where k are feasible;
* ``suboptimal``   answers of k ids whose float64 total lies below the
                   float64 optimum over the whole corpus by more than
                   float32 rounding (search and diversify stages: the
                   answer names real rows with their true scores, but
                   worse ones than the search should have found);

and ``recall`` per answer against the globally optimal diverse set, and
``total_gap``, the optimum's total less the answer's, both in float64.
"""
from __future__ import annotations

import itertools

import numpy as np


# ------------------------------------------------------------- similarity

def sims64(q, x, metric):
    """float64 similarity of q[d] to rows of x[m, d] (paper Eqs. 5-7)."""
    q = np.asarray(q, np.float64)
    x = np.asarray(x, np.float64)
    dots = x @ q
    if metric == "ip":
        return dots
    if metric == "cos":
        return dots / (np.maximum(np.linalg.norm(x, axis=1), 1e-6)
                       * max(np.linalg.norm(q), 1e-6))
    return 1.0 - np.sqrt(np.maximum(((x - q) ** 2).sum(axis=1), 0.0))


def pair_sims64(a, metric):
    """float64 similarity among the rows of a[m, d]."""
    a = np.asarray(a, np.float64)
    dots = a @ a.T
    if metric == "ip":
        return dots
    if metric == "cos":
        nrm = np.maximum(np.linalg.norm(a, axis=1), 1e-6)
        return dots / np.outer(nrm, nrm)
    sq = (a * a).sum(axis=1)
    return 1.0 - np.sqrt(np.maximum(sq[:, None] + sq[None, :] - 2.0 * dots,
                                    0.0))


# ------------------------------------------------------------------ div-A*

def div_astar_ref(scores, adj, k):
    """Exact max-total-score independent sets of sizes 1..k on the
    diversity graph ``adj``. Returns ``(best_sets, best_scores)``:
    ``best_sets[m]`` the local indices of the optimal set of size m+1
    (None if there is none), ``best_scores[m]`` its total (or -inf).

    Depth-first branch and bound over candidates in descending score order
    with the admissible bound "current score plus the best remaining
    scores". A state is pruned only when it cannot improve the incumbent of
    any size in (|S|, k] (Theorem 2 consumes every size); scores are
    sorted, so the first sibling that fails the bound ends its level."""
    scores = np.asarray(scores, np.float64)
    n = scores.shape[0]
    adj = np.asarray(adj, bool)
    k = min(k, n)
    order = np.lexsort((np.arange(n), -scores))
    s_sorted = scores[order]
    adj_sorted = adj[np.ix_(order, order)]
    cum = np.concatenate([[0.0], np.cumsum(s_sorted)])
    best_scores = np.full(k, -np.inf)
    best_sets: list = [None] * k

    def bound(score, cursor, add):
        hi = cursor + add
        return -np.inf if hi > n else score + (cum[hi] - cum[cursor])

    stack = [([], np.zeros(n, bool), 0.0, 0)]
    while stack:
        chosen, banned, score, cursor = stack[-1]
        if cursor >= n or len(chosen) >= k:
            stack.pop()
            continue
        stack[-1] = (chosen, banned, score, cursor + 1)
        if banned[cursor]:
            continue
        new_score = score + s_sorted[cursor]
        new_chosen = chosen + [cursor]
        m = len(new_chosen)
        if new_score > best_scores[m - 1]:
            best_scores[m - 1] = new_score
            best_sets[m - 1] = list(new_chosen)
        if m >= k:
            stack[-1] = (chosen, banned, score, n)
            continue
        new_banned = banned | adj_sorted[cursor]
        new_banned[cursor] = True
        if any(bound(new_score, cursor + 1, m2 - m) > best_scores[m2 - 1]
               for m2 in range(m + 1, k + 1)):
            stack.append((new_chosen, new_banned, new_score, cursor + 1))
        else:
            stack[-1] = (chosen, banned, score, n)
    out = [None if s is None else sorted(int(order[i]) for i in s)
           for s in best_sets]
    return out, best_scores


def theorem2_gap(best, k):
    """min over i < k of (best[k-1] - best[i]) / (k-1-i): the paper's
    minValue; the set is optimal beyond a frontier whose last score is
    below it."""
    gaps = [(best[k - 1] - best[i]) / (k - 1 - i)
            for i in range(k - 1) if np.isfinite(best[i])]
    return min(gaps, default=np.inf)


def host_oracle(x64, metric, q, k, eps, X=256, sims_fn=None,
                pair_fn=None):
    """Globally optimal diverse top-k over the rows of ``x64``: exact top-X
    by brute force, div-A* over it, X doubled until Theorem 2 holds.
    Returns ``(ids, total, frontier_ids, scores_of_ids, certified)``.

    ``sims_fn`` / ``pair_fn`` replace the float64 similarities (the
    lower-precision control, ``bench.control``)."""
    sims_fn = sims_fn or sims64
    pair_fn = pair_fn or pair_sims64
    s = sims_fn(q, x64, metric)
    n = s.shape[0]
    order = np.lexsort((np.arange(n), -s))
    while True:
        X = min(X, n)
        ids = order[:X]
        sc = s[ids]
        adj = pair_fn(x64[ids], metric) > eps
        np.fill_diagonal(adj, False)
        sets, best = div_astar_ref(sc, adj, k)
        holds = (np.isfinite(best[k - 1])
                 and theorem2_gap(best, k) > sc[-1])
        if holds or X >= n:
            sel = np.asarray(sets[k - 1] or [], np.int64)
            return (ids[sel], float(best[k - 1]), ids, sc[sel],
                    bool(np.isfinite(best[k - 1])))
        X *= 2


# ------------------------------------------------------- certificate check

def f32_total_tol(total, k):
    """Float32 rounding allowed on a total of k scores: k ulps of its
    magnitude. The engine picks and certifies sets in float32; two sets
    whose float64 totals differ by less are a tie to it."""
    return k * float(np.spacing(np.float32(max(abs(total), 1.0))))


def f32_sim_tol(eps):
    """Float32 rounding allowed on one similarity near eps: 16 ulps of its
    magnitude (covers the cancellation in the l2 form)."""
    return 16 * float(np.spacing(np.float32(max(abs(eps), 1.0))))


MAX_AMBIGUOUS = 6


def recheck_frontier(frontier_ids, q, x64, metric, k, eps, served_total):
    """The engine's Theorem-2 certificate, re-proved in float64 over the
    candidate frontier it was issued on. Returns ``(ok, margin)``.

    The frontier's optimal diverse total must equal the served total and
    pass Theorem 2 against its last score, ties allowed, both within
    ``f32_total_tol``. A pair whose float64 similarity lies within
    ``f32_sim_tol`` of eps may be an edge of the engine's float32 G^eps or
    not: the certificate passes if it holds for some choice of those pairs
    (every choice up to ``MAX_AMBIGUOUS`` such pairs; beyond that, all
    edges or none). ``margin`` is the best choice's smaller slack."""
    ids = np.asarray(frontier_ids)
    ids = ids[ids >= 0]
    if ids.size < k:
        return False, -np.inf
    s = sims64(q, x64[ids], metric)
    order = np.lexsort((ids, -s))
    ids, s = ids[order], s[order]
    pair = pair_sims64(x64[ids], metric)
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
        _, best = div_astar_ref(s, adj, k)
        if not np.isfinite(best[k - 1]):
            continue
        margin = max(margin, min(theorem2_gap(best, k) - s[-1] + 2 * tol,
                                 tol - abs(served_total - best[k - 1])))
    return bool(margin >= 0), float(margin)


# -------------------------------------------------------------- comparison

def check_answer(ans, q, x64, metric, k, eps):
    """The numbers of one served answer. ``ans`` has ``ids``, ``scores``,
    ``certified`` and ``frontier`` (ids, or None). Returns a dict with
    ``score_err``, ``div_excess``, ``recheck_fail``, ``short``,
    ``suboptimal``, ``total_gap`` and ``recall``."""
    ids = np.asarray(ans["ids"], np.int64)
    keep = ids >= 0
    ids = ids[keep]
    scores = np.asarray(ans["scores"], np.float64)[keep]
    truth, opt, _, _, _ = host_oracle(x64, metric, q, k, eps)
    s64 = sims64(q, x64[ids], metric) if ids.size else np.zeros(0)
    score_err = float(np.max(np.abs(scores - s64), initial=0.0))
    pair = pair_sims64(x64[ids], metric)[np.triu_indices(ids.size, 1)]
    div_excess = float(np.max(pair - eps)) if pair.size else 0.0
    fail, margin = 0, None
    if ans["certified"]:
        got = float(s64.sum())
        if ans["frontier"] is None:
            margin = (got - opt + f32_total_tol(opt, k)
                      if ids.size == len(truth) else -np.inf)
        else:
            _, margin = recheck_frontier(ans["frontier"], q, x64, metric, k,
                                         eps, got)
        fail = int(margin < 0)
    short = ids.size < len(truth)
    gap = opt - float(s64.sum()) if not short else np.inf
    recall = (len(set(ids.tolist()) & set(np.asarray(truth).tolist()))
              / max(len(truth), 1))
    return dict(score_err=score_err, div_excess=div_excess,
                recheck_fail=fail, recheck_margin=margin, short=int(short),
                suboptimal=int(not short and gap > f32_total_tol(opt, k)),
                total_gap=gap, recall=recall)


def summarize(rows):
    """Fold per-answer numbers into the compared ones (worst case) and the
    mean recall."""
    return dict(
        score_err=max((r["score_err"] for r in rows), default=0.0),
        div_excess=max((r["div_excess"] for r in rows), default=0.0),
        recheck_fail=sum(r["recheck_fail"] for r in rows),
        short=sum(r["short"] for r in rows),
        suboptimal=sum(r["suboptimal"] for r in rows),
        total_gap=max((r["total_gap"] for r in rows if not r["short"]),
                      default=0.0),
        recheck_margins=[r["recheck_margin"] for r in rows
                         if r["recheck_fail"]],
        recall=float(np.mean([r["recall"] for r in rows])) if rows else 0.0)
