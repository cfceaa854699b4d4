"""div-A* in JAX: exact diverse-set optimization under jit (DESIGN.md §2).

The paper's div-A* walks a dynamically grown search tree; TPU-side we run the
equivalent depth-first branch-and-bound as a ``lax.while_loop`` over a
fixed-capacity stack (depth <= k+1 thanks to in-place sibling cursors).
Candidates are processed in (score desc, id asc) order; the admissible bound
is current score + sum of the next best remaining scores (conflicts
ignored) — identical to the python oracle ``div_astar_ref``. That bound
only falls along a level (scores are sorted), so the first sibling that
fails it ends the level: a long tail of hopeless candidates costs one
step, not one step each.

Pruning keeps a state alive if it could improve the incumbent of ANY size
m' <= k, so the optimal sets of every size 1..k come out certified (PSS
consumes all of them through Theorem 2).

A step budget bounds the loop for jit; ``complete=False`` signals exhaustion
(drivers then fall back to a larger budget — never observed at
Theorem-1/2-sized K in our benchmarks).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG = jnp.float32(-jnp.inf)


class DivAStarResult(NamedTuple):
    best_sets: jnp.ndarray    # int32[k, k] local indices, -1 padded; row m = size m+1
    best_scores: jnp.ndarray  # f32[k]
    complete: jnp.ndarray     # bool
    expansions: jnp.ndarray   # int32


@functools.partial(jax.jit, static_argnames=("k", "max_expansions"))
def div_astar(scores: jnp.ndarray, adj: jnp.ndarray, k: int,
              max_expansions: int = 200_000) -> DivAStarResult:
    K = scores.shape[0]
    scores = scores.astype(jnp.float32)
    valid = jnp.isfinite(scores)
    order = jnp.lexsort((jnp.arange(K), -jnp.where(valid, scores, NEG)))
    s = jnp.where(valid[order], scores[order], NEG)
    a = adj[order][:, order]
    # cum[i] = sum of the i best (valid) scores
    cum = jnp.concatenate([jnp.zeros(1), jnp.cumsum(jnp.where(s > NEG, s, 0.0))])
    n_valid = jnp.sum(valid).astype(jnp.int32)

    class Carry(NamedTuple):
        t: jnp.ndarray             # stack top (depth == #chosen at top)
        cursor: jnp.ndarray        # int32[k+1]
        score: jnp.ndarray         # f32[k+1]
        banned: jnp.ndarray        # bool[k+1, K]
        chosen: jnp.ndarray        # int32[k+1, k]
        best_scores: jnp.ndarray   # f32[k]
        best_sets: jnp.ndarray     # int32[k, k]
        steps: jnp.ndarray

    init = Carry(
        t=jnp.int32(0),
        cursor=jnp.zeros((k + 1,), jnp.int32),
        score=jnp.zeros((k + 1,), jnp.float32),
        banned=jnp.zeros((k + 1, K), jnp.bool_),
        chosen=jnp.full((k + 1, k), -1, jnp.int32),
        best_scores=jnp.full((k,), NEG),
        best_sets=jnp.full((k, k), -1, jnp.int32),
        steps=jnp.int32(0),
    )

    def cond(c: Carry):
        return (c.t >= 0) & (c.steps < max_expansions)

    def body(c: Carry):
        cur = c.cursor[c.t]
        depth = c.t

        def pop(c: Carry):
            return c._replace(t=c.t - 1, steps=c.steps + 1)

        def advance(c: Carry):
            cand = cur
            cursor = c.cursor.at[c.t].add(1)
            c = c._replace(cursor=cursor, steps=c.steps + 1)
            skip = c.banned[depth, cand] | (s[cand] <= NEG)

            def consider(c: Carry):
                new_score = c.score[depth] + s[cand]
                m = depth + 1  # size of the new set
                new_chosen_row = c.chosen[depth].at[m - 1].set(cand)
                improve = new_score > c.best_scores[m - 1]
                best_scores = c.best_scores.at[m - 1].set(
                    jnp.maximum(c.best_scores[m - 1], new_score))
                best_sets = jnp.where(improve,
                                      c.best_sets.at[m - 1].set(new_chosen_row),
                                      c.best_sets)
                c = c._replace(best_scores=best_scores, best_sets=best_sets)

                # promising for any deeper size m2 in (m, k] ?
                sizes = jnp.arange(1, k + 1)          # candidate m2
                add = sizes - m                        # how many more to pick
                hi = jnp.clip(cand + 1 + add, 0, K)
                feasible = (add > 0) & (cand + 1 + add <= n_valid + 0 * hi) \
                    & (cand + 1 + add <= K)
                bounds = new_score + (cum[hi] - cum[cand + 1])
                promising = jnp.any(jnp.where(
                    feasible, bounds > c.best_scores, False))
                do_push = (m < k) & promising
                # scores are sorted, so the plain bound only falls along
                # this level: once it fails, no later sibling can improve
                # any size either, and the level is done
                c = c._replace(cursor=jnp.where(
                    promising, c.cursor, c.cursor.at[depth].set(K)))

                def push(c: Carry):
                    nt = c.t + 1
                    new_banned = c.banned[depth] | a[cand]
                    new_banned = new_banned.at[cand].set(True)
                    return c._replace(
                        t=nt,
                        cursor=c.cursor.at[nt].set(cand + 1),
                        score=c.score.at[nt].set(new_score),
                        banned=c.banned.at[nt].set(new_banned),
                        chosen=c.chosen.at[nt].set(new_chosen_row),
                    )

                return jax.lax.cond(do_push, push, lambda c: c, c)

            return jax.lax.cond(skip, lambda c: c, consider, c)

        do_pop = (cur >= K) | (depth >= k)
        return jax.lax.cond(do_pop, pop, advance, c)

    out = jax.lax.while_loop(cond, body, init)
    # map sorted-space indices back to caller-local indices
    safe = jnp.maximum(out.best_sets, 0)
    mapped = jnp.where(out.best_sets >= 0, order[safe].astype(jnp.int32), -1)
    return DivAStarResult(
        best_sets=mapped,
        best_scores=out.best_scores,
        complete=out.t < 0,
        expansions=out.steps,
    )


def optimal_diverse_set(scores, adj, k, max_expansions: int = 200_000):
    """Convenience: (ids_local int32[k] (-1 pad), total_score, complete)."""
    res = div_astar(scores, adj, k, max_expansions)
    return res.best_sets[k - 1], res.best_scores[k - 1], res.complete
