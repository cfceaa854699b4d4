"""Pallas TPU kernel: bitonic merge of two sorted candidate lists.

The tournament reducer of the sharded search (DESIGN.md §5) repeatedly merges
two descending-sorted (ids, scores) lists of length L and keeps the top L.
Concatenating ``a`` (descending) with ``reverse(b)`` (ascending, reversed
by the wrapper: Mosaic has no lowering for ``rev``) forms a bitonic sequence, so log2(2L) vectorized compare-exchange stages produce a
fully sorted result — no data-dependent control flow, pure VPU work.

Comparator matches the ref's lexsort exactly: (score desc, id asc).
L must be a power of two (wrapper pads with -inf sentinels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compare_exchange(scores, ids, dist):
    """One bitonic stage at the given distance over a (1, 2L) vector.

    Element i pairs with i ^ dist; the lower index of each pair keeps the
    (score desc, id asc)-greater element. Partners are fetched with lane
    rotations and selected by a mask, so no lane-splitting reshape is
    needed.
    """
    n = scores.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    is_hi = (lane & dist) == 0

    def partner(v):   # v[i + dist] on hi slots, v[i - dist] on lo slots
        return jnp.where(is_hi, pltpu.roll(v, n - dist, 1),
                         pltpu.roll(v, dist, 1))

    ps, pi = partner(scores), partner(ids)
    p_greater = (ps > scores) | ((ps == scores) & (pi < ids))
    self_greater = (scores > ps) | ((scores == ps) & (ids < pi))
    take = (is_hi & p_greater) | (~is_hi & self_greater)
    return jnp.where(take, ps, scores), jnp.where(take, pi, ids)


def _kernel(sa_ref, ia_ref, sb_ref, ib_ref, so_ref, io_ref, *, length: int):
    # b arrives reversed (ascending), so a ++ b is bitonic.
    scores = jnp.concatenate([sa_ref[...], sb_ref[...]], axis=1)  # (1, 2L)
    ids = jnp.concatenate([ia_ref[...], ib_ref[...]], axis=1)
    dist = length
    while dist >= 1:
        scores, ids = _compare_exchange(scores, ids, dist)
        dist //= 2
    so_ref[...] = scores[:, :length]
    io_ref[...] = ids[:, :length]


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_merge_pallas(ids_a, scores_a, ids_b, scores_b, interpret: bool = False):
    """Merge two descending-sorted lists; return top len(a) (ids, scores)."""
    L = ids_a.shape[0]
    Lp = max(128, 1 << (L - 1).bit_length())

    def pad(ids, scores):
        ids_p = jnp.full((1, Lp), jnp.iinfo(jnp.int32).max, jnp.int32)
        sc_p = jnp.full((1, Lp), -jnp.inf, jnp.float32)
        return (ids_p.at[0, :L].set(ids.astype(jnp.int32)),
                sc_p.at[0, :L].set(scores.astype(jnp.float32)))

    ia, sa = pad(ids_a, scores_a)
    ib, sb = pad(ids_b, scores_b)
    ib, sb = jnp.flip(ib, axis=1), jnp.flip(sb, axis=1)
    so, io = pl.pallas_call(
        functools.partial(_kernel, length=Lp),
        out_shape=(
            jax.ShapeDtypeStruct((1, Lp), jnp.float32),
            jax.ShapeDtypeStruct((1, Lp), jnp.int32),
        ),
        interpret=interpret,
    )(sa, ia, sb, ib)
    return io[0, :L], so[0, :L]
