"""Pallas TPU kernel: fused greedy diverse selection (paper §II-B-2 / Alg. 2).

Given a scored candidate tile and its diversity-graph adjacency, run the k
greedy steps entirely in VMEM: pick the best non-banned candidate, then ban
its adjacency row. Each step is one masked max + one vectorized mask OR
over K lanes — the sequential-k loop stays on-chip instead of bouncing
score/mask tensors through HBM between steps.

``greedy_select`` is that loop, shared with the fused round kernel
(``fused_round.py``). It is written for Mosaic's lowering rules:

* the ban mask is an int32 ``(1, K)`` carry (Mosaic cannot carry bool
  vectors through ``scf.for``);
* the pick is the first index of the row maximum (``argmax``'s tie rule,
  spelled as a max and a masked min so no value is dynamically indexed);
* picks and their scores accumulate in ``(1, k_pad)`` vectors and are
  written once after the loop (a one-lane dynamic store is refused);
* the adjacency row comes from the caller (``ban_row``): the greedy kernel
  loads the tile-aligned 32-row int8 slab holding it (one-row loads from
  packed int8 tiles are refused) and picks the row with a masked max; the
  fused round kernel computes it from the candidate tile.

The batched entry point (``greedy_diversify_batch_pallas``) runs one grid
program per request lane over ``(B, 1, K)`` score and ``(B, K, K)``
adjacency blocks; the single-query form is the ``B = 1`` case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SLAB = 32     # rows of one int8 (32, 128) tile


def greedy_select(scores, ban_row, k: int, kp: int):
    """k greedy steps over ``scores`` (1, K) f32 (-inf = invalid).

    ``ban_row(j)`` returns the adjacency row of the picked index ``j`` (a
    ``(1, 1)`` int32 vector) as a ``(1, K)`` bool. Returns ``(picks int32
    (1, kp), pick_scores f32 (1, kp))``: local indices -1-padded and the
    picked scores zero-padded. The picked index itself is banned
    explicitly, so the adjacency diagonal may hold anything.
    """
    K = scores.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)

    def body(t, carry):
        banned, picks, psc = carry
        avail = jnp.where(banned > 0, -jnp.inf, scores)
        m = jnp.max(avail, axis=1, keepdims=True)                 # (1, 1)
        j = jnp.min(jnp.where(avail == m, lane, K), axis=1,
                    keepdims=True)                                # (1, 1)
        j = jnp.minimum(j, K - 1)
        ok = m > -jnp.inf
        picks = jnp.where((slot == t) & ok, j, picks)
        psc = jnp.where((slot == t) & ok, m, psc)
        hit = (ban_row(j) | (lane == j)) & ok
        return banned | hit.astype(jnp.int32), picks, psc

    banned0 = jnp.where(jnp.isfinite(scores), 0, 1).astype(jnp.int32)
    carry = (banned0, jnp.full((1, kp), -1, jnp.int32),
             jnp.zeros((1, kp), jnp.float32))
    _, picks, psc = jax.lax.fori_loop(0, k, body, carry)
    return picks, psc


def _kernel(scores_ref, adj_ref, sel_ref, *, k: int):
    K = adj_ref.shape[2]
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SLAB, K), 0)

    def ban_row(j):
        # one tile-aligned slab of int8 rows, then the row by a masked max
        jj = j[0, 0]
        base = pl.multiple_of((jj // _SLAB) * _SLAB, _SLAB)
        slab = adj_ref[0, pl.ds(base, _SLAB), :].astype(jnp.int32)
        row = jnp.max(jnp.where(sub == jj - base, slab, 0), axis=0,
                      keepdims=True)
        return row != 0

    picks, _ = greedy_select(scores_ref[0], ban_row, k, sel_ref.shape[2])
    sel_ref[0] = picks


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def greedy_diversify_batch_pallas(scores: jnp.ndarray, adj: jnp.ndarray,
                                  k: int, interpret: bool = False) -> jnp.ndarray:
    """Batched greedy selection: one grid program per request lane.

    scores (B, K) f32 (-inf = invalid), adj (B, K, K). Returns sel
    int32[B, k] local indices (-1 padded).
    """
    B, K = scores.shape
    Kp = -(-K // 128) * 128
    kp = -(-k // 128) * 128
    s_p = jnp.full((B, 1, Kp), -jnp.inf, jnp.float32).at[:, 0, :K].set(
        scores.astype(jnp.float32))
    a_p = jnp.zeros((B, Kp, Kp), jnp.int8).at[:, :K, :K].set(
        adj.astype(jnp.int8))
    sel = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, Kp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Kp, Kp), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, kp), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, kp), jnp.int32),
        interpret=interpret,
    )(s_p, a_p)
    return sel[:, 0, :k]


def greedy_diversify_pallas(scores: jnp.ndarray, adj: jnp.ndarray, k: int,
                            interpret: bool = False) -> jnp.ndarray:
    """Single-query form: scores (K,), adj (K, K) -> sel int32[k] (-1 pad)."""
    return greedy_diversify_batch_pallas(scores[None], adj[None], k,
                                         interpret=interpret)[0]
