"""Pallas TPU kernel: tiled batched similarity scoring (the search hot loop).

Computes scores[b, n] = sim(qs[b], x[n]) for metric in {l2, ip, cos} as one
MXU pass per (BQ, BN) tile. This is the paper's per-node ``sim(v, q)``
re-expressed as a blocked matmul (DESIGN.md §2): beam-search neighbor
expansion scores an (M0, d) gather block at once, and batched / sharded
search scores (BQ, d) x (d, BN) tiles.

Tiling: qs tile (BQ, d) and x tile (BN, d) live in VMEM; d is kept whole
(padded to a multiple of 128 by the wrapper so the MXU contraction dim is
aligned); accumulation in f32 via preferred_element_type.

VMEM budget at defaults (BQ=128, BN=512, d<=1024, f32):
  128*1024*4 + 512*1024*4 + 128*512*4 = 0.5MB + 2MB + 0.25MB < 3MB  (OK)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30


def _kernel(q_ref, x_ref, o_ref, *, metric: str):
    q = q_ref[...].astype(jnp.float32)          # (BQ, d)
    x = x_ref[...].astype(jnp.float32)          # (BN, d)
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # (BQ, BN)
    if metric == "ip":
        out = dots
    elif metric == "cos":
        qn = jnp.sqrt(jnp.maximum(jnp.sum(q * q, axis=1, keepdims=True), 1e-12))
        xn = jnp.sqrt(jnp.maximum(jnp.sum(x * x, axis=1, keepdims=True), 1e-12))
        out = dots / (qn * xn.T)
    elif metric == "l2":
        q2 = jnp.sum(q * q, axis=1, keepdims=True)
        x2 = jnp.sum(x * x, axis=1, keepdims=True)
        d2 = jnp.maximum(q2 + x2.T - 2.0 * dots, 0.0)
        out = 1.0 - jnp.sqrt(d2)
    else:
        raise ValueError(metric)
    o_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("metric", "bq", "bn", "interpret"))
def batch_similarity_many_pallas(qs: jnp.ndarray, x: jnp.ndarray, metric: str,
                                 bq: int = 128, bn: int = 512,
                                 interpret: bool = False) -> jnp.ndarray:
    """scores[b, n] for qs[b, d], x[n, d]. Pads internally; exact output."""
    b, d = qs.shape
    n, _ = x.shape
    bq = min(bq, max(8, -(-b // 8) * 8))
    bn = min(bn, max(128, -(-n // 128) * 128))
    bp = -(-b // bq) * bq
    np_ = -(-n // bn) * bn
    dp = -(-d // 128) * 128
    # zero padding preserves dots and norms; padded rows are sliced away.
    qs_p = jnp.zeros((bp, dp), qs.dtype).at[:b, :d].set(qs)
    x_p = jnp.zeros((np_, dp), x.dtype).at[:n, :d].set(x)
    grid = (bp // bq, np_ // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        interpret=interpret,
    )(qs_p, x_p)
    out = out[:b, :n]
    if metric == "l2":
        # guard: padded-dim zeros do not alter l2 (norms include zeros only)
        pass
    return out
