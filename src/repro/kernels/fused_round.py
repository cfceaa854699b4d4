"""Pallas TPU kernel: one fused progressive-round diversify stage per lane.

The progressive serving loop's hot path (``ProgressiveEngine._pgs_round``)
used to be a chain of separate dispatches per prefix group — prefix-mask,
gather + G^eps adjacency build, greedy diversification, output extraction —
each bouncing the (K, K) candidate tile through HBM between stages. This
kernel fuses the whole per-lane round into one ``pallas_call``:

* **grid** — one program per occupied lane (``grid=(B,)``), exactly like
  ``greedy_diversify_batch_pallas``: each program sees its own lane's
  ``(1, 1, W)`` score row and ``(1, W, d)`` gathered candidate tile (3-D
  blocks with a leading lane axis, so every block's last two dims are
  whole array dims).
* **eps-adjacency, one row at a time** — each greedy step needs only the
  picked candidate's row of G^eps, so the kernel computes that row when it
  is needed: one ``(1, d) x (d, W)`` MXU pass against the VMEM tile plus
  the metric transform (the same math as ``pairwise_adjacency``),
  thresholded against the lane's own ``eps``. Neither the ``(W, W)`` Gram
  nor the adjacency exists anywhere, so VMEM holds only the tile.
* **greedy diversification** — the k sequential steps of paper §II-B-2
  (``greedy_diversify.greedy_select``, shared with the standalone greedy
  kernel): masked argmax, ban the picked row's neighbors, repeat. Picks and
  their scores are written once after the loop.

Outputs per lane: ``sel`` (local candidate indices, -1 padded) and
``selsc`` (the picked scores — zero where no pick). The wrapper in
``ops.fused_round_batch`` derives global ids, the pick count, and the
Theorem-2 certificate inputs ``(total, s_K)`` from these plus the masked
score row (kept outside the kernel so both the ref and Pallas paths share
one bit-exact reduction).

VMEM per program is the ``(W, d)`` f32 tile, double-buffered: 8 MiB at
W=2048, d=512. The scoped limit is raised to 100 MiB, so W up to 16384 at
d=96 and 8192 at d=512 compile for a v5e (``tests/test_tpu_compile.py``
compiles W=2048, d=512).

Parity contract: identical greedy decisions to ``ref.fused_round`` given
identical adjacency; an adjacency row is a thresholded row of the Gram
tile whose edges can flip vs the jnp oracle only for pairs within one float
rounding step of ``eps`` (the repo-wide documented near-eps tie caveat) — bit-exact
on tie-free inputs, which is what ``tests/test_fused_round.py`` pins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.greedy_diversify import greedy_select

# the (W, d) tile is double-buffered in VMEM; a v5e core has 128 MiB, and
# the default 16 MiB scoped limit would cap W at 4096 for d = 512
_VMEM_LIMIT = 100 * 2**20


def _kernel(eps_ref, scores_ref, vecs_ref, sel_ref, selsc_ref, *, k: int,
            metric: str):
    x = vecs_ref[0]                                       # (W, d) tile
    eps = eps_ref[0]                                      # (1, 1)
    sq = jnp.sum(x * x, axis=1, keepdims=True).T          # (1, W)
    norm = jnp.sqrt(jnp.maximum(sq, 1e-12))

    def ban_row(j):
        # row j of G^eps: one (1, d) x (d, W) MXU pass against the tile.
        # (Invalid candidates carry -inf scores, so they are banned from
        # step zero; their edges and the diagonal need no masking here.)
        xj = vecs_ref[0, pl.ds(j[0, 0], 1), :]            # (1, d)
        dots = jax.lax.dot_general(
            xj, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)           # (1, W)
        if metric == "ip":
            sims = dots
        elif metric == "cos":
            nj = jnp.sqrt(jnp.maximum(jnp.sum(xj * xj, axis=1,
                                              keepdims=True), 1e-12))
            sims = dots / (nj * norm)
        elif metric == "l2":
            sqj = jnp.sum(xj * xj, axis=1, keepdims=True)
            sims = 1.0 - jnp.sqrt(jnp.maximum(sqj + sq - 2.0 * dots, 0.0))
        else:
            raise ValueError(metric)
        return sims > eps

    picks, psc = greedy_select(scores_ref[0], ban_row, k, sel_ref.shape[2])
    sel_ref[0] = picks
    selsc_ref[0] = psc


@functools.partial(jax.jit, static_argnames=("k", "metric", "interpret"))
def fused_round_batch_pallas(vectors: jnp.ndarray, ids: jnp.ndarray,
                             scores: jnp.ndarray, Ks: jnp.ndarray,
                             eps: jnp.ndarray, k: int, metric: str,
                             interpret: bool = False):
    """Fused round over a lane batch: one grid program per lane.

    ``ids``/``scores`` are the raw ``(B, W)`` queue prefix rows (sorted,
    -1 / -inf sentinels), ``Ks`` int32[B] the per-lane candidate budgets
    (positions >= Ks[b] are masked off — the fused equivalent of the
    engine's ``_mask_prefix`` stage), ``eps`` f32[B] the per-lane
    diversification thresholds. The candidate gather stays outside the
    kernel (one XLA gather feeding the flattened ``(B*W, d)`` row blocks),
    inside the same jit, so the whole round is still a single dispatch.

    Returns ``(sel int32[B, k] local indices -1-padded,
    selsc f32[B, k] picked scores, ids_m int32[B, W] the masked prefix,
    scores_m f32[B, W] the masked scores)`` — callers derive global ids,
    counts and certificate inputs from these (see ``ops.fused_round_batch``).
    """
    B, W = ids.shape
    pos = jnp.arange(W)[None, :]
    keep = pos < Ks[:, None]
    ids_m = jnp.where(keep, ids, -1)
    scores_m = jnp.where(keep, scores, -jnp.inf)
    valid = ids_m >= 0
    s_in = jnp.where(valid, scores_m, -jnp.inf)
    vecs = vectors[jnp.maximum(ids_m, 0)]                 # (B, W, d)

    d = vectors.shape[1]
    Wp = -(-W // 128) * 128
    dp = -(-d // 128) * 128
    kp = -(-k // 128) * 128
    s_p = jnp.full((B, 1, Wp), -jnp.inf, jnp.float32).at[:, 0, :W].set(
        s_in.astype(jnp.float32))
    v_p = jnp.zeros((B, Wp, dp), jnp.float32).at[:, :W, :d].set(
        vecs.astype(jnp.float32))
    eps_p = jnp.asarray(eps, jnp.float32).reshape(B, 1, 1)

    sel, selsc = pl.pallas_call(
        functools.partial(_kernel, k=k, metric=metric),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Wp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Wp, dp), lambda b: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, kp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, kp), lambda b: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, 1, kp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(eps_p, s_p, v_p)
    return sel[:, 0, :k], selsc[:, 0, :k], ids_m, scores_m
