"""Similarity spaces from the paper (§IV-A, Eqs. 5-7).

The paper defines three similarity functions — higher is more similar:

  sim_L2(u, v)  = 1 - ||u - v||_2                       (Deep1M)
  sim_ip(u, v)  = <u, v>                                 (Txt2img)
  sim_cos(u, v) = <u, v> / (||u|| * ||v||)               (LAION-art)

All public entry points are pure jnp and jit/vmap-safe. The Pallas kernel in
``repro.kernels.batch_similarity`` implements the same math for the hot path;
these functions double as its oracle.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

Metric = Literal["l2", "ip", "cos"]

METRICS: tuple[str, ...] = ("l2", "ip", "cos")

_EPS = 1e-12


def _l2_sim(dots: jnp.ndarray, u_sq: jnp.ndarray, v_sq: jnp.ndarray) -> jnp.ndarray:
    # sim = 1 - sqrt(||u||^2 - 2<u,v> + ||v||^2); clamp for numerical safety.
    d2 = jnp.maximum(u_sq + v_sq - 2.0 * dots, 0.0)
    return 1.0 - jnp.sqrt(d2)


def query_sim(q: jnp.ndarray, x: jnp.ndarray, metric: Metric) -> jnp.ndarray:
    """Similarity of one query ``q``[d] against rows of ``x``[..., d].

    The dot products are a multiply+reduce rather than a matvec: XLA's gemv
    changes accumulation order under vmap, while the last-axis reduce is
    bitwise batch-invariant — the batched progressive engine relies on this
    for exact per-lane parity with the per-query drivers.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    dots = jnp.sum(x * q, axis=-1)
    if metric == "ip":
        return dots
    if metric == "cos":
        qn = jnp.sqrt(jnp.maximum(jnp.sum(q * q), _EPS))
        xn = jnp.sqrt(jnp.maximum(jnp.sum(x * x, axis=-1), _EPS))
        return dots / (qn * xn)
    if metric == "l2":
        return _l2_sim(dots, jnp.sum(q * q), jnp.sum(x * x, axis=-1))
    raise ValueError(f"unknown metric {metric!r}")


def pairwise_sim(x: jnp.ndarray, y: jnp.ndarray, metric: Metric) -> jnp.ndarray:
    """Pairwise similarity matrix between rows of ``x``[m, d] and ``y``[n, d]."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    # full f32 passes: the TPU's default f32 matmul rounds operands to bf16,
    # which moves near-eps edges and Theorem-2 values off the exact answer
    dots = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return dots
    if metric == "cos":
        xn = jnp.sqrt(jnp.maximum(jnp.sum(x * x, axis=-1), _EPS))
        yn = jnp.sqrt(jnp.maximum(jnp.sum(y * y, axis=-1), _EPS))
        return dots / (xn[:, None] * yn[None, :])
    if metric == "l2":
        return _l2_sim(
            dots,
            jnp.sum(x * x, axis=-1)[:, None],
            jnp.sum(y * y, axis=-1)[None, :],
        )
    raise ValueError(f"unknown metric {metric!r}")


def self_sim(x: jnp.ndarray, metric: Metric) -> jnp.ndarray:
    """Pairwise similarity among rows of ``x``[n, d] (diagonal = self-sim)."""
    return pairwise_sim(x, x, metric)


@functools.partial(jax.jit, static_argnames=("metric",))
def query_sim_jit(q: jnp.ndarray, x: jnp.ndarray, metric: Metric) -> jnp.ndarray:
    return query_sim(q, x, metric)


def sim_one(u: jnp.ndarray, v: jnp.ndarray, metric: Metric) -> jnp.ndarray:
    """Scalar similarity between two vectors."""
    return query_sim(u, v[None, :], metric)[0]
