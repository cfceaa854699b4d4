"""Pallas TPU kernel: diversity-graph adjacency build (paper Def. 2).

A[i, j] = sim(x_i, x_j) > eps over a candidate tile x[K, d]. The paper builds
G^eps with an O(K^2) loop at query time; here each (B, B) tile is one MXU
Gram-block + threshold, so the build is a single pass over K^2/B^2 tiles.

The kernel emits the *raw* thresholded Gram tile (including the diagonal);
the ops.py wrapper removes the diagonal and applies the validity mask — that
keeps the kernel free of global-index bookkeeping.

Output is int8 (TPU-friendly mask dtype); wrapper casts to bool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(eps_ref, xi_ref, xj_ref, o_ref, *, metric: str):
    xi = xi_ref[...].astype(jnp.float32)   # (B, d)
    xj = xj_ref[...].astype(jnp.float32)   # (B, d)
    eps = eps_ref[0, 0]
    dots = jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    if metric == "ip":
        sims = dots
    elif metric == "cos":
        ni = jnp.sqrt(jnp.maximum(jnp.sum(xi * xi, axis=1, keepdims=True), 1e-12))
        nj = jnp.sqrt(jnp.maximum(jnp.sum(xj * xj, axis=1, keepdims=True), 1e-12))
        sims = dots / (ni * nj.T)
    elif metric == "l2":
        i2 = jnp.sum(xi * xi, axis=1, keepdims=True)
        j2 = jnp.sum(xj * xj, axis=1, keepdims=True)
        d2 = jnp.maximum(i2 + j2.T - 2.0 * dots, 0.0)
        sims = 1.0 - jnp.sqrt(d2)
    else:
        raise ValueError(metric)
    o_ref[...] = (sims > eps).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("metric", "block", "interpret"))
def pairwise_adjacency_pallas(x: jnp.ndarray, eps, metric: str,
                              block: int = 128,
                              interpret: bool = False) -> jnp.ndarray:
    """Raw thresholded Gram matrix (int8[K, K]) — see module docstring."""
    k, d = x.shape
    kp = -(-k // block) * block
    dp = -(-d // 128) * 128
    x_p = jnp.zeros((kp, dp), x.dtype).at[:k, :d].set(x)
    eps_arr = jnp.asarray(eps, jnp.float32).reshape(1, 1)
    grid = (kp // block, kp // block)
    out = pl.pallas_call(
        functools.partial(_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((block, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((kp, kp), jnp.int8),
        interpret=interpret,
    )(eps_arr, x_p, x_p)
    return out[:k, :k]
