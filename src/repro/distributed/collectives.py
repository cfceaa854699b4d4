"""Compute/communication overlap primitives.

``ring_allgather_matmul``: the "collective matmul" (overlap class used by
Megatron/MaxText): computing y = all_gather(x) @ w_local as P ring steps.
Each step multiplies the currently-resident x shard into its row-block of
the output while the next shard travels one ICI hop — on TPU the permute
hides behind the MXU work, removing the serial all-gather from the critical
path. Used by the §Perf collective-bound iteration; the one-shot
``allgather_matmul`` is the baseline it replaces.

Both run inside shard_map with ``axis`` sharding x's leading dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def allgather_matmul(x_shard, w_local, axis: str):
    """Baseline: y = all_gather(x) @ w_local, serial collective."""
    x_full = jax.lax.all_gather(x_shard, axis, axis=0, tiled=True)
    return jnp.dot(x_full, w_local, preferred_element_type=jnp.float32)


def ring_allgather_matmul(x_shard, w_local, axis: str):
    """Ring-overlapped y = all_gather(x) @ w_local.

    x_shard [Bs, K] (leading dim sharded over ``axis``), w_local [K, N].
    Returns y [Bs*P, K->N] identical to the baseline (up to fp reorder).
    """
    p = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    bs = x_shard.shape[0]
    # receive from the next rank each step: after t hops we hold shard me+t
    perm = [(i, (i - 1) % p) for i in range(p)]
    y0 = jnp.zeros((bs * p,) + (w_local.shape[-1],), jnp.float32)

    def step(carry, t):
        y, xs = carry
        src = (me + t) % p
        block = jnp.dot(xs, w_local, preferred_element_type=jnp.float32)
        y = jax.lax.dynamic_update_slice(y, block, (src * bs, 0))
        xs = jax.lax.ppermute(xs, axis, perm)
        return (y, xs), None

    (y, _), _ = jax.lax.scan(step, (y0, x_shard), jnp.arange(p))
    return y
