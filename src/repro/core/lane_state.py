"""Lane-state layer: fixed-shape per-lane search state for the batched engine.

A *lane* is one slot of the batched progressive engine: a fixed-capacity
candidate queue, a visited set, and a step counter — ``beam_search.SearchState``
with a leading lane axis on every leaf. This module is the bottom of the
serving stack's lane-state / backend / scheduler split: the pure-function
layer under ``core.batch_progressive.ProgressiveEngine`` (the single-host
``core.backend.LaneBackend`` implementation; the mesh-sharded
``sharded_search.engine.ShardedEngine`` keeps its per-lane budgets host-side
instead, because its device state lives sharded across the mesh). It owns
the shape/sentinel conventions and the three lane-slot operations the
engine and the serving scheduler build on:

* ``extract_lane`` / ``inject_lane`` — move one lane between the batched
  pytree and a solo ``SearchState`` (the parity bridge to the per-query
  drivers: an extracted lane *is* a solo driver state).
* ``recycle_lane`` — re-initialize one lane slot for a **new query** in
  place: the slot gets exactly the state ``beam_search.init_state`` would
  produce at the batch's physical capacity, sibling lanes are untouched, and
  the lane index is traced so re-admitting different lanes never recompiles.
  This is what lets the scheduler run continuous batching: a certified
  lane's slot is handed to the next queued request without disturbing the
  in-flight lanes around it.
* ``pad_queue`` / ``pad_lanes`` / ``slice_queue_capacity`` — physical
  capacity moves. All lanes share one physical queue width; each lane's
  *logical* capacity is enforced by the engine's clamp, so padding with the
  empty-slot sentinel (id=-1, score=-inf, stable=True) never changes lane
  semantics.

Everything here is jit-friendly and bit-deterministic; host-side policy
(which lane to recycle, when to grow) lives in the engine and scheduler.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import beam_search as bs
from repro.core import queue as qmod
from repro.core.graph import FlatGraph


class LaneCertificate(NamedTuple):
    """Per-lane Theorem-2 verification snapshot (host-side, one lane)."""
    min_value: float     # Theorem-2 minValue over the lane's candidates
    s_K: float           # K-th candidate score the bound is checked against
    certified: bool      # min_value > s_K (global optimality under the paper)
    complete: bool       # div-A* ran to completion within its budget


# ------------------------------------------------------------- shape ops ----

def pad_queue(queue: qmod.Queue, pad: int) -> qmod.Queue:
    """Extend a queue's last axis with empty-slot sentinels (id=-1,
    score=-inf, stable=True) — the one place the sentinel convention for
    padding lives."""
    if pad == 0:
        return queue
    spec = [(0, 0)] * (queue.ids.ndim - 1) + [(0, pad)]
    return qmod.Queue(
        ids=jnp.pad(queue.ids, spec, constant_values=-1),
        scores=jnp.pad(queue.scores, spec, constant_values=-np.inf),
        stable=jnp.pad(queue.stable, spec, constant_values=True),
    )


def physical_capacity(state: bs.SearchState) -> int:
    return int(state.queue.ids.shape[-1])


def pad_lanes(state: bs.SearchState, new_capacity: int) -> bs.SearchState:
    """Grow the shared physical queue width (logical capacities unchanged):
    one dispatch where it grows, none where it does not."""
    pad = new_capacity - physical_capacity(state)
    if pad <= 0:
        return state
    return _pad_lanes(state, pad)


@functools.partial(jax.jit, static_argnames=("pad",))
def _pad_lanes(state: bs.SearchState, pad: int) -> bs.SearchState:
    return bs.SearchState(pad_queue(state.queue, pad), state.visited,
                          state.steps)


def slice_queue_capacity(state: bs.SearchState, cap: int) -> bs.SearchState:
    """View of the lanes at queue width ``cap`` (<= physical capacity).

    Safe whenever every lane's logical capacity is <= ``cap``: slots past
    the logical capacity hold only the padding sentinel.
    """
    q = state.queue
    return bs.SearchState(
        qmod.Queue(q.ids[..., :cap], q.scores[..., :cap], q.stable[..., :cap]),
        state.visited, state.steps)


# ------------------------------------------------------------- lane init ----

@functools.partial(jax.jit, static_argnames=("capacity",))
def init_lanes(graph: FlatGraph, qs: jnp.ndarray,
               capacity: int) -> bs.SearchState:
    """Batched ``beam_search.init_state`` over a query batch."""
    return jax.vmap(lambda q: bs.init_state(graph, q, capacity))(qs)


# -------------------------------------------------------- lane slot ops ----

def extract_lane(state: bs.SearchState, lane: int) -> bs.SearchState:
    """One lane's state as a solo ``SearchState`` (bit-identical leaves)."""
    return jax.tree_util.tree_map(lambda a: a[lane], state)


def inject_lane(state: bs.SearchState, lane: int,
                lane_state: bs.SearchState) -> bs.SearchState:
    """Replace one lane's state; sibling lanes are untouched."""
    return jax.tree_util.tree_map(lambda b, s: b.at[lane].set(s),
                                  state, lane_state)


@jax.jit
def _recycle(graph: FlatGraph, state: bs.SearchState, lane: jnp.ndarray,
             q: jnp.ndarray) -> bs.SearchState:
    # physical capacity comes from the state's shape -> static under jit;
    # the lane index is traced, so recycling lane 0 vs lane 7 shares one
    # compilation.
    fresh = bs.init_state(graph, q, physical_capacity(state))
    return jax.tree_util.tree_map(lambda b, s: b.at[lane].set(s),
                                  state, fresh)


def recycle_lane(graph: FlatGraph, state: bs.SearchState, lane: int,
                 q) -> bs.SearchState:
    """Re-initialize lane ``lane`` for a new query ``q`` in place.

    The slot's queue/visited/steps become exactly what a fresh solo driver
    would start from (entry point seeded after HNSW descent), at the batch's
    current physical capacity; all other lanes keep their bits. One compile
    per (lane count, physical capacity) — never per lane index or query.
    """
    return _recycle(graph, state, jnp.int32(lane),
                    jnp.asarray(q, jnp.float32))


def select_lanes(state: bs.SearchState, lanes) -> bs.SearchState:
    """Gather a sub-batch of lanes (used for bucketed rebuilds)."""
    idx = jnp.asarray(lanes)
    return jax.tree_util.tree_map(lambda a: a[idx], state)
