"""LaneBackend: the backend contract the continuous-batching scheduler drives.

The paper's progressive search -> diversify -> verify loop is query-owned and
index-free (Definition 1): a request carries its own ``(k, eps)`` and the
index is never rebuilt between diversification levels. That is exactly what
makes continuous batching backend-neutral — the scheduler only needs a fixed
set of *lanes* it can admit requests into, step, and harvest, regardless of
whether a lane is a slot in the single-host batched engine or a query row
replicated across an N-device mesh.

This module defines that contract:

* ``LaneRequest`` — what a backend needs to serve one request: the query
  vector plus its own ``(k, eps, ef, method, max_K)``. The scheduler's
  ``Request`` subclasses it with timing/bookkeeping fields, so scheduler
  requests flow into ``admit`` unwrapped.
* ``LaneBackend`` — the structural protocol. Implementations:
  ``core.batch_progressive.ProgressiveEngine`` (single-host lanes, methods
  ``pss``/``pgs``/``pds``) and ``sharded_search.engine.ShardedEngine`` (mesh
  lanes, method ``sharded``). ``serve.scheduler.LaneScheduler`` runs
  unmodified against either.

Lifecycle of one lane, as the scheduler drives it::

    free_lanes() -> admit(lane, request) -> step() ... step()
        -> harvest() yields (lane, result) once the lane finishes
        -> recycle(lane) returns the slot to free_lanes()

Drivers must ``harvest()`` after every ``step()`` before the next refill: a
finished lane's result is only retrievable until the lane is reused, and
backends differ on what a not-yet-harvested slot admits (``ShardedEngine``
refuses re-admission until ``recycle``; ``ProgressiveEngine`` additionally
reports finished lanes as free and allows direct re-admission — its
pre-protocol lockstep path — which discards the unharvested result).

``step()`` advances *every* occupied lane one round; lanes are independent,
so admission order can never leak into results (each backend documents and
tests its own parity contract against its per-query reference path).

Stats contract: the ``DiverseResult.stats`` a backend hands back from
``harvest`` must carry *real* per-lane counters — ``expansions`` is the
work actually performed for that request (cumulative under beam resumption,
re-counted restarts under scratch) and ``search_calls`` its progressive
round count. These are not just telemetry: the serving layer's
``ExpansionCostModel`` (``serve.policies``) learns per-``(k, eps, method)``
cost from them, and cost-aware admission (``drr``/``slo_cost``) schedules
by those predictions — a backend reporting fake counters would skew
multi-tenant fairness, not just a dashboard. Pinned for the mesh backend by
``tests/test_sharded_resume.py``
(``test_multiround_beam_fewer_expansions_same_budget``).
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import numpy as np


@dataclasses.dataclass(eq=False)
class LaneRequest:
    """One diverse-search request, the way a backend sees it.

    ``ef`` <= 0 means "backend default" (the sharded backend has no beam-ef
    knob at all — its beam width follows the candidate budget). ``max_K``
    caps the progressive candidate budget (the paper's N/A guard).
    Compares by identity (``eq=False``): ``q`` is an array, so generated
    field equality would be ill-defined, and the scheduler's policy layer
    tracks requests by object identity through its queues.
    """
    q: np.ndarray
    k: int
    eps: float
    ef: int = 0
    method: str = "pss"
    max_K: int | None = None


@runtime_checkable
class LaneBackend(Protocol):
    """Structural protocol — duck-typed, checked by tests via isinstance."""

    num_lanes: int
    max_k: int
    default_ef: int
    #: methods this backend can serve; methods[0] is the scheduler default
    methods: tuple
    #: True when search rounds score a compressed (quantized) corpus — the
    #: exact-rerank stage then guards every certificate (contract 13). The
    #: serving layer's ``ExpansionCostModel`` keys its buckets on this flag
    #: so quantized and float tenants are priced separately.
    compressed: bool

    @property
    def bytes_per_vector(self) -> float:
        """Stored corpus bytes per vector on a device (f32: ``4 * d``;
        quantized: codes + amortized sidecars) — the memory-scaling stat
        surfaced through ``LaneScheduler.latency_stats()`` and the
        ``quant@`` bench-trend points."""
        ...

    @property
    def signature_log(self):
        """The backend's ``SignatureLog`` (compile-budget auditing)."""
        ...

    def free_lanes(self) -> np.ndarray:
        """Indices of lanes a new request may be admitted into."""
        ...

    def active_count(self) -> int:
        """Number of occupied (not yet harvested) lanes."""
        ...

    def admit(self, lane: int, request: LaneRequest) -> None:
        """Hand a free lane to ``request`` (fresh per-lane state; siblings
        untouched)."""
        ...

    def step(self):
        """Advance every occupied lane one progressive round."""
        ...

    def harvest(self) -> list:
        """Drain finished lanes: ``[(lane, DiverseResult), ...]`` for every
        lane that finished since the last harvest. The lane stays reserved
        until ``recycle``."""
        ...

    def recycle(self, lane: int) -> None:
        """Return a harvested lane's slot to the free pool."""
        ...

    def prewarm(self, *, max_capacity: int | None = None, ks: tuple = (),
                widths: tuple = ()):
        """Compile the backend's signature ladder ahead of serving."""
        ...


class RescalableBackend(LaneBackend, Protocol):
    """A ``LaneBackend`` whose capacity can follow traffic (contract 16).

    Implemented by ``ShardedEngine`` (and delegated through
    ``index.mutable.MutableBackend``); the single-host ``ProgressiveEngine``
    is not rescalable. Conformance is declared, not probed: an
    implementation sets ``rescalable = True`` and the scheduler's elastic
    trigger asks ``is_rescalable`` (a wrapper that serves the surface
    through ``__getattr__`` is invisible to a runtime Protocol check on
    Python >= 3.12, which inspects attributes statically). The contract
    mirrors the epoch swap's two-phase shape, but the barrier is
    quiesce-FREE:

    * ``prepare_rescale`` pays the expensive halves (repartitioning the
      corpus, compiling the target mesh's dispatch ladder) ahead of load;
    * ``rescale`` then migrates every in-flight lane's search state to the
      prepared mesh *between rounds* — occupied lanes resume their budget
      ladder on the new topology, nothing drains, and a migrated lane's
      certified result still passes ``theorem2_recheck``. Resharding is a
      capacity knob, never a results knob.
    """

    rescalable: bool

    @property
    def num_shards(self) -> int:
        """Shard count of the mesh currently serving."""
        ...

    def prepare_rescale(self, shards: int, mesh, index=None, *,
                        prewarm: bool = True):
        """Build + prewarm an elastic target mesh ahead of the scale
        event."""
        ...

    def rescale_options(self) -> tuple[int, ...]:
        """Shard counts servable right now (current + prepared targets)."""
        ...

    def rescale(self, shards: int) -> bool:
        """Migrate corpus + in-flight lanes to the prepared ``shards``
        mesh; False if already there."""
        ...


def is_rescalable(backend) -> bool:
    """True when ``backend`` declares the ``RescalableBackend`` surface."""
    return bool(getattr(backend, "rescalable", False))
