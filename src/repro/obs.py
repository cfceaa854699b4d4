"""Spans on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: a host event
in the profiler's trace, on the same clock as the device planes, so a trace
can say what the host was doing while the device sat idle. With no profile
being recorded it is inert (a TraceMe that records nothing, about a
microsecond), so the serving path keeps its spans on and needs no switch.
Nothing is kept in Python: the trace is the only record.

Names are ``<layer>.<what>`` with the layers of the serving stack:
``sched.`` (``serve.scheduler``), ``engine.`` (``core.batch_progressive``:
steps, search bursts, growth, host syncs), ``diversify.`` (the PGS round)
and ``verify.`` (the PDS and PSS rounds). Metadata values may not contain
``,``, ``=`` or ``#`` (TraceMe's encoding); lists go as space-separated
text (``id_text``). Metadata known only at the end of a span is added with the
returned object's ``set_metadata``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    return TraceAnnotation(name, **meta)


def id_text(values) -> str:
    """A list of ints as one metadata value."""
    return " ".join(str(int(v)) for v in values)
