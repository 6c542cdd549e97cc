"""The program's spans and counters, recorded in the JAX profiler's trace.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler traces, it is
held in memory with the rest of the trace and written at ``stop_trace``, on
the clock of the device's own events; the profiler's trace is the only
exporter. A counter is a stat on the span of the work it counts
(``nbytes``, ``dirty_blocks`` ...); summing counters over a window is the
reader's job. Nesting on a thread gives a span's parent, and the
``session`` stat of ``serve.session`` ties a decode session's spans
together.

There is no switch: with no profiler tracing, a span costs about a
microsecond. Work done only to fill a stat is guarded by :func:`tracing`.

    with span("arena.write", object=name) as s:
        ...
        s.add(nbytes=n)
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


class span(TraceAnnotation):
    """``span(name, **stats)``: a trace annotation whose ``add(**stats)``
    attaches stats known only inside the block."""

    add = TraceAnnotation.set_metadata


#: whether a profiler is tracing, so that stats are recorded
tracing = TraceAnnotation.is_enabled
