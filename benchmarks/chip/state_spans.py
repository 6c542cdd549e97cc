"""The program's ``flush.whole`` spans, as a traced run recorded them.

``program_spans`` keeps the spans it names; ``flush.whole``, the span of
one object that a flush writes whole with no mask (a model's recurrent
state), is read here, in the same way: from the run's ``.xplane.pb``, once
per run, inside the ``bench.window`` span, with its stats (``object``,
``nbytes``, ``blocks``), kept on ``ctx``. A program without the span leaves
the list empty, and its readers then return ``None``.
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Optional

import program_spans
import xplane

NAME = "flush.whole"


def read(trace_dir: Path) -> List[program_spans.Span]:
    from jax.profiler import ProfileData

    try:
        pd = ProfileData.from_file(xplane.find_xplane(str(trace_dir)))
    except FileNotFoundError:
        return []
    window, found = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == xplane.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name == NAME:
                    found.append(program_spans.Span(
                        ev.name, f"{plane.name}/{line.name}", ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats)))
    if window is None:
        return []
    return [s for s in found if window[0] <= s.start_ns and s.end_ns <= window[1]]


def spans(ctx) -> List[program_spans.Span]:
    if getattr(ctx, "whole_spans", None) is None:
        ctx.whole_spans = read(Path(ctx.workdir) / "trace")
    return ctx.whole_spans


def persist_ms_per_step(ctx) -> Optional[float]:
    """Milliseconds per decode step in which the host persisted an object
    written whole: the union of its ``flush.whole`` spans and the arena's
    spans of the same objects (``arena.write``, ``arena.fsync``,
    ``arena.rename``), so that nested spans count once."""
    whole = spans(ctx)
    steps = ctx.counters.get("decode_steps", 0)
    if not whole or not steps:
        return None
    objects = {str(s.stats.get("object")) for s in whole}
    arena = [s for s in program_spans.spans(ctx)
             if s.name.startswith("arena.") and str(s.stats.get("object")) in objects]
    secs = xplane.union_length([(s.start_ns, s.end_ns) for s in whole + arena]) * 1e-9
    return 1000.0 * secs / steps
