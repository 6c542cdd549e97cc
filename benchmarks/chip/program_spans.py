"""The program's own spans and counters, as a traced run recorded them.

The program marks its work with ``jax.profiler.TraceAnnotation``s that
carry stats (``repro.telemetry``). ``xplane.Trace.host_spans`` keeps names
and times only, so this reads the run's ``.xplane.pb`` again, once per run
while the metric readers run (the trace lies in ``<workdir>/trace`` until
they finish), and keeps the program's spans inside the ``bench.window``
span, with their stats, on ``ctx``. A program without these spans leaves
the list empty, and each reader then returns ``None``.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import xplane

#: the program's span names (``repro.telemetry`` users)
NAMES = frozenset({
    "serve.session", "serve.setup", "serve.prefill", "serve.decode", "serve.host_copy",
    "flush", "flush.stage", "flush.mask",
    "arena.mix", "arena.write", "arena.fsync", "arena.rename", "arena.manifest",
})


@dataclass(frozen=True)
class Span:
    name: str
    thread: str
    start_ns: float
    end_ns: float
    stats: Dict[str, object]


def read(trace_dir: Path) -> List[Span]:
    """The program's spans inside the last ``bench.window`` of the newest
    trace under ``trace_dir``; empty where there is no trace or window."""
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
                elif ev.name in NAMES:
                    found.append(Span(ev.name, f"{plane.name}/{line.name}", ev.start_ns,
                                      ev.start_ns + ev.duration_ns, dict(ev.stats)))
    if window is None:
        return []
    return [s for s in found if window[0] <= s.start_ns and s.end_ns <= window[1]]


def spans(ctx) -> List[Span]:
    if getattr(ctx, "program_spans", None) is None:
        ctx.program_spans = read(Path(ctx.workdir) / "trace")
    return ctx.program_spans


def seconds(ctx, *names: str) -> Optional[float]:
    """Seconds in the spans of these names, summed; ``None`` if none ran."""
    found = [s for s in spans(ctx) if s.name in names]
    return sum(s.end_ns - s.start_ns for s in found) * 1e-9 if found else None


def stat_sum(ctx, name: str, stat: str,
             where: Callable[[Span], bool] = lambda s: True) -> Optional[float]:
    """A counter summed over the spans ``name`` that carry it and satisfy
    ``where``; ``None`` if none does."""
    values = [s.stats[stat] for s in spans(ctx)
              if s.name == name and stat in s.stats and where(s)]
    return sum(values) if values else None


def ms_per_step(ctx, *names: str) -> Optional[float]:
    """Milliseconds in the spans of these names per decode step of the window."""
    secs = seconds(ctx, *names)
    steps = ctx.counters.get("decode_steps", 0)
    return None if secs is None or not steps else 1000.0 * secs / steps


def _idle(ctx) -> List[xplane.Interval]:
    """The first chip's idle stretches inside the window."""
    tr = ctx.xtrace
    ops = next(iter(tr.ops.values()))
    busy = xplane.merged(xplane.clip([(o.start_ns, o.end_ns) for o in ops], *tr.window))
    gaps, t = [], tr.window[0]
    for s, e in busy + [(tr.window[1], tr.window[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    return gaps


def idle_covered_share(ctx) -> Optional[float]:
    """Share of the chip's idle window time that lies inside at least one
    program span: how much of the host's time the spans can name."""
    if ctx.xtrace is None or not ctx.xtrace.ops or not spans(ctx):
        return None
    idle = _idle(ctx)
    total = sum(e - s for s, e in idle)
    cover = xplane.merged((s.start_ns, s.end_ns) for s in spans(ctx))
    covered = sum(xplane.union_length(xplane.clip(cover, s, e)) for s, e in idle)
    return covered / total if total else None


def idle_gaps(ctx, n: int = 5) -> List[List]:
    """The ``n`` longest idle stretches of the chip in the window, each named
    by the innermost program span around its middle (``host`` where none is)."""
    if ctx.xtrace is None or not ctx.xtrace.ops:
        return []
    out = []
    for s, e in sorted(_idle(ctx), key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        around = [(p.end_ns - p.start_ns, p.name) for p in spans(ctx)
                  if p.start_ns <= mid <= p.end_ns]
        out.append([min(around)[1] if around else "host", (e - s) * 1e-9])
    return out
