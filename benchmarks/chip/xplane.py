"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device events sit on planes named ``/device:TPU:<n>``; on the ``XLA Ops``
line each event is one operation run on that chip. Host spans (the
benchmark's ``TraceAnnotation``s) sit on the host plane's thread lines, on
the same clock.

Busy time is the union of a chip's operation intervals inside the window;
idle share is one minus busy over the window. Only the process that holds
the chip can trace it.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the host span that marks the measured window in a traced run
WINDOW_SPAN = "bench.window"

Interval = Tuple[float, float]  # (start_ns, end_ns)


def union_length(intervals: Iterable[Interval]) -> float:
    """Total length covered by possibly overlapping intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclass
class DeviceOp:
    name: str
    module: str
    start_ns: float
    end_ns: float


@dataclass
class Trace:
    """What the benchmark keeps of one traced window."""

    window: Interval
    #: per chip (plane name): the operations run inside the window
    ops: Dict[str, List[DeviceOp]]
    #: per chip: the programs run (``name`` is the jitted function's name)
    modules: Dict[str, List[DeviceOp]] = field(default_factory=dict)
    #: host spans: (name, start_ns, end_ns)
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        per_chip = [union_length(clip([(o.start_ns, o.end_ns) for o in ops], *self.window))
                    for ops in self.ops.values()]
        return sum(per_chip) / len(per_chip) * 1e-9

    def module_seconds(self, match) -> Tuple[float, int]:
        """Device seconds and run count of the programs whose name satisfies
        ``match``, inside the window, averaged over chips."""
        if not self.modules:
            return 0.0, 0
        secs, count = 0.0, 0
        for mods in self.modules.values():
            sel = clip([(m.start_ns, m.end_ns) for m in mods if match(m.name)], *self.window)
            secs += union_length(sel) * 1e-9
            count += len(sel)
        n = len(self.modules)
        return secs / n, count // n

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` device operations that took most time (seconds, summed
        over the window, averaged over chips), named ``program/op``."""
        tot: Dict[str, float] = defaultdict(float)
        for ops in self.ops.values():
            for o in ops:
                s, e = max(o.start_ns, self.window[0]), min(o.end_ns, self.window[1])
                if e > s:
                    tot[f"{o.module}/{o.name}" if o.module else o.name] += (e - s) * 1e-9
        k = max(len(self.ops), 1)
        return [[name, secs / k] for name, secs in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, names: Sequence[str] = ()) -> List[List]:
        """The ``n`` longest stretches of the window in which the first chip
        ran nothing, each named by the innermost of the host spans ``names``
        around its middle (``host`` where none is)."""
        if not self.ops:
            return []
        ops = next(iter(self.ops.values()))
        busy = merged(clip([(o.start_ns, o.end_ns) for o in ops], *self.window))
        gaps, t = [], self.window[0]
        for s, e in busy + [(self.window[1], self.window[1])]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            around = [(he - hs, name) for name, hs, he in self.host_spans
                      if hs <= mid <= he and name in names]
            out.append([min(around)[1] if around else "host", (e - s) * 1e-9])
        return out


def _program(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def _in_programs(ops: List[DeviceOp], mods: List[DeviceOp]) -> None:
    """Name each operation's program from the program run around it."""
    mods = sorted(mods, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    for o in ops:
        i = bisect.bisect_right(starts, o.start_ns) - 1
        if i >= 0 and o.end_ns <= mods[i].end_ns:
            o.module = mods[i].name


def _stat(ev, key: str) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str, device_prefix: Optional[str] = "/device:TPU:",
         op_line: str = "XLA Ops", module_line: str = "XLA Modules") -> Trace:
    """Read the newest trace under ``trace_dir``.

    Device operations are the events of ``op_line``, and programs those of
    ``module_line``, on planes whose name starts with ``device_prefix``.
    With ``device_prefix=None`` (a CPU run, where XLA runs on host threads)
    the operations are the host events that carry an ``hlo_op`` stat, and
    each is its own program run, named by its ``hlo_module`` stat.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    host_spans: List[Tuple[str, float, float]] = []
    ops: Dict[str, List[DeviceOp]] = {}
    modules: Dict[str, List[DeviceOp]] = {}
    for plane in pd.planes:
        on_device = device_prefix is not None and plane.name.startswith(device_prefix)
        if on_device:
            def events(line_name):
                return [DeviceOp(_op(ev.name), "", ev.start_ns, ev.start_ns + ev.duration_ns)
                        for line in plane.lines if line.name == line_name
                        for ev in line.events]

            ops[plane.name] = events(op_line)
            modules[plane.name] = events(module_line)
            for m in modules[plane.name]:
                m.name = _program(m.name)
            _in_programs(ops[plane.name], modules[plane.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if device_prefix is None and _stat(ev, "hlo_op") is not None:
                        program = _stat(ev, "hlo_module") or ""
                        end = ev.start_ns + ev.duration_ns
                        ops.setdefault(plane.name, []).append(
                            DeviceOp(ev.name, program, ev.start_ns, end))
                        modules.setdefault(plane.name, []).append(
                            DeviceOp(program, "", ev.start_ns, end))
                    else:
                        host_spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return Trace(window=windows[-1], ops=ops, modules=modules, host_spans=host_spans)


def describe(trace_dir: str) -> List[str]:
    """One line per plane and line with its event count: for looking at a
    trace by hand before reading it in code."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:6]
            out.append(f"{plane.name} | {line.name} | events={len(evs)} | e.g. {names}")
    return out
