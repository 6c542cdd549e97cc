"""Host spans that the benchmark wraps around calls into the program.

Installed only in a traced run. Each wrapped call is timed on the host
clock and written into the profiler's trace as a ``TraceAnnotation`` of
the span's name, so idle gaps on the device can be named by what the host
was doing. A target that no longer exists is reported and skipped: the
metrics that read it are then absent, and the run goes on.
"""
from __future__ import annotations

import functools
import importlib
import sys
import time
from collections import defaultdict
from typing import Callable, Dict, List, Tuple


class Spans:
    def __init__(self):
        self.durations: Dict[str, List[float]] = defaultdict(list)
        self.missing: List[str] = []
        self._undo: List[Callable[[], None]] = []

    def wrap(self, name: str, target: str) -> bool:
        """Wrap ``target``, ``"package.module:Attr.attr"``, in span ``name``."""
        import jax

        mod_name, _, qual = target.partition(":")
        try:
            owner = importlib.import_module(mod_name)
            *path, attr = qual.split(".")
            for p in path:
                owner = getattr(owner, p)
            fn = owner.__dict__[attr] if isinstance(owner, type) else getattr(owner, attr)
        except (ImportError, AttributeError, KeyError) as e:
            self.missing.append(name)
            print(f"[spans] {name}: target {target} not found ({e!r}); "
                  "metrics that read it are left out", file=sys.stderr)
            return False
        if isinstance(fn, (staticmethod, classmethod)):
            self.missing.append(name)
            print(f"[spans] {name}: {target} is not a plain function", file=sys.stderr)
            return False
        durations = self.durations[name]

        @functools.wraps(fn)
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                try:
                    return fn(*args, **kwargs)
                finally:
                    durations.append(time.perf_counter() - t0)

        setattr(owner, attr, timed)
        self._undo.append(lambda: setattr(owner, attr, fn))
        return True

    def remove(self) -> None:
        while self._undo:
            self._undo.pop()()

    def total(self, name: str) -> Tuple[float, int]:
        d = self.durations.get(name, [])
        return sum(d), len(d)
