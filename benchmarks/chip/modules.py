"""Load the benchmark's pieces by name from their files."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path


def load_module(path: Path, name: str):
    """Import ``path`` as module ``name`` (registered, so dataclasses and
    pickling find it); a module already loaded under that name is reused."""
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod
