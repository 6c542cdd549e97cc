"""The harness's own tests run on the CPU, with no TPU library loaded, and
keep their compiled programs out of the checkout's cache."""
import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", tempfile.mkdtemp(prefix="bench-tests-jax-"))

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))
