"""Share of the traced window in which the chip ran no operation: the
reading of ``idle_share.serve``, for the latent-attention cell.

Layer: device. Source: the profiler's trace (``xplane.Trace.busy_s``).
Moves: ``serve_tokens_per_s``.
"""
from pathlib import Path

from modules import load_module

read = load_module(Path(__file__).with_name("idle_share.serve.py"),
                   "bench_metric_idle_share_serve").read
