"""Whole serving step's share of the chip's bf16 peak, for the
latent-attention cell: the reading of ``mfu.serve`` (the driver's
``model_flops`` over the window's host-clock seconds and the peak of the
chips used), where the driver counts ``model_flops`` with
``flops_mla.serve_session_flops``: every prefill and decode token the window
completed, from the shapes, and the routed experts' pairs each session
counted.

Layer: model step. Source: host clock. Moves: ``serve_tokens_per_s``.
"""
from pathlib import Path

from modules import load_module

read = load_module(Path(__file__).with_name("mfu.serve.py"), "bench_metric_mfu_serve").read
