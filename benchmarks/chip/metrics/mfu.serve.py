"""Whole serving step's share of the chip's bf16 peak.

Forward FLOPs of every prefill and decode token the window completed,
counted from the configuration's shapes (``flops.serve_session_flops``),
over the window's host-clock seconds and the peak of the chips used.
Layer: model step. Source: host clock. Moves: ``serve_tokens_per_s``.
"""


def read(ctx):
    flops = ctx.counters.get("model_flops", 0.0)
    peak = ctx.peaks.get("bf16_flops_per_s")
    if not flops or not peak or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * peak * ctx.device["count"])
