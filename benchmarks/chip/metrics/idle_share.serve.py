"""Share of the traced window in which the chip ran no operation.

Layer: device. Source: the profiler's trace (``xplane.Trace.busy_s``).
Moves: ``serve_tokens_per_s``.
"""


def read(ctx):
    tr = ctx.xtrace
    if tr is None or tr.window_s <= 0 or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
