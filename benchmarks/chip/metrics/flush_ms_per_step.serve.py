"""Host milliseconds spent in ``EasyCrashManager.maybe_flush`` per decode
step of the window (the benchmark's own span around the call).

Layer: persistence runtime. Source: host clock. Moves: ``serve_tokens_per_s``.
"""


def read(ctx):
    if ctx.spans is None or "manager.maybe_flush" in ctx.spans.missing:
        return None
    secs, calls = ctx.spans.total("manager.maybe_flush")
    steps = ctx.counters.get("decode_steps", 0)
    if not calls or not steps:
        return None
    return 1000.0 * secs / steps
