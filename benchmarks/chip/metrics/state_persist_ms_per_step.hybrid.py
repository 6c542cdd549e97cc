"""Host milliseconds per decode step spent persisting the recurrent state:
the program's ``flush.whole`` spans (one a Mamba SSM state or conv window
written whole, with no mask) and the arena's spans of those objects, their
union (``state_spans.persist_ms_per_step``).

Layer: persistence runtime. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import state_spans


def read(ctx):
    return state_spans.persist_ms_per_step(ctx)
