"""Host milliseconds per decode step spent persisting the decode cache, all
of it written whole in ``full`` mode: the program's ``flush.whole`` spans
(one per object: each layer group's ``c_kv`` and ``k_pe``, the counters)
and the arena's spans of those objects, their union
(``state_spans.persist_ms_per_step``).

Layer: persistence runtime. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import state_spans


def read(ctx):
    return state_spans.persist_ms_per_step(ctx)
