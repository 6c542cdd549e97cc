"""Host milliseconds per decode step in the program's ``serve.host_copy``
span: on flush steps the decode cache (K/V, SSM state, conv window) and the
token buffer brought to the host, and the served buffer at a session's end.

Layer: server loop. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def read(ctx):
    return program_spans.ms_per_step(ctx, "serve.host_copy")
