"""Host milliseconds per decode step in the program's ``serve.host_copy``
span: the decode cache and the token buffer brought to the host, after the
step's program has finished (``serve.decode`` waits for it).

Layer: server loop. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def read(ctx):
    return program_spans.ms_per_step(ctx, "serve.host_copy")
