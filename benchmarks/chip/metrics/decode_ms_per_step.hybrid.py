"""Host milliseconds per decode step in the program's ``serve.decode`` span:
the decode program's dispatch and the wait for its token.

Layer: model step. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def read(ctx):
    return program_spans.ms_per_step(ctx, "serve.decode")
