"""Host milliseconds per decode step in the program's ``serve.host_copy``
span, for the latent-attention cell: the token buffer brought to the host
on each flush step and at a session's end (the latent cache crosses in
``flush.fetch``, inside ``flush.whole``, not here).

Layer: server loop. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def read(ctx):
    return program_spans.ms_per_step(ctx, "serve.host_copy")
