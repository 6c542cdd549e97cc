"""Host milliseconds per session in the program's ``serve.prefill`` span:
the prefill program (where the chunked SSD of every Mamba layer runs), the
splice into the decode cache and the first token.

Layer: model step. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def read(ctx):
    secs = program_spans.seconds(ctx, "serve.prefill")
    sessions = ctx.counters.get("sessions", 0)
    return None if secs is None or not sessions else 1000.0 * secs / sessions
