"""Host milliseconds per decode step in the arena's file spans: writing each
backing file (``arena.write``), its fsync (``arena.fsync``), the durable
rename with the directory's fsync (``arena.rename``) and the manifest
(``arena.manifest``).

Layer: persistence runtime. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def read(ctx):
    if program_spans.seconds(ctx, "arena.write") is None:
        return None
    return program_spans.ms_per_step(ctx, "arena.write", "arena.fsync", "arena.rename",
                                     "arena.manifest")
