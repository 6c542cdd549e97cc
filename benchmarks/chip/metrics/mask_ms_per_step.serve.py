"""Host milliseconds per decode step in the program's ``flush.mask`` spans:
each flushed object's dirty-block mask, with the ``delta_snapshot``
kernel's transfers to the chip, the kernel and the mask's way back.

Layer: delta kernel. Source: program span. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def read(ctx):
    return program_spans.ms_per_step(ctx, "flush.mask")
