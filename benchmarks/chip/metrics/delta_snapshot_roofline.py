"""The ``delta_snapshot`` dirty-block mask's share of its roofline.

Bytes: both compared objects read once and one int32 flag per 64-byte block
written, summed over the window's masked objects (``flops.delta_mask_bytes``,
from the objects' sizes). Time: device seconds of the runs of the mask's
program (``jit_dirty_block_mask``) on the trace's ``XLA Modules`` line,
its packing of bytes into words included. The mask does no arithmetic
worth counting, so HBM bandwidth bounds it.
Layer: delta kernel. Source: device trace. Moves: ``serve_tokens_per_s``.
"""

PROGRAM = "jit_dirty_block_mask"


def read(ctx):
    tr = ctx.xtrace
    nbytes = ctx.counters.get("delta_mask_bytes", 0)
    bw = ctx.peaks.get("hbm_bytes_per_s")
    if tr is None or not nbytes or not bw:
        return None
    secs, events = tr.module_seconds(lambda name: name == PROGRAM)
    if not events or secs <= 0:
        return None
    return 100.0 * (nbytes / bw) / secs
