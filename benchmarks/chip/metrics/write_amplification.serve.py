"""Bytes the arena's files received for the K/V cache objects over the
bytes their flushes found dirty: ``arena.write``'s ``nbytes`` summed, over
``flush.mask``'s ``dirty_blocks`` times ``block_bytes`` summed. 1 where a
flush writes only dirty blocks; the arena rewrites a whole file for one
dirty block.

Layer: persistence runtime. Source: program counter. Moves: ``serve_tokens_per_s``.
"""
import program_spans


def kv(span):
    obj = str(span.stats.get("object", ""))
    return obj.startswith("cache/") and obj.rsplit("/", 1)[-1] in ("k", "v")


def read(ctx):
    written = program_spans.stat_sum(ctx, "arena.write", "nbytes", kv)
    dirty = sum(s.stats["dirty_blocks"] * s.stats["block_bytes"]
                for s in program_spans.spans(ctx)
                if s.name == "flush.mask" and "dirty_blocks" in s.stats and kv(s))
    if not written or not dirty:
        return None
    return written / dirty
