"""Operations and bytes the benchmark's yardsticks divide by.

Counted from shapes, never from the program: a later change to the program
cannot change them.
"""
from __future__ import annotations

from typing import Dict


def dense_decoder_token_flops(cfg: Dict, context: int, logits: bool) -> float:
    """Forward FLOPs of one token of a dense decoder that attends to
    ``context`` positions (itself included), with or without the output
    head. Multiply-adds count two; norms, rotary and softmax are left out."""
    d = cfg["hidden_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    ff = cfg["intermediate_size"]
    per_layer = 2 * d * (2 * hd + 2 * kvd)      # q, o and k, v projections
    per_layer += 3 * 2 * d * ff                  # gate, up, down
    per_layer += 2 * 2 * context * hd            # q.k and p.v
    total = cfg["num_hidden_layers"] * per_layer
    if logits:
        total += 2 * d * cfg["vocab_size"]
    return float(total)


def serve_session_flops(cfg: Dict, rows: int, prompt_len: int, decode_steps: int) -> float:
    """Forward FLOPs a serving session needs: a causal prefill of ``rows``
    prompts of ``prompt_len`` with logits for the last position only, then
    ``decode_steps`` single-token steps, each with logits, the ``i``-th
    attending to ``prompt_len + i + 1`` positions."""
    prefill = sum(dense_decoder_token_flops(cfg, p + 1, logits=False) for p in range(prompt_len))
    prefill += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    decode = sum(dense_decoder_token_flops(cfg, prompt_len + i + 1, logits=True)
                 for i in range(decode_steps))
    return rows * (prefill + decode)


def delta_mask_bytes(nbytes: int, block_bytes: int = 64) -> int:
    """Bytes a dirty-block mask over two ``nbytes`` objects must move: both
    objects read once, one int32 flag per block written. From the objects'
    sizes, not from whatever tiles or padding an implementation uses."""
    blocks = -(-nbytes // block_bytes)
    return 2 * nbytes + 4 * blocks
