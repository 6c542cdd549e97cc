"""Operations of a Mamba-2 + attention hybrid decoder (Granite-4.0-H), from
its configuration's shapes, never from the program.

Multiply-adds count two. Counted per token: every projection (attention's
q, k, v, o; Mamba's ``in_proj`` and ``out_proj``), the depthwise conv, the
SSM recurrence as the per-step equations state it (the state update
``exp(dt A) * S + (dt x) B^T``: two multiplies and an add per state element;
the read-out ``S C``: a multiply-add per element), attention's q.k and p.v
over its context, the SwiGLU MLP of every layer, and the output head where
logits are taken. Norms, gates, softmax and the ``D`` skip are left out.
"""
from __future__ import annotations

from typing import Dict


def mamba_token_flops(cfg: Dict) -> float:
    """One Mamba-2 mixer, one token."""
    d = cfg["hidden_size"]
    inner = cfg["mamba_expand"] * d
    heads = cfg["mamba_n_heads"]
    conv_ch = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    state = heads * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    proj = 2 * d * (inner + conv_ch + heads) + 2 * inner * d
    conv = 2 * conv_ch * cfg["mamba_d_conv"]
    return float(proj + conv + 3 * state + 2 * state)


def attention_token_flops(cfg: Dict, context: int) -> float:
    """One attention mixer, one token attending to ``context`` positions."""
    d = cfg["hidden_size"]
    head_dim = d // cfg["num_attention_heads"]
    hd = cfg["num_attention_heads"] * head_dim
    kvd = cfg["num_key_value_heads"] * head_dim
    return float(2 * d * (2 * hd + 2 * kvd) + 2 * 2 * context * hd)


def hybrid_token_flops(cfg: Dict, context: int, logits: bool) -> float:
    """Forward FLOPs of one token at position ``context - 1``, through every
    layer, with or without the output head."""
    d = cfg["hidden_size"]
    mlp = 3 * 2 * d * cfg["shared_intermediate_size"]
    total = 0.0
    for kind in cfg["layer_types"]:
        mix = attention_token_flops(cfg, context) if kind == "attention" else mamba_token_flops(cfg)
        total += mix + mlp
    if logits:
        total += 2 * d * cfg["vocab_size"]
    return total


def serve_session_flops(cfg: Dict, rows: int, prompt_len: int, decode_steps: int) -> float:
    """Forward FLOPs a serving session needs: a prefill of ``rows`` prompts of
    ``prompt_len`` with logits for the last position only, then
    ``decode_steps`` single-token steps with logits, the ``i``-th attending
    to ``prompt_len + i + 1`` positions."""
    prefill = sum(hybrid_token_flops(cfg, p + 1, logits=False) for p in range(prompt_len))
    prefill += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    decode = sum(hybrid_token_flops(cfg, prompt_len + i + 1, logits=True)
                 for i in range(decode_steps))
    return rows * (prefill + decode)
