"""Operations of a decoder with latent attention and a share of routed
experts (DeepSeek-V2-Lite), from its configuration's shapes, never from the
program.

Multiply-adds count two. Counted per token, in the expanded form of MLA
(each position's K and V expanded from its latent once): the projections
``Wq``, ``Wkv_a``, ``Wkv_b`` and ``Wo``; q.k over the context at the query
head's width (no-rotary plus rotary) and p.v at the value head's; the dense
first layers' SwiGLU; in each expert layer the router over every published
expert, the shared experts' SwiGLU, and one expert SwiGLU for each (token,
held expert) pair routed here; the output head where logits are taken.
Norms, rotary, softmax and the routing weights are left out. The absorbed
decode the program runs does more arithmetic than this count: the count is
what the model needs, not what an implementation spends.
"""
from __future__ import annotations

from typing import Dict, Optional


def expert_flops(cfg: Dict) -> float:
    """One routed expert's SwiGLU, one token."""
    return float(3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"])


def expected_pairs_per_token(cfg: Dict) -> float:
    """(token, held expert) pairs per token and expert layer, where routing
    spreads evenly: ``top_k x held / published``."""
    published = cfg["published"]["n_routed_experts"]
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / published


def mla_token_flops(cfg: Dict, context: int) -> float:
    """One MLA layer, one token attending to ``context`` positions."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    proj = 2 * d * h * (nope + rope) + 2 * d * (rank + rope) + 2 * rank * h * (nope + v)
    proj += 2 * h * v * d
    return float(proj + 2 * context * h * (nope + rope) + 2 * context * h * v)


def token_flops(cfg: Dict, context: int, logits: bool) -> float:
    """Forward FLOPs of one token at position ``context - 1``, through every
    layer, with or without the output head; the routed experts left out
    (:func:`serve_session_flops` adds them per pair)."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    total = cfg["num_hidden_layers"] * mla_token_flops(cfg, context)
    total += dense * 3 * 2 * d * cfg["intermediate_size"]
    total += moe * (2 * d * cfg["published"]["n_routed_experts"] + 3 * 2 * d * shared)
    if logits:
        total += 2 * d * cfg["vocab_size"]
    return float(total)


def serve_session_flops(cfg: Dict, rows: int, prompt_len: int, decode_steps: int,
                        expert_pairs: Optional[int] = None) -> float:
    """Forward FLOPs a serving session needs: a prefill of ``rows`` prompts of
    ``prompt_len`` with logits for the last position only, then
    ``decode_steps`` single-token steps with logits, the ``i``-th attending
    to ``prompt_len + i + 1`` positions; and the routed experts' SwiGLU for
    ``expert_pairs`` (token, held expert) pairs, the session's count, or
    where none is at hand its expectation over every token and expert layer."""
    prefill = sum(token_flops(cfg, p + 1, logits=False) for p in range(prompt_len))
    prefill += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    decode = sum(token_flops(cfg, prompt_len + i + 1, logits=True)
                 for i in range(decode_steps))
    if expert_pairs is None:
        moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
        expert_pairs = (rows * (prompt_len + decode_steps) * moe
                        * expected_pairs_per_token(cfg))
    return rows * (prefill + decode) + expert_pairs * expert_flops(cfg)
