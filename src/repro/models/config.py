"""Model / shape configuration for the architecture zoo.

One :class:`ModelConfig` describes any of the assigned architectures
(dense GQA, latent attention (MLA), MoE, SSM/RWKV-6, RG-LRU and Mamba-2 hybrids,
audio/VLM backbones).  Layer
stacks are described as *groups* — ``(pattern, repeat)`` pairs — so hybrids
like RecurrentGemma's (rec, rec, attn) x 12 + (rec, rec) compile as one
``lax.scan`` per group.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Literal, Optional, Sequence, Tuple

LayerKind = Literal["attn", "rec", "rwkv", "mamba"]


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    d_ff_shared: int = 0          # shared-expert MLP width (0 = none)
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    #: prefill dispatch: ``sort`` (capacity buffers, drops overflow),
    #: ``dropless`` (each held expert's routed tokens in tiles, none
    #: dropped) or ``dense`` (every held expert on every token)
    impl: Literal["sort", "dropless", "dense"] = "sort"
    #: shard experts over "model" (EP) when num_experts divides the axis,
    #: else shard the expert FF dim (TP)
    expert_parallel: bool = True
    #: dispatch in G token groups (group dim sharded with the batch) so the
    #: sort/scatter stays shard-local; 1 = one global dispatch (baseline)
    dispatch_groups: int = 1
    #: the router's top-k weights renormalised to sum to 1 (False: the
    #: softmax's own weights, as DeepSeek-V2's ``norm_topk_prob`` false)
    norm_topk_prob: bool = True
    #: the first this many layers have a dense MLP of ``d_ff``, the rest
    #: experts (DeepSeek's ``first_k_dense_replace``)
    first_k_dense: int = 0
    #: expert parallelism: ``ep_size`` chips share each expert layer, each
    #: holding ``num_experts / ep_size`` consecutive experts; this chip is
    #: ``ep_rank`` and computes its own experts' part of the layer (the
    #: router still chooses among all ``num_experts``)
    ep_size: int = 1
    ep_rank: int = 0

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.ep_size

    @property
    def first_held(self) -> int:
        return self.ep_rank * self.experts_held


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): K and V come from a
    ``kv_lora_rank``-wide latent (RMS-normed) and one shared rotary key of
    ``qk_rope_head_dim``; a query head is ``qk_nope_head_dim`` +
    ``qk_rope_head_dim`` wide, a value head ``v_head_dim``. The decode cache
    holds the latent and the rotary key, not per-head K/V."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (``rope_scaling`` of type ``yarn``)."""

    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class RecurrentConfig:
    """RG-LRU (RecurrentGemma) block parameters."""

    d_rnn: int = 0                # recurrence width (lru_width)
    conv_width: int = 4
    window: int = 2048            # local-attention window of the hybrid


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64


@dataclass(frozen=True)
class MambaConfig:
    """Mamba-2 mixer: ``expand * d_model`` inner channels in heads of
    ``head_dim``, a ``d_state``-wide SSM state per head, ``n_groups`` shared
    B/C groups, a depthwise causal conv of width ``d_conv`` (with bias) and
    the chunk length of the full-sequence SSD."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None
    activation: Literal["silu", "gelu", "relu2"] = "silu"
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    rec: Optional[RecurrentConfig] = None
    rwkv: Optional[RWKVConfig] = None
    mamba: Optional[MambaConfig] = None
    #: latent attention in place of GQA in every ``attn`` layer
    mla: Optional[MLAConfig] = None
    #: YaRN-scaled rotary frequencies (None: plain rotary)
    yarn: Optional[YarnConfig] = None
    #: layer groups: ((kind, kind, ...), repeat); default = all-attn
    layer_groups: Optional[Tuple[Tuple[Tuple[str, ...], int], ...]] = None
    #: number of prepended frontend embeddings (VLM patches); 0 = none
    frontend_tokens: int = 0
    #: attention is quadratic unless a window bounds it
    attn_window: Optional[int] = None
    dtype: str = "bfloat16"
    #: Adam moment dtype — f32 default, bf16 for the very large archs
    moment_dtype: str = "float32"
    remat: bool = True
    #: microbatches for gradient accumulation (1 = none)
    grad_accum: int = 1
    #: rotary position embedding in attention; False = none (NoPE)
    rope: bool = True
    #: attention score scale; None = head_dim ** -0.5
    attn_scale: Optional[float] = None
    #: Granite's scalars: embeddings times ``embedding_multiplier``, each
    #: sublayer's output times ``residual_multiplier`` before its residual
    #: add, logits divided by ``logits_scaling`` (1 = absent)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    #: standard deviation of the embedding table's random draw
    embed_std: float = 1.0

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def groups(self) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
        if self.layer_groups is not None:
            return self.layer_groups
        dense = self.moe.first_k_dense if self.moe is not None else 0
        if dense:
            return ((("attn",), dense), (("attn",), self.n_layers - dense))
        return ((("attn",), self.n_layers),)

    def group_uses_moe(self, gi: int) -> bool:
        """Whether the layers of group ``gi`` have experts in place of the
        dense MLP: all layers from ``moe.first_k_dense`` on."""
        if self.moe is None:
            return False
        first = sum(len(pat) * rep for pat, rep in self.groups[:gi])
        return first >= self.moe.first_k_dense

    def total_layers(self) -> int:
        return sum(len(pat) * rep for pat, rep in self.groups)

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run 500k-token contexts?  SSM / windowed-attn only."""
        kinds = {k for pat, _ in self.groups for k in pat}
        if "attn" in kinds and self.attn_window is None:
            return False
        return True

    def validate(self) -> "ModelConfig":
        assert self.total_layers() == self.n_layers, (
            f"{self.name}: groups sum to {self.total_layers()} != {self.n_layers}"
        )
        if self.family == "moe":
            assert self.moe is not None
        if self.moe is not None:
            m = self.moe
            assert m.num_experts % m.ep_size == 0 and 0 <= m.ep_rank < m.ep_size
            assert m.ep_size == 1 or m.impl != "sort", "a share dispatches dropless or dense"
            starts = [sum(len(p) * r for p, r in self.groups[:gi])
                      for gi in range(len(self.groups))] + [self.n_layers]
            assert m.first_k_dense in starts, "dense layers fill whole groups"
        if self.yarn is not None:
            # YaRN's cos/sin factor, mscale(mscale) / mscale(mscale_all_dim), is then 1
            assert self.yarn.mscale == self.yarn.mscale_all_dim, "YaRN's attention factor must be 1"
        kinds = {k for pat, _ in self.groups for k in pat}
        if "rec" in kinds:
            assert self.rec is not None and self.rec.d_rnn > 0
        if "rwkv" in kinds:
            assert self.rwkv is not None
        if "mamba" in kinds:
            assert self.mamba is not None
            assert self.mamba.expand * self.d_model % self.mamba.head_dim == 0
        return self


@dataclass(frozen=True)
class ShapeConfig:
    """One benchmark cell: sequence x batch x step kind."""

    name: str
    seq_len: int
    global_batch: int
    mode: Literal["train", "prefill", "decode"]


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)


def get_shape(name: str) -> ShapeConfig:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(f"unknown shape {name!r}; have {[s.name for s in SHAPES]}")


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Is (arch, shape) a runnable cell?  Returns (ok, reason-if-not)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "pure full-attention arch: 500k context needs sub-quadratic attention"
    return True, ""


def scaled_down(cfg: ModelConfig, layers: int = 2, width: int = 64) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    ratio = width / cfg.d_model
    d_head = max(16, int(cfg.head_dim * ratio) // 8 * 8) if cfg.d_head else None
    n_heads = max(2, min(cfg.n_heads, 4))
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    groups: Optional[Tuple] = None
    if cfg.layer_groups is not None:
        # keep one group with the full pattern, repeated once
        pat = cfg.layer_groups[0][0]
        groups = ((pat, 1),)
        layers = len(pat)
    moe = None
    if cfg.moe:
        # an expert share keeps shares of two experts, on at most four chips
        ep = min(cfg.moe.ep_size, 4)
        moe = dataclasses.replace(
            cfg.moe, num_experts=min(cfg.moe.num_experts, 4 if ep == 1 else 2 * ep),
            top_k=min(cfg.moe.top_k, 2), d_ff_expert=width * 2, d_ff_shared=(width * 2 if cfg.moe.d_ff_shared else 0),
            first_k_dense=min(cfg.moe.first_k_dense, layers - 1), ep_size=ep,
            ep_rank=min(cfg.moe.ep_rank, ep - 1),
        )
    mla = (dataclasses.replace(cfg.mla, kv_lora_rank=width // 2, qk_nope_head_dim=16,
                               qk_rope_head_dim=8, v_head_dim=16) if cfg.mla else None)
    rec = dataclasses.replace(cfg.rec, d_rnn=width, window=32) if cfg.rec else None
    rwkv = dataclasses.replace(cfg.rwkv, head_dim=16) if cfg.rwkv else None
    mamba = (dataclasses.replace(cfg.mamba, d_state=16, head_dim=16, chunk=8)
             if cfg.mamba else None)
    return dataclasses.replace(
        cfg,
        n_layers=layers,
        d_model=width,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        d_head=width // n_heads,
        d_ff=width * 3,
        vocab=256,
        moe=moe,
        rec=rec,
        rwkv=rwkv,
        mamba=mamba,
        mla=mla,
        layer_groups=groups,
        frontend_tokens=min(cfg.frontend_tokens, 4),
        attn_window=min(cfg.attn_window, 32) if cfg.attn_window else None,
        grad_accum=1,
    ).validate()
