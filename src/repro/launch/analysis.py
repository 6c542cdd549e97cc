"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model (TPU v5e):
    peak bf16 compute   197 TFLOP/s / chip
    HBM bandwidth       819 GB/s   / chip
    ICI link bandwidth  ~50 GB/s   / link

Terms per (arch x shape x mesh) cell — all in seconds-per-step, per chip:

    compute    = HLO_FLOPs / peak            (cost_analysis is per-device)
    memory     = HLO_bytes / HBM_bw
    collective = sum over collective ops of algo-weighted shard bytes / link_bw

cost_analysis does not expose collective traffic, so we parse the
post-partitioning HLO: every ``all-reduce|all-gather|reduce-scatter|
all-to-all|collective-permute`` line contributes its shard bytes times the
ring-algorithm factor for its replica-group size.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\s"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclass
class CollectiveOp:
    kind: str
    shape_bytes: int
    group_size: int

    @property
    def wire_bytes(self) -> float:
        """Ring-algorithm bytes through one device's link."""
        g = max(self.group_size, 1)
        if g == 1:
            return 0.0
        if self.kind == "all-reduce":
            return 2.0 * (g - 1) / g * self.shape_bytes
        if self.kind in ("all-gather", "reduce-scatter", "all-to-all"):
            return (g - 1) / g * self.shape_bytes
        return float(self.shape_bytes)  # collective-permute


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims_s, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        dims = [int(d) for d in dims_s.split(",") if d] or [1]
        size = nbytes * math.prod(dims)
        g = 1
        mi = _GROUPS_IOTA_RE.search(line)
        if mi:
            g = int(mi.group(2))
        else:
            ml = _GROUPS_LIST_RE.search(line)
            if ml:
                g = len([x for x in ml.group(1).split(",") if x.strip() != ""])
        ops.append(CollectiveOp(kind, size, g))
    return ops


@dataclass
class RooflineTerms:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    n_collectives: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float
    memory_stats: Dict[str, float] = field(default_factory=dict)
    collective_breakdown: Dict[str, float] = field(default_factory=dict)
    trip_counts: List[int] = field(default_factory=list)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes": self.collective_bytes,
            "n_collectives": self.n_collectives,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "memory_stats": self.memory_stats,
            "collective_breakdown": self.collective_breakdown,
            "trip_counts": self.trip_counts,
        }


def roofline_from_compiled(
    compiled,
    n_devices: int,
    model_flops: float,
) -> RooflineTerms:
    """Roofline terms from the compiled module.

    XLA's cost_analysis counts ``while`` bodies once, so scan-over-layers
    models understate by the trip counts; :mod:`repro.launch.hlo_cost`
    re-derives FLOPs / bytes / collective traffic from the partitioned HLO
    with nesting-aware trip multipliers.  cost_analysis raw values are kept
    as ``*_raw`` cross-checks.
    """
    from .hlo_cost import analyze_hlo

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jaxlib wraps the dict in a list
        ca = ca[0] if ca else {}
    flops_raw = float(ca.get("flops", 0.0))
    bytes_raw = float(ca.get("bytes accessed", 0.0))
    hc = analyze_hlo(compiled.as_text())
    flops = max(hc.flops, flops_raw)
    bytes_acc = max(hc.bytes_accessed, bytes_raw)
    coll_bytes = hc.collective_bytes
    breakdown = dict(hc.collective_breakdown)
    n_colls = hc.n_collectives

    mem_stats: Dict[str, float] = {}
    try:
        ms = compiled.memory_analysis()
        mem_stats = {
            "argument_bytes": float(ms.argument_size_in_bytes),
            "output_bytes": float(ms.output_size_in_bytes),
            "temp_bytes": float(ms.temp_size_in_bytes),
            "alias_bytes": float(ms.alias_size_in_bytes),
        }
        mem_stats["peak_hbm_bytes"] = (
            mem_stats["argument_bytes"] + mem_stats["output_bytes"]
            + mem_stats["temp_bytes"] - mem_stats["alias_bytes"]
        )
    except Exception:
        pass

    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = coll_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)  # type: ignore[arg-type]
    per_dev_model = model_flops / n_devices
    useful = per_dev_model / flops if flops else 0.0
    mem_stats["flops_raw_scan_once"] = flops_raw
    mem_stats["bytes_raw_scan_once"] = bytes_raw
    return RooflineTerms(
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        collective_bytes=coll_bytes,
        n_collectives=int(n_colls),
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops=model_flops,
        useful_ratio=useful,
        memory_stats=mem_stats,
        collective_breakdown=breakdown,
        trip_counts=hc.trip_counts,
    )


# --------------------------------------------------------- model FLOP counts
def param_counts(cfg) -> Dict[str, float]:
    """Analytic parameter counts: total (of the experts, those held here) /
    active (MoE top-k) / embeddings."""
    d, ff, V = cfg.d_model, cfg.d_ff, cfg.vocab
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    embed = V * d * (1 if cfg.tie_embeddings else 2)
    total = 0.0
    active = 0.0
    for gi, (pattern, rep) in enumerate(cfg.groups):
        for kind in pattern:
            if kind == "attn" and cfg.mla:
                a = cfg.mla
                mix = (d * hq * a.qk_head_dim + d * (a.kv_lora_rank + a.qk_rope_head_dim)
                       + a.kv_lora_rank * hq * (a.qk_nope_head_dim + a.v_head_dim)
                       + hq * a.v_head_dim * d + a.kv_lora_rank)
            elif kind == "attn":
                mix = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
            elif kind == "rec":
                dr = cfg.rec.d_rnn
                mix = 2 * d * dr + 2 * dr * dr + dr * d + cfg.rec.conv_width * dr
            elif kind == "rwkv":
                lora = max(32, d // 32)
                mix = 5 * d * d + d * lora + lora * d
            elif kind == "mamba":
                m = cfg.mamba
                inner = m.expand * d
                heads = inner // m.head_dim
                conv_ch = inner + 2 * m.n_groups * m.d_state
                mix = (d * (inner + conv_ch + heads) + (m.d_conv + 1) * conv_ch
                       + 3 * heads + inner + inner * d)
            else:
                mix = 0.0
            if cfg.group_uses_moe(gi):
                m = cfg.moe
                expert = 3 * d * m.d_ff_expert
                routed_total = m.experts_held * expert
                routed_active = m.top_k * expert
                shared = 3 * d * m.d_ff_shared if m.d_ff_shared else 0.0
                router = d * m.num_experts
                ffn_total = routed_total + shared + router
                ffn_active = routed_active + shared + router
            else:
                ffn_total = ffn_active = 3 * d * ff
            total += rep * (mix + ffn_total)
            active += rep * (mix + ffn_active)
    return {"total": total, "active": active, "embed": float(embed)}


def model_flops_for(cfg, shape) -> float:
    """6*N_active*D for a train step; 2*N*D for prefill; 2*N*B for decode."""
    counts = param_counts(cfg)
    n = counts["active"]
    if shape.mode == "train":
        tokens = shape.global_batch * (shape.seq_len - cfg.frontend_tokens)
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * (shape.seq_len - cfg.frontend_tokens)
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence
