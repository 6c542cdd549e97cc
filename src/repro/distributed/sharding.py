"""Logical-axis sharding rules (MaxText-style) for the production meshes.

Model code annotates tensors with *logical* axis names; the active
:class:`ShardingRules` maps them to mesh axes.  Baseline mapping:

  batch   -> ("pod", "data")     activations' batch dim
  seq     -> "model"             sequence-parallel activations between blocks
  vocab   -> "model"             embedding/logit vocab dim
  heads   -> "model"             attention-head tensor parallelism
  ff      -> "model"             MLP hidden tensor parallelism
  experts -> "model"             expert parallelism (MoE, when divisible)
  fsdp    -> ("pod", "data")     ZeRO-3 sharding of params/moments
  kv_seq  -> "model"             decode KV-cache sequence sharding (GQA<TP)

Anything unmapped is replicated.  ``with_logical`` is the model-side
constraint helper; it is a no-op outside a mesh context (single-device smoke
tests run the same code).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    rules: Tuple[Tuple[str, Axis], ...] = (
        ("batch", ("pod", "data")),
        ("seq", "model"),
        ("vocab", "model"),
        ("heads", "model"),
        ("kv_heads", "model"),
        ("ff", "model"),
        ("experts", "model"),
        ("expert_ff", "model"),
        ("fsdp", ("pod", "data")),
        ("kv_seq", "model"),
        ("rnn", "model"),
    )

    def resolve(self, mesh_axes: Sequence[str], *logical: Optional[str]) -> P:
        """Translate logical names to a PartitionSpec valid on this mesh."""
        table = dict(self.rules)
        out = []
        used: set = set()
        for name in logical:
            if name is None:
                out.append(None)
                continue
            ax = table.get(name)
            if ax is None:
                out.append(None)
                continue
            if isinstance(ax, str):
                ax = (ax,)
            ax = tuple(a for a in ax if a in mesh_axes and a not in used)
            used.update(ax)
            if not ax:
                out.append(None)
            elif len(ax) == 1:
                out.append(ax[0])
            else:
                out.append(ax)
        return P(*out)

    def replace(self, **kw: Axis) -> "ShardingRules":
        table = dict(self.rules)
        table.update(kw)
        return ShardingRules(tuple(table.items()))


DEFAULT_RULES = ShardingRules()

# A context-global rules object: launch code swaps it before lowering.
_active_rules = DEFAULT_RULES


def set_rules(rules: ShardingRules) -> None:
    global _active_rules
    _active_rules = rules


def get_rules() -> ShardingRules:
    return _active_rules


def _current_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The mesh installed by ``jax.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def with_logical(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Sharding constraint by logical axis names (no-op without a mesh)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = get_rules().resolve(mesh.axis_names, *logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *logical: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, get_rules().resolve(mesh.axis_names, *logical))


def spec_for(mesh: Mesh, *logical: Optional[str]) -> P:
    return get_rules().resolve(mesh.axis_names, *logical)
