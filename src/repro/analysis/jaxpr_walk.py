"""Trace app regions to jaxprs and walk their dataflow.

Regions are plain ``dict -> dict`` transitions over numpy arrays, so tracing
them with :func:`jax.make_jaxpr` needs one accommodation: many region
bodies round-trip values through ``np.asarray`` (the state contract is
numpy), which would force a concrete value out of a tracer.
:func:`numpy_shim` patches ``np.asarray``/``np.array`` to pass jax tracers
through unchanged for the duration of a trace — the same shim makes
``jax.jvp`` work for the damping probe in :mod:`repro.analysis.classify`.

The walker computes, for every value a region writes, (a) which state
objects it depends on and (b) which primitives sit on those input-dependent
paths — with the operand roles that matter for crash classification:
comparisons, ``argmin``/``sort``, ``select_n`` with a data-dependent
predicate, and gathers/scatters with data-dependent *indices* are tagged
``discrete:*`` (a crashed stale input can flip them by a whole category, so
no contraction argument applies); constant-index scatters (boundary pins)
and iota-derived masks are not.

Not every region traces — some call ``int(...)``/``float(...)`` on state
(host-side control flow) or index in place.  That is a *finding*, not an
error: :func:`trace_region` returns ``ok=False`` and the classifier falls
back to the region's declared reads/writes at reduced confidence.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Mapping, Sequence, Tuple

import numpy as np

import jax
from jax.extend.core import Literal

from ..core.regions import Region, State

#: tag recorded for objects written by a region that could not be traced
UNTRACED = "<untraced>"

_TracerT = jax.core.Tracer

# discrete-valued primitives, by the operand role that makes them discrete
_DISCRETE_ALWAYS = frozenset({"argmin", "argmax", "sort", "top_k"})
_DISCRETE_CMP = frozenset({"eq", "ne", "lt", "le", "gt", "ge"})
#: primitive -> positions of its *index* operands; the op is discrete only
#: when an index is data-dependent (constant-index pins/segment ids are not)
_INDEX_OPERANDS = {
    "gather": (1,),
    "scatter": (1,),
    "scatter-add": (1,),
    "scatter-mul": (1,),
    "scatter-min": (1,),
    "scatter-max": (1,),
    "dynamic_slice": slice(1, None),
    "dynamic_update_slice": slice(2, None),
}


@contextlib.contextmanager
def numpy_shim():
    """Let ``np.asarray``/``np.array`` pass jax tracers through unchanged."""
    orig_asarray, orig_array = np.asarray, np.array

    def asarray(x, dtype=None, **kw):
        if isinstance(x, _TracerT):
            return x if dtype is None else x.astype(dtype)
        return orig_asarray(x, dtype=dtype, **kw)

    def array(x, dtype=None, **kw):
        if isinstance(x, _TracerT):
            return x if dtype is None else x.astype(dtype)
        return orig_array(x, dtype=dtype, **kw)

    np.asarray, np.array = asarray, array
    try:
        yield
    finally:
        np.asarray, np.array = orig_asarray, orig_array


@dataclass(frozen=True)
class RegionTrace:
    """Dataflow summary of one region (or the declared-metadata fallback)."""

    name: str
    ok: bool
    #: written object -> state objects its new value depends on
    deps: Mapping[str, FrozenSet[str]]
    #: written object -> primitives on its input-dependent paths
    #: (plus ``discrete:*`` tags and :data:`UNTRACED`)
    ops: Mapping[str, FrozenSet[str]]
    #: statically estimated bytes this region writes per iteration
    write_bytes: int
    error: str = ""

    def reads(self) -> FrozenSet[str]:
        """State objects whose current value this region consumes."""
        out: FrozenSet[str] = frozenset()
        for d in self.deps.values():
            out |= d
        return out


Info = Tuple[FrozenSet[str], FrozenSet[str]]  # (deps, ops)
_EMPTY: Info = (frozenset(), frozenset())


def _sub_jaxprs(eqn) -> List[object]:
    out = []
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):  # ClosedJaxpr (checked first: it proxies .eqns)
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):  # open Jaxpr
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                if hasattr(x, "jaxpr"):
                    out.append(x.jaxpr)
                elif hasattr(x, "eqns"):
                    out.append(x)
    return out


def _all_prims(jaxpr) -> FrozenSet[str]:
    """Every primitive name reachable from ``jaxpr`` (transitively)."""
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn):
            out |= _all_prims(sub)
    return frozenset(out)


def _discrete_tags(eqn, in_info: Sequence[Info]) -> FrozenSet[str]:
    """``discrete:*`` tags this equation contributes, given operand deps."""
    name = eqn.primitive.name
    if name in _DISCRETE_ALWAYS and any(d for d, _ in in_info):
        return frozenset({f"discrete:{name}"})
    if name in _DISCRETE_CMP and any(d for d, _ in in_info):
        return frozenset({f"discrete:{name}"})
    if name == "select_n" and in_info and in_info[0][0]:
        # data-dependent predicate: the selection itself can flip
        return frozenset({"discrete:select_n"})
    idx = _INDEX_OPERANDS.get(name)
    if idx is not None:
        pos = list(range(len(in_info)))[idx] if isinstance(idx, slice) else list(idx)
        if any(p < len(in_info) and in_info[p][0] for p in pos):
            return frozenset({f"discrete:{name}"})
    return frozenset()


def walk_jaxpr(jaxpr, in_info: Sequence[Info]) -> List[Info]:
    """Propagate (deps, ops) from a jaxpr's invars to its outvars.

    ``jit``-style single-body higher-order primitives recurse exactly;
    multi-branch/looping ones (``scan``/``while``/``cond``) join
    conservatively — all outputs depend on all data-dependent inputs, and
    every primitive inside counts as on-path.
    """
    env: Dict[object, Info] = {}
    for var, info in zip(jaxpr.invars, in_info):
        env[var] = info
    for var in jaxpr.constvars:
        env[var] = _EMPTY

    def read(atom) -> Info:
        if isinstance(atom, Literal):
            return _EMPTY
        return env.get(atom, _EMPTY)

    for eqn in jaxpr.eqns:
        infos = [read(v) for v in eqn.invars]
        deps = frozenset().union(*(d for d, _ in infos)) if infos else frozenset()
        if not deps:
            for ov in eqn.outvars:
                env[ov] = _EMPTY
            continue
        subs = _sub_jaxprs(eqn)
        if len(subs) == 1 and len(subs[0].invars) == len(eqn.invars):
            # jit / closed_call / custom_jvp-style: exact recursion
            out_infos = walk_jaxpr(subs[0], infos)
            for ov, info in zip(eqn.outvars, out_infos):
                env[ov] = info
            continue
        ops = frozenset().union(*(o for _, o in infos)) if infos else frozenset()
        if subs:
            inner = frozenset().union(*(_all_prims(s) for s in subs))
            ops |= {eqn.primitive.name} | inner
            ops |= {f"discrete:{p}" for p in inner
                    if p in _DISCRETE_ALWAYS | _DISCRETE_CMP | {"select_n"}
                    or p in _INDEX_OPERANDS}
        else:
            ops |= {eqn.primitive.name} | _discrete_tags(eqn, infos)
        for ov in eqn.outvars:
            env[ov] = (deps, ops)
    return [read(v) for v in jaxpr.outvars]


def trace_region(state: State, region: Region,
                 const_objects: FrozenSet[str] = frozenset()) -> RegionTrace:
    """Trace one region against an example state; falls back to declared
    metadata (``reads + writes``, self-dependent, :data:`UNTRACED`) when the
    region body cannot be traced.

    ``const_objects`` names state entries no region ever writes: they are
    rebuilt bit-identically by ``restart_init`` after a crash, so for crash
    dataflow they are constants — a scatter whose indices come from a
    read-only pin table is *not* data-dependent."""
    keys = sorted(state)

    def fn(s):
        out = region.fn(dict(s))
        return {k: out[k] for k in region.writes if k in out}

    try:
        with numpy_shim():
            closed = jax.make_jaxpr(fn)(dict(state))
    except Exception as e:  # noqa: BLE001 - untraceable is a finding, not an error
        deps = {w: (frozenset(region.reads) | {w}) - const_objects
                for w in region.writes}
        ops = {w: frozenset({UNTRACED}) for w in region.writes}
        wb = sum(int(np.asarray(state[w]).nbytes) for w in region.writes if w in state)
        return RegionTrace(region.name, False, deps, ops, wb,
                           error=f"{type(e).__name__}: {e}")

    jaxpr = closed.jaxpr
    # dict input flattens in sorted-key order, one leaf per state entry
    in_info: List[Info] = [
        (_EMPTY[0] if k in const_objects else frozenset({k}), frozenset())
        for k in keys
    ]
    out_info = walk_jaxpr(jaxpr, in_info)
    written = [w for w in sorted(region.writes)]
    # output dict flattens in sorted-key order too
    deps = {}
    ops = {}
    wb = 0
    for w, (d, o), var in zip(written, out_info, jaxpr.outvars):
        deps[w] = d
        ops[w] = o
        aval = getattr(var, "aval", None)
        if aval is not None and hasattr(aval, "shape") and hasattr(aval, "dtype"):
            wb += int(np.prod(aval.shape, dtype=np.int64)) * np.dtype(aval.dtype).itemsize
        elif w in state:
            wb += int(np.asarray(state[w]).nbytes)
    return RegionTrace(region.name, True, deps, ops, int(wb))
