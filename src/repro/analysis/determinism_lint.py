"""Bitwise-batchability lint for batched step kernels.

The vectorized campaign engine requires every ``supports_batched_step`` app
to advance stacked restart lanes *bitwise identically* to the serial hooks
(``core/regions.py`` contract).  The classic violation is a vmapped matmul:
``vmap(lambda u: A @ u)`` batches the contraction into a matrix-matrix
product with a different reduction tiling, so lane i's result is no longer
the serial matvec bit for bit — found by hand in the PR that introduced the
vec engine, institutionalized here.

The lint walks a batched kernel's jaxpr propagating, per intermediate value,
*which axis carries the lane dimension* (or none).  An operation is safe
when each lane's slice of its output is computed by exactly the scalar/array
program the serial kernel would run:

* elementwise and shape-only ops preserve the lane axis;
* reductions over non-lane axes are per-lane;
* ``scan`` whose mapped ``xs`` carry the lane on axis 0 and whose
  consts/carry are lane-free executes its body once per lane
  (``lax.map`` — the sanctioned way to batch a matmul);
* ``scan``/``while`` with a *laned carry* (a vmapped ``fori_loop``) recurse
  into the body with the same lane layout.

Everything else touching a laned value is a finding, with ``dot_general``
called out specially: **any** contraction with a lane-carrying operand is
flagged, even lane-as-batch-dim forms, because batched GEMM tilings are not
guaranteed bitwise-per-lane — the default-deny that makes the lint an
allowlist, not a blocklist.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np  # noqa: F401  (kernels build example args with numpy)

import jax
from jax.extend.core import Literal

#: ops whose output element (i, ...) depends only on operand elements
#: (i, ...) — lane axis passes straight through
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg", "abs",
    "sign", "floor", "ceil", "round", "exp", "log", "log1p", "expm1",
    "sqrt", "rsqrt", "cbrt", "sin", "cos", "tan", "asin", "acos", "atan",
    "atan2", "sinh", "cosh", "tanh", "erf", "erfc", "erf_inv", "logistic",
    "max", "min", "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "eq", "ne", "lt", "le",
    "gt", "ge", "select_n", "clamp", "nextafter", "convert_element_type",
    "reduce_precision", "stop_gradient", "copy", "real", "imag", "conj",
    "is_finite", "square", "exp2", "log2", "population_count", "clz",
})

_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin",
})

_CUMULATIVE = frozenset({
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})


@dataclass(frozen=True)
class LintFinding:
    kernel: str
    primitive: str
    reason: str

    def __str__(self) -> str:
        return f"{self.kernel}: {self.primitive}: {self.reason}"


class _Walker:
    def __init__(self, kernel: str):
        self.kernel = kernel
        self.findings: List[LintFinding] = []

    def flag(self, prim: str, reason: str) -> None:
        self.findings.append(LintFinding(self.kernel, prim, reason))

    # ---------------------------------------------------------------- walk
    def walk(self, jaxpr, in_lanes: Sequence[Optional[int]]) -> List[Optional[int]]:
        env: Dict[object, Optional[int]] = {}
        for var, lane in zip(jaxpr.invars, in_lanes):
            env[var] = lane
        for var in jaxpr.constvars:
            env[var] = None

        def read(atom) -> Optional[int]:
            if isinstance(atom, Literal):
                return None
            return env.get(atom, None)

        for eqn in jaxpr.eqns:
            lanes = [read(v) for v in eqn.invars]
            outs = self._eqn(eqn, lanes)
            for ov, lane in zip(eqn.outvars, outs):
                env[ov] = lane
        return [read(v) for v in jaxpr.outvars]

    def _eqn(self, eqn, lanes: Sequence[Optional[int]]) -> List[Optional[int]]:
        prim = eqn.primitive.name
        n_out = len(eqn.outvars)
        laned = [x for x in lanes if x is not None]
        if not laned:
            return [None] * n_out
        lane = laned[0]

        if prim == "dot_general":
            # default-deny: batched GEMM reduction tilings are not
            # guaranteed bitwise-per-lane, whatever role the lane dim plays
            self.flag(prim, "contraction with a lane-carrying operand is not "
                            "bitwise-per-lane; batch matmuls with lax.map")
            return [None] * n_out

        if prim in _ELEMENTWISE:
            if any(x != lane for x in laned):
                self.flag(prim, f"operands disagree on lane axis {sorted(set(laned))}")
            return [lane] * n_out

        if prim in _REDUCTIONS:
            axes = tuple(int(a) for a in eqn.params.get("axes", ()))
            if lane in axes:
                self.flag(prim, f"reduces over the lane axis {lane} "
                                f"(cross-lane reduction)")
                return [None] * n_out
            out_lane = lane - sum(1 for a in axes if a < lane)
            return [out_lane] * n_out

        if prim in _CUMULATIVE:
            axis = int(eqn.params.get("axis", 0))
            if axis == lane:
                self.flag(prim, "cumulative op along the lane axis")
                return [None] * n_out
            return [lane] * n_out

        if prim == "broadcast_in_dim":
            bcast = tuple(int(d) for d in eqn.params["broadcast_dimensions"])
            return [bcast[lane]] * n_out

        if prim == "transpose":
            perm = tuple(int(p) for p in eqn.params["permutation"])
            return [perm.index(lane)] * n_out

        if prim == "reshape":
            in_shape = tuple(eqn.invars[0].aval.shape)
            new_sizes = tuple(int(s) for s in eqn.params["new_sizes"])
            if lane == 0 and new_sizes and in_shape and new_sizes[0] == in_shape[0]:
                return [0] * n_out
            self.flag(prim, f"reshape {in_shape} -> {new_sizes} mixes the "
                            f"lane axis into other dimensions")
            return [None] * n_out

        if prim == "squeeze":
            dims = tuple(int(d) for d in eqn.params.get("dimensions", ()))
            if lane in dims:
                self.flag(prim, "squeezes away the lane axis")
                return [None] * n_out
            return [lane - sum(1 for d in dims if d < lane)] * n_out

        if prim == "expand_dims":
            dims = tuple(int(d) for d in eqn.params.get("dimensions", ()))
            out_lane = lane + sum(1 for d in dims if d <= lane)
            return [out_lane] * n_out

        if prim == "pad":
            cfg = eqn.params["padding_config"]
            lo, hi, interior = cfg[lane]
            if int(lo) or int(hi) or int(interior):
                self.flag(prim, "pads along the lane axis (adds phantom lanes)")
                return [None] * n_out
            return [lane] * n_out

        if prim in ("slice", "rev"):
            # static slice/reverse: each output lane is one input lane's data
            return [lane] * n_out

        if prim == "concatenate":
            if any(x is not None and x != lane for x in lanes):
                self.flag(prim, "operands disagree on lane axis")
            return [lane] * n_out

        if prim == "scan":
            return self._scan(eqn, lanes)

        if prim == "while":
            return self._while(eqn, lanes)

        if prim in ("jit", "closed_call", "core_call", "remat", "remat2",
                    "custom_jvp_call", "custom_vjp_call",
                    "custom_jvp_call_jaxpr", "checkpoint"):
            sub = self._single_sub(eqn)
            if sub is not None and len(sub.invars) == len(lanes):
                return self.walk(sub, lanes)
            self.flag(prim, "call primitive with unrecognized body layout")
            return [None] * n_out

        self.flag(prim, f"primitive not on the bitwise-per-lane allowlist "
                        f"(lane axis {lane})")
        return [None] * n_out

    # ------------------------------------------------------- control flow
    @staticmethod
    def _single_sub(eqn):
        subs = []
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):  # ClosedJaxpr proxies .eqns, check first
                subs.append(v.jaxpr)
            elif hasattr(v, "eqns"):
                subs.append(v)
        return subs[0] if len(subs) == 1 else None

    def _scan(self, eqn, lanes: Sequence[Optional[int]]) -> List[Optional[int]]:
        p = eqn.params
        num_consts = int(p.get("num_consts", 0))
        num_carry = int(p.get("num_carry", p.get("num_carries", 0)))
        body = p["jaxpr"].jaxpr if hasattr(p["jaxpr"], "jaxpr") else p["jaxpr"]
        consts = lanes[:num_consts]
        carry = lanes[num_consts:num_consts + num_carry]
        xs = lanes[num_consts + num_carry:]
        n_ys = len(eqn.outvars) - num_carry

        lane_is_scan_dim = any(x == 0 for x in xs if x is not None)
        if lane_is_scan_dim:
            if all(x in (None, 0) for x in xs) and all(c is None for c in carry) \
                    and all(c is None for c in consts):
                # lax.map: the scan dimension *is* the lane dimension, so the
                # body executes the serial program once per lane — safe by
                # construction, body needs no lane tracking
                return [None] * num_carry + [0] * n_ys
            self.flag("scan", "scans over the lane axis while consts/carry "
                              "also carry lanes: steps mix lanes")
            return [None] * len(eqn.outvars)
        # vmapped loop: consts and carry keep their lane layout inside the
        # body (loop-invariant batched operands become laned consts), xs
        # lose the scan axis
        inner_xs = [None if x is None else x - 1 for x in xs]
        inner_out = self.walk(body, list(consts) + list(carry) + inner_xs)
        carry_out = inner_out[:num_carry]
        ys_out = inner_out[num_carry:]
        if list(carry_out) != list(carry):
            self.flag("scan", f"carry lane layout changes across iterations "
                              f"({list(carry)} -> {list(carry_out)})")
        outer_ys = [
            (0 if lane_is_scan_dim else None) if y is None else y + 1
            for y in ys_out
        ]
        return list(carry_out) + outer_ys

    def _while(self, eqn, lanes: Sequence[Optional[int]]) -> List[Optional[int]]:
        p = eqn.params
        cn = int(p.get("cond_nconsts", 0))
        bn = int(p.get("body_nconsts", 0))
        cond = p["cond_jaxpr"].jaxpr if hasattr(p["cond_jaxpr"], "jaxpr") else p["cond_jaxpr"]
        body = p["body_jaxpr"].jaxpr if hasattr(p["body_jaxpr"], "jaxpr") else p["body_jaxpr"]
        cond_consts = lanes[:cn]
        body_consts = lanes[cn:cn + bn]
        carry = lanes[cn + bn:]
        self.walk(cond, list(cond_consts) + list(carry))
        carry_out = self.walk(body, list(body_consts) + list(carry))
        if list(carry_out) != list(carry):
            self.flag("while", f"carry lane layout changes across iterations "
                               f"({list(carry)} -> {list(carry_out)})")
        return list(carry_out)


def lint_batched_fn(name, fn, args, batched) -> List[LintFinding]:
    """Lint one batched kernel: ``batched`` maps argument positions to the
    lane axis they carry.  Returns the (possibly empty) finding list; a
    kernel whose laned outputs lose track of the lane is also a finding."""
    closed = jax.make_jaxpr(fn)(*args)
    # map flattened invars back to argument positions
    lanes: List[Optional[int]] = []
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_leaves(a)
        lanes.extend([batched.get(i)] * len(leaves))
    w = _Walker(name)
    w.walk(closed.jaxpr, lanes)
    return w.findings


def lint_app(app) -> Dict[str, List[LintFinding]]:
    """Lint every declared batched kernel of one app."""
    out: Dict[str, List[LintFinding]] = {}
    for k in app.batched_kernels():
        out[k.name] = lint_batched_fn(
            f"{app.name}/{k.name}", k.fn, k.args, dict(k.batched)
        )
    return out
