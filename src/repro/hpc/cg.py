"""CG: preconditioner-free conjugate gradient on the 2-D Laplacian.

Analogue of NPB CG (sparse linear algebra).  Four first-level code regions
per main-loop iteration — matvec, x-update, r-update, p-update — matching
the paper's region abstraction.  Acceptance verification: true relative
residual ||b - A x|| / ||b|| below tolerance (a math-invariant check, §2.2).

CG is the paper's interesting case: its short-term recurrence is *fragile*
(stale p/r break conjugacy), so recomputation often needs extra iterations
(S2) — the paper reports 9.1 extra iterations on average and a 49 % gap to
best-achievable recomputability.
"""
from __future__ import annotations

from functools import partial
from typing import Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.regions import IterativeApp, Region, State, VerifyResult
from .common import laplacian_apply, rel_residual, tree_sum


@jax.jit
def _dot(a, b):
    """Dot products over the last axis, ``(..., n) -> (..., 1)``, summed in
    :func:`tree_sum`'s fixed order: a serial region's vector and a stack of
    lanes round alike."""
    return tree_sum(a * b)[..., None]


@jax.jit
def _ratio(num, den):
    """``num / den`` in float32, 0 where ``den == 0`` — the one division both
    paths share, so it rounds alike even where float32 division is not
    correctly rounded (on the CPU it is, and equals the float64 quotient
    rounded to float32)."""
    return jnp.where(den != 0.0, num / den, 0.0)


@jax.jit
def _alpha(rho, p, q):
    """The x-update's step ``rho / (p . q)`` in one dispatch."""
    return _ratio(rho, _dot(p, q))


# Batched lane hooks for the vectorized campaign engine.  CG is matrix-free
# (the Laplacian is a stencil), so the whole iteration is elementwise chains
# plus per-lane reductions over the *data* axis — no ``dot_general`` — and
# vmapping is bitwise-safe.  Both paths take their dot products through
# :func:`_dot` and their scalar divisions through :func:`_ratio`, so they
# sum in one order and divide with one operation on any backend.
def _cg_step_core(a: dict, b: jnp.ndarray, one: jnp.ndarray, g: int, rr_every: int) -> dict:
    """One CG iteration (matvec, x-update, r-update, p-update) on stacked
    lanes; mirrors the serial region chain value-for-value.

    The axpy-style updates run in NumPy on the serial path (multiply, round,
    add, round); inside one XLA program the bare multiply-add contracts to an
    FMA at LLVM codegen (``llvm.fmuladd``, below HLO — optimization barriers
    and ``xla_allow_excess_precision=False`` do not reach it) and drifts by
    an ulp.  Multiplying each product by ``one`` — a *runtime* 1.0f operand
    the compiler cannot fold — forces the product to round first: the add
    then either stays separate or contracts to the exact ``fma(prod, 1, x)``,
    and both give the serial NumPy bits.
    """
    p, r, x = a["p"], a["r"], a["x"]
    q = jax.vmap(lambda v: laplacian_apply(v, g))(p)
    rho = a["rho"]
    alpha = _alpha(rho, p, q)
    x = x + (alpha * p) * one
    kk = a["k"]
    use_rr = ((kk + 1) % rr_every) == 0 if rr_every else jnp.zeros_like(kk, bool)
    # both branches computed, selected per lane (exact select, no rounding)
    r_true = b - jax.vmap(lambda v: laplacian_apply(v, g))(x)
    r = jnp.where(use_rr, r_true, r - (alpha * q) * one)
    rho_prev = rho
    rho = _dot(r, r)
    beta = _ratio(rho, rho_prev)
    p = jnp.where(use_rr, r, r + (beta * p) * one)
    return {"x": x, "r": r, "p": p, "q": q, "rho": rho,
            "rho_prev": rho_prev, "alpha": alpha, "k": kk + 1}


@partial(jax.jit, static_argnames=("g", "rr_every"))
def _cg_step_batch(x, r, p, q, rho, rho_prev, alpha, k, b, one, g: int, rr_every: int):
    out = _cg_step_core(
        {"x": x, "r": r, "p": p, "q": q, "rho": rho, "rho_prev": rho_prev,
         "alpha": alpha, "k": k}, b, one, g, rr_every)
    return (out["x"], out["r"], out["p"], out["q"], out["rho"],
            out["rho_prev"], out["alpha"], out["k"])


@partial(jax.jit, static_argnames=("g",))
def _lap_batch(u_b: jnp.ndarray, g: int) -> jnp.ndarray:
    return jax.vmap(lambda u: laplacian_apply(u, g))(u_b)


class CGApp(IterativeApp):
    """CG with periodic residual replacement (van der Vorst/Ye), the standard
    HPC guard against recurrence drift — and the mechanism that lets CG
    absorb block-stale state after an EasyCrash restart."""

    name = "cg"
    candidates = ("x", "r", "p", "q", "rho", "rho_prev", "alpha", "k")

    def __init__(
        self,
        grid: int = 48,
        tol: float = 1e-4,
        n_iters: int = 600,
        seed: int = 0,
        residual_replace_every: int = 20,
    ):
        self.grid = grid
        self.tol = tol
        self.n_iters = n_iters
        self._seed = seed
        self.rr_every = residual_replace_every

    # ------------------------------------------------------------------ state
    def init(self, seed: int = 0) -> State:
        g = self.grid
        rng = np.random.default_rng(self._seed)
        x_true = rng.standard_normal(g * g).astype(np.float32)
        b = np.asarray(laplacian_apply(jnp.asarray(x_true), g))
        x = np.zeros(g * g, np.float32)
        r = b.copy()
        p = r.copy()
        rho = np.array([float(r @ r)], np.float32)
        return {
            "x": x, "r": r, "p": p, "q": np.zeros_like(x),
            "rho": rho, "rho_prev": rho.copy(), "alpha": np.zeros(1, np.float32),
            "k": np.zeros(1, np.int64),
            "b": b,  # read-only
        }

    # ---------------------------------------------------------------- regions
    def _matvec(self, s: State) -> State:
        s = dict(s)
        s["q"] = np.asarray(laplacian_apply(jnp.asarray(s["p"]), self.grid))
        return s

    def _x_update(self, s: State) -> State:
        s = dict(s)
        alpha = float(_alpha(s["rho"], s["p"], s["q"])[0])
        s["alpha"] = np.array([alpha], np.float32)
        s["x"] = s["x"] + alpha * s["p"]
        return s

    def _r_update(self, s: State) -> State:
        s = dict(s)
        k = int(s["k"][0])
        if self.rr_every and (k + 1) % self.rr_every == 0:
            # residual replacement: recompute the *true* residual
            r = s["b"] - np.asarray(laplacian_apply(jnp.asarray(s["x"]), self.grid))
        else:
            r = s["r"] - s["alpha"][0] * s["q"]
        s["r"] = r.astype(np.float32)
        s["rho_prev"] = s["rho"].copy()
        s["rho"] = np.array([float(_dot(jnp.asarray(r), jnp.asarray(r))[0])], np.float32)
        return s

    def _p_update(self, s: State) -> State:
        s = dict(s)
        k = int(s["k"][0])
        if self.rr_every and (k + 1) % self.rr_every == 0:
            # restart direction after residual replacement
            s["p"] = s["r"].copy()
        else:
            beta = float(_ratio(jnp.asarray(s["rho"]), jnp.asarray(s["rho_prev"]))[0])
            s["p"] = s["r"] + beta * s["p"]
        s["k"] = s["k"] + 1
        return s

    def regions(self) -> Tuple[Region, ...]:
        return (
            Region("matvec", self._matvec, writes=("q",), reads=("p",), cost=2.0),
            Region("x_update", self._x_update, writes=("alpha", "x"), reads=("p", "q", "rho", "x")),
            Region("r_update", self._r_update, writes=("r", "rho_prev", "rho"), reads=("alpha", "q", "r", "x", "b")),
            Region("p_update", self._p_update, writes=("p", "k"), reads=("r", "rho", "rho_prev", "p")),
        )

    # ----------------------------------------------------------- verification
    def verify(self, state: State) -> VerifyResult:
        res = rel_residual(state["x"], state["b"], self.grid)
        return VerifyResult(bool(np.isfinite(res) and res < self.tol), res)

    def progress(self, state: State) -> float:
        return rel_residual(state["x"], state["b"], self.grid)

    def converged(self, state: State, it: int) -> bool:
        if it >= self.n_iters:
            return True
        rho = float(state["rho"][0])
        if not np.isfinite(rho):
            raise FloatingPointError("CG blow-up")
        # cheap recurrence-residual check every iteration; the *true*
        # residual is only asserted by verify()
        nb = float(np.linalg.norm(state["b"]))
        return np.sqrt(max(rho, 0.0)) / max(nb, 1e-30) < self.tol * 0.5

    # ------------------------------------------------------- batched recompute
    # ``b`` is read-only, so the hooks stack only the per-lane vectors and
    # close over lane 0's right-hand side.
    supports_batched_step = True
    supports_lane_driver = True

    _CARRY = ("x", "r", "p", "q", "rho", "rho_prev", "alpha", "k")

    def batched_kernels(self):
        from ..core.regions import BatchedKernel

        s = self.init(0)
        b = jnp.asarray(s["b"])
        rows = {f: np.stack([s[f]] * 3) for f in self._CARRY}
        g, rr = self.grid, self.rr_every
        args = tuple(rows[f] for f in self._CARRY)
        return (
            BatchedKernel("cg_step_batch",
                          lambda *vs: _cg_step_batch(*vs, b, np.float32(1.0), g, rr),
                          args, {i: 0 for i in range(len(args))}),
            BatchedKernel("lap_batch", lambda ub: _lap_batch(ub, g),
                          (rows["x"],), {0: 0}),
        )

    def run_iteration_batch(self, states):
        b = jnp.asarray(states[0]["b"])
        stacked = [jnp.asarray(np.stack([s[f] for s in states])) for f in self._CARRY]
        new = _cg_step_batch(*stacked, b, np.float32(1.0), self.grid, self.rr_every)
        new = [np.asarray(v) for v in new]
        out = []
        for i, s in enumerate(states):
            s = dict(s)
            for f, rows in zip(self._CARRY, new):
                s[f] = rows[i].astype(s[f].dtype, copy=False)
            out.append(s)
        return out

    def converged_batch(self, states, its):
        # pure host scalar math on the carried rho — exactly the serial hook,
        # with the lane-constant ||b|| computed once
        out: list = []
        nb = float(np.linalg.norm(states[0]["b"]))
        for s, it in zip(states, its):
            if it >= self.n_iters:
                out.append(True)
                continue
            rho = float(s["rho"][0])
            if not np.isfinite(rho):
                out.append(FloatingPointError("CG blow-up"))
            else:
                out.append(bool(np.sqrt(max(rho, 0.0)) / max(nb, 1e-30) < self.tol * 0.5))
        return out

    def verify_batch(self, states):
        # one batched Laplacian dispatch; the norms run in NumPy per
        # contiguous row, exactly like the serial rel_residual
        x_rows = np.stack([s["x"] for s in states])
        b_rows = np.stack([s["b"] for s in states])
        lap = np.asarray(_lap_batch(jnp.asarray(x_rows), self.grid))
        out = []
        for i in range(len(states)):
            r = b_rows[i] - lap[i]
            nb = float(np.linalg.norm(b_rows[i]))
            res = float(np.linalg.norm(r)) / max(nb, 1e-30)
            out.append(VerifyResult(bool(np.isfinite(res) and res < self.tol), res))
        return out

    def advance_lanes(self, states, its, stop):
        from ..core.lane_driver import LaneSpec, cached_driver, f32_monotone_cutoff

        g, rr, n_iters = self.grid, self.rr_every, self.n_iters
        # the serial decision sqrt(max(rho,0))/max(||b||,eps) < tol/2 is a
        # monotone float64 predicate of the carried float32 rho; ||b|| is
        # lane-constant, so the whole decision folds to rho <= cutoff
        nb = float(np.linalg.norm(states[0]["b"]))
        tol = self.tol
        cutoff = f32_monotone_cutoff(
            lambda v: np.sqrt(max(v, 0.0)) / max(nb, 1e-30) < tol * 0.5
        )

        def step(consts, a):
            return _cg_step_core(a, consts["b"], consts["one"], g, rr)

        def check(consts, a, it):
            rho = a["rho"][:, 0]
            over = it >= n_iters
            fin = jnp.isfinite(rho)
            conv = over | (fin & (rho <= cutoff))
            suspect = ~over & ~fin  # serial converged() would raise
            return conv, suspect

        key = ("cg", g, tol, n_iters, self._seed, rr)
        drv = cached_driver(key, lambda: LaneSpec(
            carry=self._CARRY,
            consts=lambda s0: {"b": s0["b"], "one": np.float32(1.0)},
            step=step, check=check,
        ))
        return drv.advance(states, its, stop)
