"""MG: two-grid multigrid for the 2-D Poisson problem (NPB MG analogue).

Four first-level code regions per V-cycle — residual, coarse solve,
prolong+correct, fine smoothing — exactly the R1–R4 structure of the paper's
Fig 2a.  ``u`` and ``r`` are the big main-loop data objects (the paper's
critical-object study on MG uses u, r and an index object); the coarse-grid
correction is temporal and rebuilt every iteration.

Multigrid is strongly self-correcting: a block-stale ``u`` is just a worse
initial guess for the next V-cycle, so recomputability is high once ``u`` is
persisted (paper Fig 4a: persisting u lifts MG from 27 % to 63 %).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.regions import IterativeApp, Region, State, VerifyResult
from .common import jacobi_sweep, laplacian_apply, prolong, rel_residual, restrict


# Batched lane hooks for the vectorized campaign engine.  The V-cycle is
# stencils, grid-transfer reshapes and elementwise chains — no ``dot_general``
# — so vmapping is bitwise-safe.  Two serial host-side roundings must survive
# the move in-program: ``restrict`` materializes ``sum / 4`` as its own
# program root (its sum in a written-out order, which no fusion may pick
# for it), and the coarse right-hand side ``4.0 * rc`` is an eager
# standalone multiply.  Inside one XLA program the first would reassociate
# with the second (``4 * (s / 4) -> s``) and the result would contract
# into the first Jacobi ``b + nb`` as an FMA; multiplying each by ``one`` — a
# *runtime* 1.0f the compiler cannot fold — pins both roundings exactly where
# the serial path takes them (see :func:`repro.hpc.cg._cg_step_core`).
def _mg_cycle_core(a: dict, b: jnp.ndarray, one: jnp.ndarray, g: int,
                   coarse_sweeps: int, fine_sweeps: int) -> dict:
    """One V-cycle (residual, coarse solve, prolong+correct, fine smoothing)
    on stacked lanes; mirrors the serial region chain value-for-value."""
    u = a["u"]
    r = b - jax.vmap(lambda v: laplacian_apply(v, g))(u)
    rc = jax.vmap(lambda v: restrict(v, g))(r) * one
    bc = (4.0 * rc) * one
    ec = jnp.zeros_like(rc)
    for _ in range(coarse_sweeps):
        ec = jax.vmap(lambda e, bb: jacobi_sweep(e, bb, g // 2))(ec, bc)
    u = u + jax.vmap(lambda e: prolong(e, g))(ec)
    for _ in range(fine_sweeps):
        u = jax.vmap(lambda v: jacobi_sweep(v, b, g))(u)
    return {"u": u, "r": r, "ec": ec, "k": a["k"] + 1}


@partial(jax.jit, static_argnames=("g", "coarse_sweeps", "fine_sweeps"))
def _mg_cycle_batch(u, r, ec, k, b, one, g: int, coarse_sweeps: int, fine_sweeps: int):
    out = _mg_cycle_core({"u": u, "r": r, "ec": ec, "k": k}, b, one, g,
                         coarse_sweeps, fine_sweeps)
    return (out["u"], out["r"], out["ec"], out["k"])


@partial(jax.jit, static_argnames=("g",))
def _lap_batch(u_b: jnp.ndarray, g: int) -> jnp.ndarray:
    return jax.vmap(lambda u: laplacian_apply(u, g))(u_b)


class MGApp(IterativeApp):
    name = "mg"
    candidates = ("u", "r", "k")

    def __init__(self, grid: int = 64, rel_eps: float = 1e-3, n_iters: int = 24, seed: int = 0,
                 coarse_sweeps: int = 8, fine_sweeps: int = 2):
        self.grid = grid
        # NPB-style verification: the final residual norm must match the
        # golden run's value to rel_eps (precise-numerical-integrity
        # acceptance, paper §2.2) — NPB MG compares norms against a reference
        # with a tight epsilon, on a *fixed* iteration schedule.
        self.rel_eps = rel_eps
        self.n_iters = n_iters
        self._seed = seed
        self.coarse_sweeps = coarse_sweeps
        self.fine_sweeps = fine_sweeps
        self._golden_res: float | None = None

    def init(self, seed: int = 0) -> State:
        g = self.grid
        rng = np.random.default_rng(self._seed)
        u_true = rng.standard_normal(g * g).astype(np.float32)
        b = np.asarray(laplacian_apply(jnp.asarray(u_true), g))
        return {
            "u": np.zeros(g * g, np.float32),
            "r": b.copy(),
            "ec": np.zeros((g // 2) * (g // 2), np.float32),  # temporal
            "k": np.zeros(1, np.int64),
            "b": b,  # read-only
        }

    # ---------------------------------------------------------------- regions
    def _residual(self, s: State) -> State:
        s = dict(s)
        s["r"] = s["b"] - np.asarray(laplacian_apply(jnp.asarray(s["u"]), self.grid))
        return s

    def _coarse(self, s: State) -> State:
        s = dict(s)
        g = self.grid
        rc = restrict(jnp.asarray(s["r"]), g)
        # scale: restriction halves h, so the coarse operator is 4x weaker
        ec = jnp.zeros_like(rc)
        for _ in range(self.coarse_sweeps):
            ec = jacobi_sweep(ec, 4.0 * rc, g // 2)
        s["ec"] = np.asarray(ec)
        return s

    def _correct(self, s: State) -> State:
        s = dict(s)
        s["u"] = s["u"] + np.asarray(prolong(jnp.asarray(s["ec"]), self.grid))
        return s

    def _smooth(self, s: State) -> State:
        s = dict(s)
        u = jnp.asarray(s["u"])
        for _ in range(self.fine_sweeps):
            u = jacobi_sweep(u, jnp.asarray(s["b"]), self.grid)
        s["u"] = np.asarray(u)
        s["k"] = s["k"] + 1
        return s

    def regions(self) -> Tuple[Region, ...]:
        return (
            Region("R1_residual", self._residual, writes=("r",), reads=("u", "b"), cost=1.0),
            Region("R2_coarse", self._coarse, writes=("ec",), reads=("r",), cost=2.0),
            Region("R3_correct", self._correct, writes=("u",), reads=("ec", "u"), cost=1.0),
            Region("R4_smooth", self._smooth, writes=("u", "k"), reads=("u", "b"), cost=2.0),
        )

    # ----------------------------------------------------------- verification
    def _golden_residual(self) -> float:
        if self._golden_res is None:
            s = self.init(self._seed)
            for _ in range(self.n_iters):
                s = self.run_iteration(s)
            self._golden_res = rel_residual(s["u"], s["b"], self.grid)
        return self._golden_res

    def verify(self, state: State) -> VerifyResult:
        res = rel_residual(state["u"], state["b"], self.grid)
        ref = self._golden_residual()
        ok = np.isfinite(res) and abs(res - ref) <= self.rel_eps * max(ref, 1e-30)
        return VerifyResult(bool(ok), res)

    def progress(self, state: State) -> float:
        return rel_residual(state["u"], state["b"], self.grid)

    def converged(self, state: State, it: int) -> bool:
        # fixed schedule (NPB MG runs exactly nit V-cycles)
        res = self.progress(state)
        if not np.isfinite(res):
            raise FloatingPointError("MG blow-up")
        return it >= self.n_iters

    # ------------------------------------------------------- batched recompute
    # ``b`` is read-only, so the hooks stack only the per-lane fields and
    # close over lane 0's right-hand side.
    supports_batched_step = True
    supports_lane_driver = True

    _CARRY = ("u", "r", "ec", "k")

    def batched_kernels(self):
        from ..core.regions import BatchedKernel

        s = self.init(0)
        b = jnp.asarray(s["b"])
        rows = {f: np.stack([s[f]] * 3) for f in self._CARRY}
        g, cs, fs = self.grid, self.coarse_sweeps, self.fine_sweeps
        args = tuple(rows[f] for f in self._CARRY)
        return (
            BatchedKernel("mg_cycle_batch",
                          lambda *vs: _mg_cycle_batch(*vs, b, np.float32(1.0), g, cs, fs),
                          args, {i: 0 for i in range(len(args))}),
            BatchedKernel("lap_batch", lambda ub: _lap_batch(ub, g),
                          (rows["u"],), {0: 0}),
        )

    def run_iteration_batch(self, states):
        b = jnp.asarray(states[0]["b"])
        stacked = [jnp.asarray(np.stack([s[f] for s in states])) for f in self._CARRY]
        new = _mg_cycle_batch(*stacked, b, np.float32(1.0), self.grid,
                              self.coarse_sweeps, self.fine_sweeps)
        new = [np.asarray(v) for v in new]
        out = []
        for i, s in enumerate(states):
            s = dict(s)
            for f, rows in zip(self._CARRY, new):
                s[f] = rows[i].astype(s[f].dtype, copy=False)
            out.append(s)
        return out

    def _rel_residuals_batch(self, states) -> list:
        """Per-lane true relative residual with one batched Laplacian
        dispatch; the subtraction and norms run in NumPy per contiguous row,
        exactly like the serial ``rel_residual``."""
        lap = np.asarray(_lap_batch(jnp.asarray(np.stack([s["u"] for s in states])), self.grid))
        out = []
        for i, s in enumerate(states):
            r = s["b"] - lap[i]
            nb = float(np.linalg.norm(s["b"]))
            out.append(float(np.linalg.norm(r)) / max(nb, 1e-30))
        return out

    def converged_batch(self, states, its):
        # the serial hook *always* computes the residual first (it raises on
        # blow-up even past the schedule), so no it-gated short-circuit here
        out: list = []
        for res, it in zip(self._rel_residuals_batch(states), its):
            if not np.isfinite(res):
                out.append(FloatingPointError("MG blow-up"))
            else:
                out.append(bool(it >= self.n_iters))
        return out

    def verify_batch(self, states):
        ref = self._golden_residual()
        return [
            VerifyResult(bool(np.isfinite(res) and abs(res - ref) <= self.rel_eps * max(ref, 1e-30)), res)
            for res in self._rel_residuals_batch(states)
        ]

    def advance_lanes(self, states, its, stop):
        from ..core.lane_driver import LaneSpec, cached_driver

        g, cs, fs, n_iters = self.grid, self.coarse_sweeps, self.fine_sweeps, self.n_iters
        # the fixed schedule makes convergence a pure counter; the only serial
        # host decision is the blow-up raise, which reads the float64 norm
        # ratio.  A lane whose residual max stays under this screen cannot
        # overflow any float32 summation order (g*g * screen^2 < f32 max), so
        # its serial residual is provably finite and the counter decision is
        # exact; anything else is handed back for serial reclassification.
        screen = np.float32(np.sqrt(3.0e38 / (g * g)))

        def step(consts, a):
            return _mg_cycle_core(a, consts["b"], consts["one"], g, cs, fs)

        def check(consts, a, it):
            lap = jax.vmap(lambda v: laplacian_apply(v, g))(a["u"])
            m = jnp.max(jnp.abs(consts["b"] - lap), axis=1)
            conv = it >= n_iters
            # NOT it-gated: the serial hook raises on blow-up even at the bound
            suspect = ~(jnp.isfinite(m) & (m <= screen))
            return conv, suspect

        key = ("mg", g, self.rel_eps, n_iters, self._seed, cs, fs)
        drv = cached_driver(key, lambda: LaneSpec(
            carry=self._CARRY,
            consts=lambda s0: {"b": s0["b"], "one": np.float32(1.0)},
            step=step, check=check,
        ))
        return drv.advance(states, its, stop)
