"""Shared numerics for the HPC app suite (2-D Laplacian, smoothers, grids)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def tree_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Sum over ``axis`` in one fixed pairwise order, written out as
    elementwise adds of halves (zero-padded to a power of two).

    A reduce leaves its order to the compiler, which picks it per shape and
    per backend — on a TPU a 1-D sum, a one-row sum and an eight-row sum of
    the same numbers all round differently — so a serial region and the same
    computation on a stack of lanes would disagree.  Adds of slices round
    the same for any batch shape on any backend."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    width = 1
    while width < n:
        width *= 2
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while width > 1:
        width //= 2
        x = x[..., :width] + x[..., width:]
    return x[..., 0]


@partial(jax.jit, static_argnames=("g",))
def laplacian_apply(x_flat: jnp.ndarray, g: int) -> jnp.ndarray:
    """y = A x for the 2-D 5-point Laplacian (Dirichlet) on a g x g grid.

    A is SPD with stencil [4, -1, -1, -1, -1]; matrix-free.
    """
    x = x_flat.reshape(g, g)
    y = 4.0 * x
    y = y - jnp.pad(x[1:, :], ((0, 1), (0, 0)))
    y = y - jnp.pad(x[:-1, :], ((1, 0), (0, 0)))
    y = y - jnp.pad(x[:, 1:], ((0, 0), (0, 1)))
    y = y - jnp.pad(x[:, :-1], ((0, 0), (1, 0)))
    return y.reshape(-1)


@partial(jax.jit, static_argnames=("g",))
def jacobi_sweep(u_flat: jnp.ndarray, b_flat: jnp.ndarray, g: int, omega: float = 0.8) -> jnp.ndarray:
    """One weighted-Jacobi smoothing sweep for A u = b."""
    u = u_flat.reshape(g, g)
    b = b_flat.reshape(g, g)
    nb = (
        jnp.pad(u[1:, :], ((0, 1), (0, 0)))
        + jnp.pad(u[:-1, :], ((1, 0), (0, 0)))
        + jnp.pad(u[:, 1:], ((0, 0), (0, 1)))
        + jnp.pad(u[:, :-1], ((0, 0), (1, 0)))
    )
    u_new = (b + nb) / 4.0
    return (u + omega * (u_new - u)).reshape(-1)


@partial(jax.jit, static_argnames=("g",))
def restrict(r_flat: jnp.ndarray, g: int) -> jnp.ndarray:
    """Full-weighting restriction g x g -> g/2 x g/2 (g even).

    The four children are summed in one fixed order, written out: a
    ``mean`` leaves the order to the compiler, which picks it per program
    and per backend, so a restriction fused into a larger (batched) program
    would round differently from this one alone."""
    r = r_flat.reshape(g, g)
    gc = g // 2
    r = r[: gc * 2, : gc * 2].reshape(gc, 2, gc, 2)
    s = ((r[:, 0, :, 0] + r[:, 0, :, 1]) + r[:, 1, :, 0]) + r[:, 1, :, 1]
    return (s / 4.0).reshape(-1)


@partial(jax.jit, static_argnames=("g",))
def prolong(e_flat: jnp.ndarray, g: int) -> jnp.ndarray:
    """Piecewise-constant prolongation g/2 x g/2 -> g x g."""
    gc = g // 2
    e = e_flat.reshape(gc, gc)
    out = jnp.repeat(jnp.repeat(e, 2, axis=0), 2, axis=1)
    return out.reshape(-1)


def rel_residual(u: np.ndarray, b: np.ndarray, g: int) -> float:
    r = np.asarray(b) - np.asarray(laplacian_apply(jnp.asarray(u), g))
    nb = float(np.linalg.norm(np.asarray(b)))
    return float(np.linalg.norm(r)) / max(nb, 1e-30)


def to_np(x) -> np.ndarray:
    return np.asarray(x)
