"""Block Jacobi for the 2-D Laplacian (paper §3.3.1, §5.1).

``A x = b`` with the standard 5-point stencil on a ``g × g`` grid
(Dirichlet), Jacobi splitting ``A = D - (L + U)``: the fixed-point map is
``G(x) = D^{-1}(b + (L+U) x)`` with iteration matrix spectral radius
``rho = cos(pi / (g+1))`` (< 1, l2-contraction).

Workers own contiguous row-blocks of the grid and perform ``sweeps`` local
Jacobi sweeps per update with the block boundary frozen at the snapshot
(the paper's multi-sweep local solve; effective only above ~90% block
internal coupling, Fig. 3).

The full-grid sweep is backed by either pure jnp or the Pallas
``jacobi_stencil`` kernel (see :mod:`repro.kernels.jacobi_stencil`).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fixedpoint import (
    DeviceBlockPlan,
    FixedPointProblem,
    restrict,
)

__all__ = ["JacobiProblem"]


@functools.partial(jax.jit, static_argnames=("g",))
def _full_sweep(x: jnp.ndarray, b: jnp.ndarray, g: int) -> jnp.ndarray:
    """One global Jacobi sweep: x' = (b + sum of 4 neighbors) / 4."""
    xg = x.reshape(g, g)
    p = jnp.pad(xg, 1)
    nb = p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
    return ((b.reshape(g, g) + nb) / 4.0).reshape(-1)


@functools.partial(jax.jit, static_argnames=("g", "r0", "r1", "sweeps"))
def _block_sweeps(
    x: jnp.ndarray, b: jnp.ndarray, g: int, r0: int, r1: int, sweeps: int
) -> jnp.ndarray:
    """``sweeps`` local Jacobi sweeps on grid rows [r0, r1).

    The halo rows (r0-1 and r1) are frozen at the snapshot values — this is
    the worker-local solve whose stale boundary produces the paper's
    iterate-level corruption mechanism.
    """
    xg = x.reshape(g, g)
    bg = b.reshape(g, g)[r0:r1]
    top = xg[r0 - 1] if r0 > 0 else jnp.zeros(g, x.dtype)
    bot = xg[r1] if r1 < g else jnp.zeros(g, x.dtype)
    blk = xg[r0:r1]

    def one(blk, _):
        p = jnp.concatenate([top[None], blk, bot[None]], axis=0)
        p = jnp.pad(p, ((0, 0), (1, 1)))
        nb = p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
        return (bg + nb) / 4.0, None

    blk, _ = jax.lax.scan(one, blk, None, length=sweeps)
    return blk.reshape(-1)


@functools.partial(jax.jit, static_argnames=("sweeps",))
def _halo_sweeps(blk: jnp.ndarray, top: jnp.ndarray, bot: jnp.ndarray,
                 bg: jnp.ndarray, sweeps: int):
    """:func:`_block_sweeps` against an already-resident block.

    Same arithmetic as ``_block_sweeps``'s scan body (so the device plane
    is bitwise-compatible with the host path on the same backend), but it
    consumes the (rows, g) block and two g-length halo rows directly
    instead of slicing the full iterate — the O(n) host array never
    crosses into the dispatch.  Also returns the fused block-local squared
    residual the data plane reports for free.
    """

    def one(b, _):
        p = jnp.concatenate([top[None], b, bot[None]], axis=0)
        p = jnp.pad(p, ((0, 0), (1, 1)))
        nb = p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
        return (bg + nb) / 4.0, None

    new, _ = jax.lax.scan(one, blk, None, length=sweeps)
    return new, jnp.sum((new - blk) ** 2)


class _JacobiDevicePlan(DeviceBlockPlan):
    """Device-resident whole-rows Jacobi block: per dispatch it consumes
    only the two g-length halo rows (r0-1 and r1) instead of the O(n)
    iterate — 32 KB instead of 32 MB at g=2048."""

    def __init__(self, problem: "JacobiProblem", r0: int, r1: int,
                 mode: str):
        g = problem.g
        self._g, self._r0, self._r1 = g, r0, r1
        self._rows = r1 - r0
        self._sweeps = problem.sweeps
        self._mode = mode
        self._bg = problem._b_j.reshape(g, g)[r0:r1]
        self._zeros = jnp.zeros(g, self._bg.dtype)
        self.needs = [s for s in (
            slice((r0 - 1) * g, r0 * g) if r0 > 0 else None,
            slice(r1 * g, (r1 + 1) * g) if r1 < g else None,
        ) if s is not None]
        self._blk = None
        # Multi-device hosts band-shard the resident block itself: each
        # local device owns rows/|devices| grid rows with an explicit
        # ppermute halo exchange per sweep (distributed/sharding.py).
        self._band_mesh = None
        if mode == "jnp" and len(jax.devices()) > 1:
            from repro.distributed.sharding import band_mesh

            self._band_mesh = band_mesh(self._rows)

    def refresh(self, block_values) -> None:
        self._blk = jnp.asarray(block_values, dtype=jnp.float64).reshape(
            self._rows, self._g)

    def step(self, *need_vals):
        halos = iter(need_vals)
        top = jnp.asarray(next(halos)) if self._r0 > 0 else self._zeros
        bot = jnp.asarray(next(halos)) if self._r1 < self._g else self._zeros
        if self._mode == "jnp":
            if self._band_mesh is not None:
                from repro.distributed.sharding import (
                    band_sharded_jacobi_sweeps)

                new, norm = band_sharded_jacobi_sweeps(
                    self._blk, top, bot, self._bg, sweeps=self._sweeps,
                    mesh=self._band_mesh)
            else:
                new, norm = _halo_sweeps(self._blk, top, bot, self._bg,
                                         self._sweeps)
        elif self._mode in ("pallas", "interpret"):
            from repro.kernels import kernel_ops

            new, norm = kernel_ops.jacobi_halo_sweeps(
                self._blk, top, bot, self._bg, sweeps=self._sweeps,
                interpret=self._mode == "interpret")
        elif self._mode == "ref":
            from repro.kernels.ref import ref_jacobi_halo_sweeps

            new, norm = ref_jacobi_halo_sweeps(
                np.asarray(self._blk), np.asarray(top), np.asarray(bot),
                np.asarray(self._bg), sweeps=self._sweeps)
            new = jnp.asarray(new)
        else:
            raise ValueError(f"unknown device_plane mode {self._mode!r}")
        self._blk = new
        return new.reshape(-1), float(norm)


@functools.partial(jax.jit, static_argnames=("g",))
def _apply_A(x: jnp.ndarray, g: int) -> jnp.ndarray:
    """y = A x for the 5-point Laplacian (diag 4, neighbors -1)."""
    xg = x.reshape(g, g)
    p = jnp.pad(xg, 1)
    nb = p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
    return (4.0 * xg - nb).reshape(-1)


@functools.partial(jax.jit, static_argnames=("g",))
def _residual_norm(x: jnp.ndarray, b: jnp.ndarray, g: int) -> jnp.ndarray:
    """||b - A x||_2 as one 0-d array: the stencil, the subtraction and the
    reduction fuse on the device, so only the scalar comes back to the
    host, not the O(n) product."""
    r = b - _apply_A(x, g)
    return jnp.sqrt(jnp.sum(r * r))


class JacobiProblem(FixedPointProblem):
    """2-D Laplacian block Jacobi with multi-sweep local solves."""

    def __init__(
        self,
        grid: int = 100,
        sweeps: int = 10,
        seed: int = 0,
        backend: str = "jnp",  # "jnp" | "pallas"
    ):
        self.g = grid
        self.n = grid * grid
        self.sweeps = sweeps
        self.backend = backend
        self.seed = seed
        rng = np.random.default_rng(seed)
        # Random right-hand side: the solution A^{-1} b is dominated by the
        # smooth (slow) Laplacian modes, which is the regime in which the
        # paper's 100x100 run needs ~3,240 x 10-sweep rounds to reach an
        # absolute residual of 1e-6.
        self._b = rng.standard_normal(self.n)
        self._b_j = jnp.asarray(self._b)
        self._x_star: Optional[np.ndarray] = None

    # ----------------------------------------------------------------- #
    def initial(self) -> np.ndarray:
        return np.zeros(self.n)

    def full_map(self, x: np.ndarray) -> np.ndarray:
        if self.backend == "pallas":
            from repro.kernels import jacobi_ops

            return np.asarray(jacobi_ops.jacobi_sweep(jnp.asarray(x), self._b_j, self.g))
        return np.asarray(_full_sweep(jnp.asarray(x), self._b_j, self.g))

    def block_update(self, x: np.ndarray, indices: np.ndarray) -> np.ndarray:
        r0, r1 = self._rows_of(indices)
        if r0 is not None:
            out = _block_sweeps(jnp.asarray(x), self._b_j, self.g, r0, r1, self.sweeps)
            return np.asarray(out)
        # Non-whole-rows selection (uniform/greedy): single-sweep restriction.
        return restrict(self.full_map(x), indices)

    def _rows_of(self, indices: np.ndarray) -> Tuple[Optional[int], Optional[int]]:
        """Detect a contiguous whole-rows block; else (None, None)."""
        i0, i1 = int(indices[0]), int(indices[-1]) + 1
        if i1 - i0 != len(indices) or i0 % self.g or i1 % self.g:
            return None, None
        if len(indices) > 1 and indices[1] - indices[0] != 1:
            return None, None
        return i0 // self.g, i1 // self.g

    def device_block_plan(self, indices, mode: str):
        r0, r1 = self._rows_of(np.asarray(indices))
        if r0 is None:
            return None  # not a whole-rows block: host path
        return _JacobiDevicePlan(self, r0, r1, mode)

    def factory_spec(self):
        return (JacobiProblem, (), dict(grid=self.g, sweeps=self.sweeps,
                                        seed=self.seed, backend=self.backend))

    # ----------------------------------------------------------------- #
    def residual(self, x: np.ndarray) -> np.ndarray:
        return self._b - np.asarray(_apply_A(jnp.asarray(x), self.g))

    def residual_norm(self, x: np.ndarray) -> float:
        # Absolute 2-norm, matching the paper's convergence criterion.
        return float(_residual_norm(jnp.asarray(x), self._b_j, self.g))

    def exact_solution(self) -> np.ndarray:
        if self._x_star is None:
            # The type-I sine transform diagonalizes the 5-point Dirichlet
            # Laplacian: a direct solve in O(n log n).  (A sparse LU would
            # not finish at a 4096 x 4096 grid, and every run's
            # RunResult.error_norm calls this.)
            from scipy.fft import dstn

            g = self.g
            lam = 2.0 - 2.0 * np.cos(np.pi * np.arange(1, g + 1) / (g + 1))
            bh = dstn(self._b.reshape(g, g), type=1, norm="ortho")
            self._x_star = dstn(bh / (lam[:, None] + lam[None, :]), type=1,
                                norm="ortho").reshape(-1)
        return self._x_star

    # --- structure (coupling, paper §3.5) ------------------------------ #
    def dependency_counts(self) -> np.ndarray:
        counts = np.full(self.n, 5, dtype=np.int64)  # self + 4 neighbors
        grid_idx = np.arange(self.n).reshape(self.g, self.g)
        counts[grid_idx[0, :]] -= 1
        counts[grid_idx[-1, :]] -= 1
        counts[grid_idx[:, 0]] -= 1
        counts[grid_idx[:, -1]] -= 1
        return counts

    def dependency_indices(self, i: int) -> np.ndarray:
        r, c = divmod(i, self.g)
        deps = [i]
        if r > 0:
            deps.append(i - self.g)
        if r < self.g - 1:
            deps.append(i + self.g)
        if c > 0:
            deps.append(i - 1)
        if c < self.g - 1:
            deps.append(i + 1)
        return np.asarray(deps)

    # --- analysis helpers ---------------------------------------------- #
    @property
    def spectral_radius(self) -> float:
        return float(np.cos(np.pi / (self.g + 1)))
