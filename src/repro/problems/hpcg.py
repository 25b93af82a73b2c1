"""Block Jacobi for HPCG's 3-D 27-point operator.

``A x = b`` on a ``g × g × g`` grid as the HPCG reference's
``GenerateProblem`` builds it: 26 on the diagonal, -1 to each of the up to
26 neighbours inside the grid (nothing lies outside it), and
``b = A · 1``, so the exact solution is all ones (``b`` is 0 at interior
points, 9 on faces, 15 on edges, 19 at corners).  The fixed-point map is
the Jacobi sweep ``x' = (b + sum of the 26 neighbours) / 26``.

Workers own z-slabs of whole ``g × g`` planes and perform ``sweeps`` local
sweeps per update with the two neighbouring planes frozen at the snapshot,
as :class:`~repro.problems.jacobi.JacobiProblem` does with grid rows.

The neighbour sum is computed as the separable 3 × 3 × 3 box sum (three
passes of three-term sums, along z, y and x) minus the centre: 7 adds per
point where the direct sum takes 26.  Its rounding differs from the
direct sum's in the last bits only.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fixedpoint import (
    DeviceBlockPlan,
    FixedPointProblem,
    restrict,
)

__all__ = ["HPCGProblem"]


def _neighbours(p, xp=jnp):
    """The sum of the 26 neighbours of each point of ``p[1:-1]``, where
    ``p`` is a (planes + 2, g, g) slab with its two halo planes at the
    ends, zero outside the grid in y and x.  ``xp`` is ``jnp``, or
    ``np`` for the ``"ref"`` device plane."""
    q = xp.pad(p, ((0, 0), (1, 1), (1, 1)))
    s = q[:-2] + q[1:-1] + q[2:]
    s = s[:, :-2] + s[:, 1:-1] + s[:, 2:]
    s = s[:, :, :-2] + s[:, :, 1:-1] + s[:, :, 2:]
    return s - p[1:-1]


def _sweep(blk, top, bot, bg, xp=jnp):
    """One Jacobi sweep of the (planes, g, g) slab ``blk`` against the
    frozen halo planes ``top`` and ``bot``."""
    p = xp.concatenate([top[None], blk, bot[None]], axis=0)
    return (bg + _neighbours(p, xp)) / 26.0


@functools.partial(jax.jit, static_argnames=("g",))
def _full_sweep27(x: jnp.ndarray, b: jnp.ndarray, g: int) -> jnp.ndarray:
    """One global Jacobi sweep: x' = (b + sum of 26 neighbours) / 26."""
    zero = jnp.zeros((g, g), x.dtype)
    return _sweep(x.reshape(g, g, g), zero, zero,
                  b.reshape(g, g, g)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("g", "z0", "z1", "sweeps"))
def _block_sweeps27(x: jnp.ndarray, b: jnp.ndarray, g: int, z0: int,
                    z1: int, sweeps: int) -> jnp.ndarray:
    """``sweeps`` local Jacobi sweeps on the planes [z0, z1), planes z0-1
    and z1 frozen at the snapshot (zero outside the grid)."""
    xg = x.reshape(g, g, g)
    bg = b.reshape(g, g, g)[z0:z1]
    top = xg[z0 - 1] if z0 > 0 else jnp.zeros((g, g), x.dtype)
    bot = xg[z1] if z1 < g else jnp.zeros((g, g), x.dtype)

    def one(blk, _):
        return _sweep(blk, top, bot, bg), None

    blk, _ = jax.lax.scan(one, xg[z0:z1], None, length=sweeps)
    return blk.reshape(-1)


@functools.partial(jax.jit, static_argnames=("sweeps",))
def _slab27_sweeps(blk: jnp.ndarray, top: jnp.ndarray, bot: jnp.ndarray,
                   bg: jnp.ndarray, sweeps: int):
    """:func:`_block_sweeps27` against an already-resident slab.

    Same scan body as ``_block_sweeps27`` (so the device plane is
    bitwise-compatible with the host path on the same backend), on the
    (planes, g, g) slab and two (g, g) halo planes; also returns the fused
    block-local squared change."""

    def one(b, _):
        return _sweep(b, top, bot, bg), None

    new, _ = jax.lax.scan(one, blk, None, length=sweeps)
    return new, jnp.sum((new - blk) ** 2)


def _ref_slab27_sweeps(blk, top, bot, bg, sweeps: int):
    """:func:`_slab27_sweeps` in numpy (the ``"ref"`` device plane)."""
    new = blk
    for _ in range(sweeps):
        new = _sweep(new, top, bot, bg, np)
    return new, float(np.sum((new - blk) ** 2))


class _Slab27Plan(DeviceBlockPlan):
    """Device-resident z-slab: per dispatch it consumes only the two
    g × g halo planes (z0-1 and z1), 86.5 KB each at g = 104, instead of
    the 9 MB iterate."""

    def __init__(self, problem: "HPCGProblem", z0: int, z1: int, mode: str):
        if mode in ("pallas", "interpret"):
            raise ValueError(
                f"device_plane={mode!r}: HPCGProblem has no Pallas kernel; "
                "its iterate is float64 and Mosaic lowers no 64-bit types, "
                "so the 27-point sweep runs as jnp (device_plane='jnp')")
        g = problem.g
        self._g, self._z0, self._z1 = g, z0, z1
        self._sweeps, self._mode = problem.sweeps, mode
        self._bg = problem._b_j.reshape(g, g, g)[z0:z1]
        self._zeros = jnp.zeros((g, g), self._bg.dtype)
        plane = g * g
        self.needs = [s for s in (
            slice((z0 - 1) * plane, z0 * plane) if z0 > 0 else None,
            slice(z1 * plane, (z1 + 1) * plane) if z1 < g else None,
        ) if s is not None]
        self._blk = None

    def refresh(self, block_values) -> None:
        self._blk = jnp.asarray(block_values, dtype=jnp.float64).reshape(
            self._z1 - self._z0, self._g, self._g)

    def step(self, *need_vals):
        g, halos = self._g, iter(need_vals)
        top = (jnp.asarray(next(halos).reshape(g, g)) if self._z0 > 0
               else self._zeros)
        bot = (jnp.asarray(next(halos).reshape(g, g)) if self._z1 < g
               else self._zeros)
        if self._mode == "jnp":
            new, norm = _slab27_sweeps(self._blk, top, bot, self._bg,
                                       self._sweeps)
        else:  # "ref"
            new, norm = _ref_slab27_sweeps(
                np.asarray(self._blk), np.asarray(top), np.asarray(bot),
                np.asarray(self._bg), self._sweeps)
            new = jnp.asarray(new)
        self._blk = new
        return new.reshape(-1), float(norm)


@functools.partial(jax.jit, static_argnames=("g",))
def _apply_A27(x: jnp.ndarray, g: int) -> jnp.ndarray:
    """y = A x for the 27-point operator (diag 26, neighbours -1)."""
    xg = x.reshape(g, g, g)
    p = jnp.pad(xg, ((1, 1), (0, 0), (0, 0)))
    return (26.0 * xg - _neighbours(p)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("g",))
def _residual_norm27(x: jnp.ndarray, b: jnp.ndarray, g: int) -> jnp.ndarray:
    """||b - A x||_2 as one 0-d array: the stencil, the subtraction and the
    reduction stay on the device, and only the scalar comes back."""
    r = b - _apply_A27(x, g)
    return jnp.sqrt(jnp.sum(r * r))


class HPCGProblem(FixedPointProblem):
    """HPCG's 27-point operator, block Jacobi on z-slabs."""

    def __init__(self, grid: int = 104, sweeps: int = 10):
        if grid < 2:
            raise ValueError(f"grid must be >= 2, got {grid}")
        self.g = grid
        self.n = grid ** 3
        self.sweeps = sweeps
        # Per axis, the coordinates a point's neighbourhood reaches inside
        # the grid: 3, or 2 on the boundary.  Their product counts the
        # neighbourhood, the point itself included, so the row's
        # off-diagonal count is that minus one and b = A 1 = 27 - product.
        c = np.full(grid, 3, dtype=np.int64)
        c[[0, -1]] = 2
        self._counts = (c[:, None, None] * c[None, :, None]
                        * c[None, None, :]).reshape(-1)
        self._b = (27.0 - self._counts).astype(np.float64)
        self._b_j = jnp.asarray(self._b)

    # ----------------------------------------------------------------- #
    def initial(self) -> np.ndarray:
        return np.zeros(self.n)

    def full_map(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(_full_sweep27(jnp.asarray(x), self._b_j, self.g))

    def block_update(self, x: np.ndarray, indices: np.ndarray) -> np.ndarray:
        z0, z1 = self._planes_of(indices)
        if z0 is not None:
            return np.asarray(_block_sweeps27(jnp.asarray(x), self._b_j,
                                              self.g, z0, z1, self.sweeps))
        # Not whole planes (uniform/greedy selection): one-sweep restriction.
        return restrict(self.full_map(x), indices)

    def _planes_of(self, indices: np.ndarray
                   ) -> Tuple[Optional[int], Optional[int]]:
        """A contiguous block of whole planes as (z0, z1); else (None, None)."""
        plane = self.g * self.g
        i0, i1 = int(indices[0]), int(indices[-1]) + 1
        if i1 - i0 != len(indices) or i0 % plane or i1 % plane:
            return None, None
        if len(indices) > 1 and indices[1] - indices[0] != 1:
            return None, None
        return i0 // plane, i1 // plane

    def device_block_plan(self, indices, mode: str):
        z0, z1 = self._planes_of(np.asarray(indices))
        if z0 is None:
            return None  # not whole planes: host path
        return _Slab27Plan(self, z0, z1, mode)

    def factory_spec(self):
        return (HPCGProblem, (), dict(grid=self.g, sweeps=self.sweeps))

    # ----------------------------------------------------------------- #
    def residual(self, x: np.ndarray) -> np.ndarray:
        return self._b - np.asarray(_apply_A27(jnp.asarray(x), self.g))

    def residual_norm(self, x: np.ndarray) -> float:
        # Absolute 2-norm, as the paper's convergence criterion.
        return float(_residual_norm27(jnp.asarray(x), self._b_j, self.g))

    def exact_solution(self) -> np.ndarray:
        return np.ones(self.n)

    # --- structure (coupling, paper §3.5) ------------------------------ #
    def dependency_counts(self) -> np.ndarray:
        return self._counts.copy()

    def dependency_indices(self, i: int) -> np.ndarray:
        g = self.g
        z, rem = divmod(i, g * g)
        y, x = divmod(rem, g)
        return np.asarray([
            (z + dz) * g * g + (y + dy) * g + (x + dx)
            for dz in (-1, 0, 1) for dy in (-1, 0, 1) for dx in (-1, 0, 1)
            if 0 <= z + dz < g and 0 <= y + dy < g and 0 <= x + dx < g])
