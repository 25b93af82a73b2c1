"""Block Jacobi on HPCG's 3-D 27-point operator.

The HPCG reference's ``GenerateProblem``: on a grid x grid x grid mesh, 26
on the diagonal and -1 to each of the up to 26 neighbours inside the grid,
``b = A 1`` (so the solution is all ones), x0 = 0.  The data is fixed by
the grid: the seed draws nothing.  The reference below builds ``A`` and
``b`` from that spec with an explicit sum over the 26 neighbours of a
zero-padded grid, and imports nothing of the program.
"""

from __future__ import annotations

import itertools

import numpy as np

#: configuration keys that shrink a cell of this family for the CPU tests
TINY = {"grid": 16}

#: the 26 neighbour offsets into a grid padded by one on every side
_OFFSETS = [d for d in itertools.product((0, 1, 2), repeat=3)
            if d != (1, 1, 1)]


def build(config: dict, seed: int):
    """The program's problem for this configuration (the seed draws
    nothing: HPCG's data is fixed by the grid)."""
    from repro.problems import HPCGProblem

    g, p = config["grid"], config["n_workers"]
    if g % p:
        raise ValueError(f"grid {g} does not split into {p} equal z-slabs")
    return HPCGProblem(grid=g, sweeps=config["sweeps"])


def prepare(problem) -> None:
    """Nothing to solve in set-up: the exact solution is all ones."""


def points_per_update(config: dict) -> int:
    """Grid points recomputed by one applied block update (all sweeps)."""
    return config["grid"] ** 3 // config["n_workers"] * config["sweeps"]


def slab27_sweeps_cost(config: dict):
    """(ops, bytes) of one block update at float64: per point and sweep 26
    neighbour adds, the add of b and the divide, then 3 for the fused
    squared change; the least traffic reads the slab and b once, writes
    the slab once and reads the two halo planes."""
    g = config["grid"]
    planes = g // config["n_workers"]
    ops = planes * g * g * (28 * config["sweeps"] + 3)
    return ops, 8 * (3 * planes * g * g + 2 * g * g)


def residual_norm27_cost(config: dict):
    """(ops, bytes) of one ``||b - A x||`` at float64: per point 26
    neighbour adds, the diagonal's multiply and its subtraction (the
    stencil), the subtraction from b, and the square and sum; the least
    traffic reads x and b once."""
    n = config["grid"] ** 3
    return n * 31, 8 * 2 * n


KERNELS = {"_slab27_sweeps": slab27_sweeps_cost,
           "_residual_norm27": residual_norm27_cost}


class Reference:
    """Plain numpy block Jacobi in ``dtype``."""

    def __init__(self, config: dict, seed: int, dtype=np.float64):
        self.g, self.sweeps, self.dtype = (config["grid"], config["sweeps"],
                                           dtype)
        ones = np.ones((self.g,) * 3, dtype)
        self.b = 26 * ones - self._neighbours(np.pad(ones, 1))

    def _neighbours(self, p: np.ndarray) -> np.ndarray:
        """The sum of the 26 neighbours of each point of ``p[1:-1, 1:-1,
        1:-1]``, one shifted view at a time."""
        k, g = p.shape[0] - 2, self.g
        out = np.zeros((k, g, g), self.dtype)
        for dz, dy, dx in _OFFSETS:
            out += p[dz:dz + k, dy:dy + g, dx:dx + g]
        return out

    def _grid(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(x).astype(self.dtype, copy=False).reshape(
            (self.g,) * 3)

    def residual_norm(self, x: np.ndarray) -> float:
        """``||b - A x||_2``, zero outside the grid."""
        xg = self._grid(x)
        ax = 26 * xg - self._neighbours(np.pad(xg, 1))
        return float(np.linalg.norm((self.b - ax).ravel()))

    def block_step(self, x: np.ndarray, indices: np.ndarray) -> np.ndarray:
        """``sweeps`` Jacobi sweeps on the whole planes ``indices`` covers,
        the planes below and above frozen at ``x``."""
        g, xg = self.g, self._grid(x)
        z0, z1 = int(indices[0]) // (g * g), (int(indices[-1]) + 1) // (g * g)
        p = np.zeros((z1 - z0 + 2, g + 2, g + 2), self.dtype)
        p[1:-1, 1:-1, 1:-1] = xg[z0:z1]
        if z0 > 0:
            p[0, 1:-1, 1:-1] = xg[z0 - 1]
        if z1 < g:
            p[-1, 1:-1, 1:-1] = xg[z1]
        for _ in range(self.sweeps):
            p[1:-1, 1:-1, 1:-1] = (self.b[z0:z1] + self._neighbours(p)) / 26
        return p[1:-1, 1:-1, 1:-1].ravel()
