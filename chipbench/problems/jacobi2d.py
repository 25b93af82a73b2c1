"""Block Jacobi on the 2-D 5-point Dirichlet Laplacian.

The program's ``JacobiProblem`` draws ``b ~ N(0, 1)`` from the seed with
``numpy.random.default_rng(seed).standard_normal(g * g)``; the reference
below draws it again by the same recipe and imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

#: configuration keys that shrink a cell of this family for the CPU tests
TINY = {"grid": 32}


def build(config: dict, seed: int):
    """The program's problem for this configuration and seed."""
    from repro.problems import JacobiProblem

    g, p = config["grid"], config["n_workers"]
    if g % p:
        raise ValueError(f"grid {g} does not split into {p} equal row blocks")
    return JacobiProblem(grid=g, sweeps=config["sweeps"], seed=seed)


def prepare(problem) -> None:
    """Solve the DST reference once in set-up: every ``RunResult`` asks for
    its error norm, and the direct solve must not land in the window."""
    problem.exact_solution()


def points_per_update(config: dict) -> int:
    """Grid points recomputed by one applied block update (all sweeps)."""
    g = config["grid"]
    return g * g // config["n_workers"] * config["sweeps"]


def halo_sweeps_cost(config: dict):
    """(ops, bytes) of one block update at float64: per point and sweep 3
    neighbour adds, the add of b and the divide, then 3 for the fused
    squared change; the least traffic reads the block and b once, writes
    the block once and reads the two halo rows."""
    g = config["grid"]
    rows = g // config["n_workers"]
    ops = rows * g * (5 * config["sweeps"] + 3)
    return ops, 8 * (3 * rows * g + 2 * g)


KERNELS = {"_halo_sweeps": halo_sweeps_cost}


class Reference:
    """Plain numpy block Jacobi in ``dtype``."""

    def __init__(self, config: dict, seed: int, dtype=np.float64):
        self.g, self.sweeps, self.dtype = (config["grid"], config["sweeps"],
                                           dtype)
        g = self.g
        self.b = (np.random.default_rng(seed).standard_normal(g * g)
                  .astype(dtype).reshape(g, g))

    def _grid(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(x).astype(self.dtype, copy=False).reshape(self.g,
                                                                    self.g)

    def residual_norm(self, x: np.ndarray) -> float:
        """``||b - A x||_2`` with A = 4 on the diagonal, -1 to each of the
        four neighbours, zero outside the grid."""
        xg = self._grid(x)
        ax = 4 * xg
        ax[1:] -= xg[:-1]
        ax[:-1] -= xg[1:]
        ax[:, 1:] -= xg[:, :-1]
        ax[:, :-1] -= xg[:, 1:]
        return float(np.linalg.norm(self.b - ax))

    def block_step(self, x: np.ndarray, indices: np.ndarray) -> np.ndarray:
        """``sweeps`` Jacobi sweeps on the whole grid rows ``indices``
        covers, the rows above and below frozen at ``x``."""
        g, xg = self.g, self._grid(x)
        r0, r1 = int(indices[0]) // g, (int(indices[-1]) + 1) // g
        p = np.zeros((r1 - r0 + 2, g + 2), self.dtype)
        p[1:-1, 1:-1] = xg[r0:r1]
        if r0 > 0:
            p[0, 1:-1] = xg[r0 - 1]
        if r1 < g:
            p[-1, 1:-1] = xg[r1]
        for _ in range(self.sweeps):
            nb = p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
            p[1:-1, 1:-1] = (self.b[r0:r1] + nb) / 4
        return p[1:-1, 1:-1].ravel()
