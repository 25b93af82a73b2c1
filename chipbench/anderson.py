"""The plain reference of the program's Anderson step, for the check.

A mix's ``accel`` object gives the window ``m`` and, where it sets them,
the damping ``beta`` and the relative regularisation ``reg``; what it
leaves out takes the paper's values in ``DEFAULTS`` (beta = 1: undamped
Anderson(m), x_acc = sum_j alpha_j G(x_j)).  Over a window of m + 1
iterates ``X``, map values ``G`` and residuals ``F`` (rows, oldest first)
the coefficients are

    alpha = argmin J(a),  J(a) = ||F^T a||^2 + reg * (tr B / h) * ||a||^2,
    subject to sum(a) = 1,  B = F F^T,  h = m + 1,

and the step is ``x_acc = alpha^T ((1 - beta) X + beta G)``.  This module
solves J by least squares on differences (a QR of a tall (n + h, h - 1)
matrix), where the program solves the KKT system of B: the same minimum,
by another road.  It imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

DEFAULTS = {"m": 5, "beta": 1.0, "reg": 1e-10}


def settings(accel: dict) -> dict:
    """``m``, ``beta`` and ``reg`` of a mix's ``accel`` object."""
    return {k: accel.get(k, v) for k, v in DEFAULTS.items()}


def _lam(F: np.ndarray, reg: float) -> np.ndarray:
    """reg * tr(B) / h, in F's precision."""
    return F.dtype.type(reg) * np.sum(F * F) / F.dtype.type(F.shape[0])


def alpha(F: np.ndarray, reg: float) -> np.ndarray:
    """The minimiser of J over sum(a) = 1, in F's precision.

    With a = (z, 1 - sum z): F^T a = f_h + D z (D's columns f_j - f_h)
    and ||a||^2 = ||z||^2 + (1 - sum z)^2, so z is the least-squares
    solution of [D; s I; -s 1^T] z = [-f_h; 0; -s], s = sqrt(lam)."""
    h, dt = F.shape[0], F.dtype.type
    if h == 1:
        return np.ones(1, F.dtype)
    s = np.sqrt(_lam(F, reg))
    M = np.concatenate([(F[:-1] - F[-1]).T, s * np.eye(h - 1, dtype=F.dtype),
                        -s * np.ones((1, h - 1), F.dtype)])
    rhs = np.concatenate([-F[-1], np.zeros(h - 1, F.dtype), [-s]])
    q, r = np.linalg.qr(M)
    z = np.linalg.solve(r, q.T @ rhs)
    return np.append(z, dt(1) - np.sum(z)).astype(F.dtype)


def combine(X: np.ndarray, G: np.ndarray, a: np.ndarray,
            beta: float) -> np.ndarray:
    """alpha^T ((1 - beta) X + beta G), in X's precision."""
    dt = X.dtype.type
    return a.astype(X.dtype) @ (dt(1 - beta) * X + dt(beta) * G)


def objective(F: np.ndarray, a: np.ndarray, reg: float) -> float:
    """J(a) in float64."""
    F = F.astype(np.float64, copy=False)
    v = a.astype(np.float64) @ F
    return float(v @ v + _lam(F, reg) * (a @ a))


def gap(X: np.ndarray, G: np.ndarray, F: np.ndarray, x_acc: np.ndarray,
        a: np.ndarray, accel: dict) -> float:
    """How far the program's step (``x_acc`` with its coefficients ``a``)
    lies from this reference's, in float64.  ``X``, ``G`` and ``x_acc``
    may hold a sample of the points (the same in each); ``F`` holds the
    window's whole residual rows.  The larger of

    * the combine: max |x_acc - a^T ((1 - beta) X + beta G)| over
      max |x_acc|;
    * the solve: J(a)'s relative excess over J's minimum, plus
      |sum(a) - 1|.  The excess is read as J(a - alpha) / J(alpha), equal
      to it where sum(a) = 1 (J is quadratic and its gradient at alpha is
      normal to the constraint), without the cancellation of two nearly
      equal sums: it stays well conditioned where alpha does not, for
      nearly collinear residuals.
    """
    c = settings(accel)
    a = np.asarray(a, np.float64)
    want = combine(X, G, a, c["beta"])
    step = float(np.max(np.abs(x_acc - want)) / np.max(np.abs(x_acc)))
    best = alpha(F, c["reg"])
    solve = (objective(F, a - best, c["reg"]) / objective(F, best, c["reg"])
             + abs(float(np.sum(a)) - 1.0))
    return max(step, solve)


class State:
    """This reference in the program's place (``AndersonState``'s
    ``push``, ``propose``, ``last_alpha``, ``snapshot`` and counters),
    computed in ``dtype``: in float32 it is the check's control for the
    Anderson step."""

    def __init__(self, accel: dict, dtype=np.float64):
        c = settings(accel)
        self.h, self.beta, self.reg = c["m"] + 1, c["beta"], c["reg"]
        self.dtype = np.dtype(dtype)
        self.rows = {"x": [], "g": [], "f": []}
        self.last_alpha = None
        self.n_fire = self.n_accept = self.n_reject = 0

    def push(self, x, g, f) -> None:
        for key, v in zip("xgf", (x, g, f)):
            rows = self.rows[key]
            rows.append(np.asarray(v).astype(self.dtype))
            del rows[:-self.h]

    def propose(self) -> np.ndarray:
        self.n_fire += 1
        X, G, F = (np.stack(self.rows[k]) for k in "xgf")
        a = alpha(F, self.reg)
        self.last_alpha = a.astype(np.float64)
        return combine(X, G, a, self.beta).astype(np.float64)

    def record_accept(self) -> None:
        self.n_accept += 1

    def record_reject(self) -> None:
        self.n_reject += 1

    def snapshot(self) -> dict:
        """The window's rows, oldest first, as ``AndersonState.snapshot``
        has them."""
        return {k.upper(): np.stack(v) for k, v in self.rows.items() if v}
