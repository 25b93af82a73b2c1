"""Anderson acceleration / DIIS with the paper's residual-decrease safeguard.

Implements the coordinator-level accelerator of paper §3.2/§3.4: keep a window
of the last ``m+1`` iterates ``x_j``, their map values ``g_j = G(x_j)`` and
residuals ``f_j`` (default ``g_j - x_j``; SCF overrides with the DIIS
commutator ``F P S - S P F``), and solve the paper's Eq. (2)

    min_alpha || sum_j alpha_j f_j ||_2   s.t.  sum_j alpha_j = 1,

via the classic DIIS/KKT system with relative Tikhonov regularization.  The
extrapolated iterate is

    x_acc = sum_j alpha_j * ((1 - beta) * x_j + beta * g_j)

so ``beta=1`` is undamped Anderson(m) (x_acc = sum alpha_j G(x_j), the paper's
form after Eq. (2)) and ``beta=0`` is classic iterate-space DIIS mixing.

The safeguard (paper Eq. 5) is applied by the *caller* (the coordinator in
``repro.core.engine``), because it requires an extra residual evaluation:
accept ``x_acc`` only if ``res(x_acc) < res(x)``; otherwise fall back to the
un-extrapolated map value ``G(x)``.  Without it, Anderson on value iteration
diverges catastrophically (residual -> 1e68 in the paper; reproduced in
``tests/test_anderson.py``).

Hot-path layout (coordinator cost model, see docs/architecture.md)
------------------------------------------------------------------
The window lives in preallocated sliding buffers of shape ``(2(m+1), n)``:
``push`` writes one row per buffer (three O(n) row writes, the residual
``g - x`` computed straight into its row, no temporaries) and compacts the
window back to the front only on wrap, so the live rows are *always* one
contiguous oldest-first block and ``propose`` never restacks ``X/G/F``.

The DIIS Gram matrix ``B = F Fᵀ`` has two build strategies
(``AndersonConfig.gram``):

* ``"exact"`` (default): one ``(h, n) x (n, h)`` GEMM on the contiguous
  window view per fire.  This reproduces the legacy deque implementation
  *bit for bit* (same values, same layout, same BLAS call), which is what
  the fixed-seed golden trajectories in ``tests/test_hotpath_goldens.py``
  pin down.
* ``"incremental"``: one rank-1 row/column GEMV update per ``push`` (evict
  shifts the window-ordered ``B`` up-left), making ``propose`` O(h·n)
  instead of O(h²·n).  Mathematically identical, but BLAS GEMV and GEMM
  round differently in the last ulp, so this mode is opt-in: bit-level
  trajectory reproducibility is traded for the cheaper fire.

The final combine dispatches to the fused Pallas kernel
(:func:`repro.kernels.ops.anderson_mix`) when the state is large enough
(``AndersonConfig.mix_kernel_n``; auto-enabled on TPU only, for a window
dtype Mosaic lowers), and otherwise
uses BLAS on the window views with ``beta``-0/1 fast paths.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

__all__ = ["AndersonConfig", "AndersonState", "diis_solve"]

#: auto-dispatch threshold for the fused Pallas combine on TPU backends
_MIX_KERNEL_AUTO_N = 1 << 18


def _mix_kernel_auto(n: int, dtype) -> bool:
    """Auto mode's choice of the fused Pallas combine for an (h, n) window.

    The kernel pays off at ``n >= 2**18`` where Mosaic compiles it: a TPU
    backend and a dtype it lowers (no 64-bit type).  Off-TPU it would run
    in interpret mode, fine for parity tests but never for the hot path.
    The float64 window checks its dtype first and keeps the numpy GEMV
    without importing JAX.
    """
    if n < _MIX_KERNEL_AUTO_N or np.dtype(dtype).itemsize >= 8:
        return False
    import jax

    return jax.default_backend() == "tpu"


@dataclass
class AndersonConfig:
    """Configuration of the coordinator-level accelerator.

    Attributes:
      m: window size; the history keeps the last ``m + 1`` (x, g, f) triples.
      beta: mixing parameter in [0, 1]; 1.0 = undamped AA-II / Anderson form.
      reg: relative Tikhonov regularization of the DIIS normal matrix; guards
        against the near-rank-deficient histories produced by asynchronous
        composite iterates (paper §3.4).
      safeguard: enforce paper Eq. 5 (performed by the caller).
      restart_on_reject: drop the history window when the safeguard rejects
        an extrapolation (fresh subspace after iterate corruption).
      max_coeff: conditioning guard — reject proposals with ||alpha||_1
        above this (used in addition to, not instead of, Eq. 5).
      gram: ``"exact"`` rebuilds ``B = F Fᵀ`` from the contiguous window per
        fire (bit-identical to the legacy implementation); ``"incremental"``
        maintains ``B`` with one rank-1 row/column update per push (O(h·n)
        fires, last-ulp differences — see the module docstring).
      mix_kernel_n: state size at or above which the extrapolation combine
        runs through the fused Pallas kernel
        (:func:`repro.kernels.ops.anderson_mix`).  ``None`` (default) means
        auto: enabled at ``n >= 2**18`` on TPU backends for a window dtype
        Mosaic lowers, so never for the float64 window and never in
        interpret mode.  Set an explicit int to force the kernel (tests use
        this); on a TPU backend a float64 window then raises ``ValueError``.
    """

    m: int = 5
    beta: float = 1.0
    reg: float = 1e-10
    safeguard: bool = True
    restart_on_reject: bool = False
    max_coeff: float = 1e8
    gram: str = "exact"
    mix_kernel_n: Optional[int] = None

    def __post_init__(self) -> None:
        if self.gram not in ("exact", "incremental"):
            raise ValueError(
                f"gram must be 'exact' or 'incremental', got {self.gram!r}")


def _solve_kkt(B: np.ndarray, reg: float) -> np.ndarray:
    """Solve the DIIS KKT system given the Gram matrix ``B = F Fᵀ``."""
    h = B.shape[0]
    scale = max(np.trace(B) / h, 1e-300)
    # KKT system [[B + reg*I, 1], [1^T, 0]] [alpha; lam] = [0; 1]
    A = np.zeros((h + 1, h + 1))
    A[:h, :h] = B + (reg * scale) * np.eye(h)
    A[:h, h] = 1.0
    A[h, :h] = 1.0
    rhs = np.zeros(h + 1)
    rhs[h] = 1.0
    try:
        sol = np.linalg.solve(A, rhs)
    except np.linalg.LinAlgError:
        sol, *_ = np.linalg.lstsq(A, rhs, rcond=None)
    return sol[:h]


def diis_solve(F: np.ndarray, reg: float) -> np.ndarray:
    """Solve Eq. (2): min ||alpha @ F|| s.t. sum(alpha) = 1.

    Args:
      F: (h, n) residual history, oldest first.
      reg: relative Tikhonov regularization.

    Returns:
      alpha: (h,) simplex-constrained coefficients.
    """
    return _solve_kkt(F @ F.T, reg)  # (h, h) Gram: the classic DIIS "B"


@dataclass
class AndersonState:
    """Mutable coordinator-side accelerator state (history window).

    The window is stored in preallocated sliding buffers (see the module
    docstring); ``xs``/``gs``/``fs`` remain available as list-of-rows views
    for introspection and tests, but the hot path never materializes them.
    """

    config: AndersonConfig
    n_accept: int = 0
    n_reject: int = 0
    n_fire: int = 0
    last_alpha: Optional[np.ndarray] = None
    # --- sliding-window storage (lazily allocated on first push) -------- #
    _X: Optional[np.ndarray] = field(default=None, repr=False)
    _G: Optional[np.ndarray] = field(default=None, repr=False)
    _F: Optional[np.ndarray] = field(default=None, repr=False)
    _B: Optional[np.ndarray] = field(default=None, repr=False)
    _scr1: Optional[np.ndarray] = field(default=None, repr=False)
    _scr2: Optional[np.ndarray] = field(default=None, repr=False)
    _start: int = 0
    _len: int = 0

    # ----------------------------------------------------------------- #
    # Window storage
    # ----------------------------------------------------------------- #
    @property
    def depth(self) -> int:
        return self._len

    @property
    def xs(self) -> List[np.ndarray]:
        """Oldest-first iterate history (row views, do not mutate)."""
        return list(self._window(self._X)) if self._len else []

    @property
    def gs(self) -> List[np.ndarray]:
        return list(self._window(self._G)) if self._len else []

    @property
    def fs(self) -> List[np.ndarray]:
        return list(self._window(self._F)) if self._len else []

    def _window(self, buf: np.ndarray) -> np.ndarray:
        """Contiguous oldest-first (h, n) view of the live window."""
        return buf[self._start:self._start + self._len]

    def _alloc(self, n: int) -> None:
        cap = 2 * (self.config.m + 1)
        self._X = np.empty((cap, n))
        self._G = np.empty((cap, n))
        self._F = np.empty((cap, n))
        self._scr1 = np.empty((self.config.m + 1, n))
        self._scr2 = np.empty((self.config.m + 1, n))
        if self.config.gram == "incremental":
            self._B = np.zeros((self.config.m + 1, self.config.m + 1))
        self._start = self._len = 0

    def push(
        self, x: np.ndarray, g: np.ndarray, f: Optional[np.ndarray] = None
    ) -> None:
        """Record an (iterate, map value, residual) triple; keeps last m+1.

        ``f`` defaults to ``g - x`` (Anderson residual); SCF passes the DIIS
        commutator instead.  Cost: three O(n) row writes (the default
        residual is subtracted directly into its row — no temporary) plus,
        in ``gram="incremental"`` mode, one (h, n) GEMV.
        """
        x = np.asarray(x, dtype=np.float64)
        g = np.asarray(g, dtype=np.float64)
        if x.ndim != 1 or g.shape != x.shape:
            raise ValueError(f"expected matching 1-D x/g, got {x.shape} "
                             f"and {g.shape}")
        if self._X is None or self._X.shape[1] != x.shape[0]:
            self._alloc(x.shape[0])
        m1 = self.config.m + 1
        if self._len == m1:  # evict the oldest triple
            self._start += 1
            self._len -= 1
            if self._B is not None:  # shift the window-ordered Gram up-left
                self._B[:-1, :-1] = self._B[1:, 1:].copy()
        if self._start + self._len == self._X.shape[0]:  # wrap: compact
            h = self._len
            for buf in (self._X, self._G, self._F):
                # rows never overlap: start == cap - h >= m + 2 > h
                buf[:h] = buf[self._start:self._start + h]
            self._start = 0
        row = self._start + self._len
        self._X[row] = x
        self._G[row] = g
        if f is None:
            np.subtract(g, x, out=self._F[row])
        else:
            self._F[row] = np.asarray(f, np.float64)
        self._len += 1
        if self._B is not None:  # rank-1 row/column update with the new f
            h = self._len
            r = self._window(self._F) @ self._F[row]
            self._B[h - 1, :h] = r
            self._B[:h, h - 1] = r

    def reset(self) -> None:
        self._start = self._len = 0
        self.last_alpha = None

    # ----------------------------------------------------------------- #
    # Checkpoint/restore (repro.recover)
    # ----------------------------------------------------------------- #
    def snapshot(self) -> dict:
        """Checkpointable state: counters plus the live window, oldest
        first.  Scratch buffers and the wrap position are not state — a
        restored window compacted to the front is numerically identical
        (every Gram/combine operates on contiguous window views)."""
        out = {
            "n_accept": int(self.n_accept),
            "n_reject": int(self.n_reject),
            "n_fire": int(self.n_fire),
            "last_alpha": (None if self.last_alpha is None
                           else np.asarray(self.last_alpha,
                                           dtype=np.float64).copy()),
        }
        if self._len:
            out["X"] = self._window(self._X).copy()
            out["G"] = self._window(self._G).copy()
            out["F"] = self._window(self._F).copy()
        return out

    def restore(self, snap: dict) -> None:
        """Inverse of :meth:`snapshot`.

        The window rows land compacted at the front of fresh buffers; the
        incremental Gram is rebuilt by replaying the per-row rank-1
        updates (each entry is the same full-length BLAS dot product the
        uninterrupted run computed, so subsequent fires stay on the same
        float sequence in both Gram modes).
        """
        self.n_accept = int(snap["n_accept"])
        self.n_reject = int(snap["n_reject"])
        self.n_fire = int(snap["n_fire"])
        la = snap.get("last_alpha")
        self.last_alpha = None if la is None else np.asarray(la, np.float64)
        X = snap.get("X")
        if X is None:
            self._start = self._len = 0
            return
        X = np.asarray(X, np.float64)
        h, n = X.shape
        self._alloc(n)
        self._X[:h] = X
        self._G[:h] = np.asarray(snap["G"], np.float64)
        self._F[:h] = np.asarray(snap["F"], np.float64)
        self._start, self._len = 0, h
        if self._B is not None:
            for k in range(h):
                r = self._F[:k + 1] @ self._F[k]
                self._B[k, :k + 1] = r
                self._B[:k + 1, k] = r

    # ----------------------------------------------------------------- #
    # Extrapolation
    # ----------------------------------------------------------------- #
    def propose(self) -> Optional[np.ndarray]:
        """Extrapolate from the current window; None if degenerate."""
        self.n_fire += 1
        if self._len == 0:
            return None
        beta = self.config.beta
        X = self._window(self._X)
        G = self._window(self._G)
        if self._len == 1:
            return (1.0 - beta) * X[0] + beta * G[0]
        h = self._len
        if self._B is not None:
            B = self._B[:h, :h]
        else:
            F = self._window(self._F)
            B = F @ F.T
        alpha = _solve_kkt(B, self.config.reg)
        if not np.all(np.isfinite(alpha)) or np.abs(alpha).sum() > self.config.max_coeff:
            return None
        self.last_alpha = alpha
        x_acc = self._combine(X, G, alpha, beta)
        if not np.all(np.isfinite(x_acc)):
            return None
        return x_acc

    def _combine(self, X: np.ndarray, G: np.ndarray, alpha: np.ndarray,
                 beta: float) -> np.ndarray:
        """x_acc = alpha @ ((1 - beta) * X + beta * G), fused.

        Dispatches to the Pallas kernel above the configured size threshold;
        otherwise one GEMV on the window views (with beta = 0/1 fast paths)
        — no (h, n) temporaries beyond the preallocated scratch rows.
        """
        n = X.shape[1]
        kn = self.config.mix_kernel_n
        use_kernel = n >= kn if kn is not None else _mix_kernel_auto(n, X.dtype)
        if use_kernel:
            from repro.kernels import ops  # lazy: keeps numpy-only use light

            return np.asarray(
                ops.anderson_mix(X, G, np.asarray(alpha), beta=float(beta)))
        if beta == 1.0:
            return alpha @ G
        if beta == 0.0:
            return alpha @ X
        h = X.shape[0]
        s1 = self._scr1[:h]
        s2 = self._scr2[:h]
        np.multiply(X, 1.0 - beta, out=s1)
        np.multiply(G, beta, out=s2)
        np.add(s1, s2, out=s1)
        return alpha @ s1

    # ----------------------------------------------------------------- #
    def record_accept(self) -> None:
        self.n_accept += 1

    def record_reject(self) -> None:
        self.n_reject += 1
        if self.config.restart_on_reject:
            self.reset()
