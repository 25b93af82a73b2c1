"""Asynchronous value iteration on Garnet MDPs (paper §3.3.2, §5.2).

The Bellman optimality operator

    (T V)(s) = max_a [ R(s,a) + gamma * sum_b P(s'_b | s,a) V(s'_b) ]

is a gamma-contraction in the sup norm.  Garnet(S, A, b) random MDPs
(Archibald et al. 1995): each (s, a) has ``b`` distinct successor states
with stick-breaking probabilities and uniform(0,1) rewards.

Workers own state blocks; each update is the *full map component* evaluated
on the (stale) snapshot — the evaluation-level-perturbation mechanism that
lets Anderson survive asynchrony (paper §3.5).

A :class:`PolicyEvaluationProblem` (linear, T_pi V = r_pi + gamma P_pi V)
isolates the max-operator non-smoothness from the l2/linf norm mismatch.
A :class:`GridWorldMDP` provides a known-optimal-policy validation target.
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
    as_block_slice,
    restrict,
)

__all__ = [
    "GarnetMDP",
    "GridWorldMDP",
    "ValueIterationProblem",
    "PolicyEvaluationProblem",
]


@jax.jit
def _bellman(V, idx, probs, R, gamma):
    """(T V)(s) for all s: gather successors, expect, max over actions."""
    ev = jnp.einsum("sab,sab->sa", probs, V[idx])
    return jnp.max(R + gamma * ev, axis=1)


@jax.jit
def _bellman_policy(V, idx, probs, R, gamma, pi):
    """(T_pi V)(s): expectation under a fixed policy (linear map)."""
    ev = jnp.einsum("sab,sab->sa", probs, V[idx])
    q = R + gamma * ev
    return jnp.take_along_axis(q, pi[:, None], axis=1)[:, 0]


class GarnetMDP:
    """Garnet(S, A, b) random MDP (Archibald/McKinnon/Thomas 1995)."""

    def __init__(self, S: int = 500, A: int = 4, b: int = 5, gamma: float = 0.95,
                 seed: int = 0, sample: str = "exact"):
        self.S, self.A, self.b, self.gamma = S, A, b, gamma
        self._ctor = dict(S=S, A=A, b=b, gamma=gamma, seed=seed,
                          sample=sample)
        rng = np.random.default_rng(seed)
        if sample == "fast":
            # Vectorized successor draw for large-S benchmarks: one
            # rng.integers call instead of S*A rng.choice calls.  Unlike
            # the exact recipe the b successors per (s, a) may repeat
            # (probability O(b^2/S) — negligible at benchmark scales);
            # the default "exact" path is untouched so every fixed-seed
            # trajectory stays bit-identical.
            idx = rng.integers(0, S, size=(S, A, b), dtype=np.int64)
            idx = idx.astype(np.int32)
        elif sample == "exact":
            idx = np.empty((S, A, b), dtype=np.int32)
            for s in range(S):
                for a in range(A):
                    idx[s, a] = rng.choice(S, size=b, replace=False)
        else:
            raise ValueError(f"unknown sample mode {sample!r}")
        # Stick-breaking transition probabilities (standard Garnet recipe).
        cuts = np.sort(rng.uniform(size=(S, A, b - 1)), axis=-1)
        probs = np.diff(np.concatenate(
            [np.zeros((S, A, 1)), cuts, np.ones((S, A, 1))], axis=-1), axis=-1)
        self.idx = jnp.asarray(idx)
        self.probs = jnp.asarray(probs)
        self.R = jnp.asarray(rng.uniform(size=(S, A)))

    def bellman(self, V: np.ndarray) -> np.ndarray:
        return np.asarray(_bellman(jnp.asarray(V), self.idx, self.probs, self.R,
                                   self.gamma))

    def q_values(self, V: np.ndarray) -> np.ndarray:
        ev = jnp.einsum("sab,sab->sa", self.probs, jnp.asarray(V)[self.idx])
        return np.asarray(self.R + self.gamma * ev)

    def greedy_policy(self, V: np.ndarray) -> np.ndarray:
        return np.argmax(self.q_values(V), axis=1)


class GridWorldMDP(GarnetMDP):
    """Deterministic grid navigation with a goal — known-optimal validation.

    ``g x g`` grid, 4 actions (N/S/E/W), step reward -1, absorbing goal at
    the top-left corner with reward 0.  Optimal V*(s) = -gamma-discounted
    Manhattan distance; computed in closed form for the tests.
    """

    def __init__(self, g: int = 10, gamma: float = 0.95):
        self.S, self.A, self.b, self.gamma = g * g, 4, 1, gamma
        self._ctor = dict(g=g, gamma=gamma)
        self.g = g
        S = self.S
        idx = np.zeros((S, 4, 1), dtype=np.int32)
        R = np.full((S, 4), -1.0)
        for s in range(S):
            r, c = divmod(s, g)
            moves = [(max(r - 1, 0), c), (min(r + 1, g - 1), c),
                     (r, max(c - 1, 0)), (r, min(c + 1, g - 1))]
            for a, (nr, nc) in enumerate(moves):
                idx[s, a, 0] = nr * g + nc
        goal = 0
        idx[goal, :, 0] = goal
        R[goal, :] = 0.0
        self.idx = jnp.asarray(idx)
        self.probs = jnp.asarray(np.ones((S, 4, 1)))
        self.R = jnp.asarray(R)

    def optimal_values(self) -> np.ndarray:
        """Closed form: V*(s) = -(1 - gamma^d(s)) / (1 - gamma)."""
        g, gamma = self.g, self.gamma
        V = np.zeros(self.S)
        for s in range(self.S):
            r, c = divmod(s, g)
            d = r + c
            V[s] = -(1.0 - gamma**d) / (1.0 - gamma)
        return V


@jax.jit
def _vi_block_step(v, vold, idx, probs, R, gamma):
    """Fused state-block Bellman backup + block-local inf-norm residual.

    ``v`` is the (possibly remapped) successor-value vector — the block's
    dependency closure when the device plane ships dependency slices, or
    the full iterate.  Same einsum/max arithmetic as :func:`_bellman`.
    """
    ev = jnp.einsum("sab,sab->sa", probs, v[idx])
    tv = jnp.max(R + gamma * ev, axis=1)
    return tv, jnp.max(jnp.abs(tv - vold))


class _VIDevicePlan(DeviceBlockPlan):
    """Device-resident VI state block.

    The block's transition rows (idx, probs, R) stay resident; per
    dispatch the plan consumes the block's *dependency closure* — the
    unique successor states its backups read, remapped once at build time
    via ``searchsorted`` — instead of the full iterate.  Garnet blocks
    whose closure approaches the full state space (dep > n/2) fall back
    to shipping all of x; the fused kernel still saves the full-map
    restriction (the host path evaluates T V at every state and throws
    away all but the block).
    """

    def __init__(self, problem: "ValueIterationProblem", s0: int, s1: int,
                 mode: str):
        mdp = problem.mdp
        self._mode = mode
        self._gamma = mdp.gamma
        idx_blk = np.asarray(mdp.idx)[s0:s1]
        dep = np.unique(idx_blk)
        if dep.size > problem.n // 2:
            self.needs = [slice(0, problem.n)]
            self._remap = mdp.idx[s0:s1]
        else:
            self.needs = [dep.astype(np.int64)]
            self._remap = jnp.asarray(
                np.searchsorted(dep, idx_blk).astype(np.int32))
        self._probs = mdp.probs[s0:s1]
        self._R = mdp.R[s0:s1]
        self._blk = None

    def refresh(self, block_values: np.ndarray) -> None:
        self._blk = jnp.asarray(np.asarray(block_values, dtype=np.float64))

    def step(self, *need_vals: np.ndarray):
        v = jnp.asarray(need_vals[0])
        if self._mode == "jnp":
            tv, norm = _vi_block_step(v, self._blk, self._remap,
                                      self._probs, self._R, self._gamma)
        elif self._mode in ("pallas", "interpret"):
            from repro.kernels import kernel_ops

            tv, norm = kernel_ops.bellman_block(
                self._remap, self._probs, self._R, v, self._blk,
                gamma=self._gamma, interpret=self._mode == "interpret")
        elif self._mode == "ref":
            from repro.kernels.ref import ref_bellman_block

            tv, norm = ref_bellman_block(
                np.asarray(self._remap), np.asarray(self._probs),
                np.asarray(self._R), np.asarray(v), np.asarray(self._blk),
                gamma=self._gamma)
            tv = jnp.asarray(tv)
        else:
            raise ValueError(f"unknown device_plane mode {self._mode!r}")
        self._blk = tv
        return np.asarray(tv), float(norm)


def _policy_iteration(idx, probs, R, gamma: float,
                      tol: float = 1e-13) -> np.ndarray:
    """V* of a finite MDP by policy iteration, in numpy on the host.

    Each policy is evaluated by iterating ``V <- r_pi + gamma P_pi V`` on
    its sparse transition matrix (one action's successors: a quarter of a
    Bellman sweep's gathers, and no max) from the previous policy's values,
    to a sup-norm step below ``tol``.  A state switches action only when
    that gains more than ``2 tol / (1 - gamma)``, twice what the evaluation
    error can fake, so every switch truly improves and the loop ends in a
    few policies.  Value iteration from zero needs log(tol) / log(gamma)
    full Bellman sweeps (about 600 at gamma = 0.95): minutes at S = 2**20
    on a TPU, whose gathers are slow.
    """
    import scipy.sparse as sp

    S, _, b = idx.shape
    states = np.arange(S)
    rows = np.repeat(states, b)
    gain = 2.0 * tol / (1.0 - gamma)
    pi = np.zeros(S, dtype=np.int64)
    V = np.zeros(S)
    while True:
        P = sp.csr_matrix((probs[states, pi].ravel(),
                           (rows, idx[states, pi].ravel())), shape=(S, S))
        r = R[states, pi]
        for _ in range(200_000):
            V2 = r + gamma * (P @ V)
            done = np.max(np.abs(V2 - V)) < tol
            V = V2
            if done:
                break
        q = R + gamma * np.einsum("sab,sab->sa", probs, V[idx])
        best = np.argmax(q, axis=1)
        better = q[states, best] > q[states, pi] + gain
        if not better.any():
            return V
        pi = np.where(better, best, pi)


def _rebuild_vi(mdp_cls, mdp_kwargs):
    """Factory for multi-interpreter executors (see ``factory_spec``)."""
    return ValueIterationProblem(mdp_cls(**mdp_kwargs))


def _rebuild_policy_eval(mdp_cls, mdp_kwargs, policy):
    return PolicyEvaluationProblem(mdp_cls(**mdp_kwargs), policy=policy)


class ValueIterationProblem(FixedPointProblem):
    """V <- T V as a partitioned fixed-point problem."""

    def __init__(self, mdp: GarnetMDP):
        self.mdp = mdp
        self.n = mdp.S
        self._sol: Optional[np.ndarray] = None

    def initial(self) -> np.ndarray:
        return np.zeros(self.n)

    def full_map(self, x: np.ndarray) -> np.ndarray:
        return self.mdp.bellman(x)

    def block_update(self, x: np.ndarray, indices: np.ndarray) -> np.ndarray:
        # Each state's update IS the full map component at the stale snapshot
        # (evaluation-level perturbation, paper §3.5).  Contiguous state
        # blocks restrict via a slice (memcpy) instead of a gather.
        return restrict(self.full_map(x), indices)

    def residual_norm(self, x: np.ndarray) -> float:
        # linf: the Bellman operator contracts in the sup norm.
        return float(np.max(np.abs(self.residual(x))))

    def exact_solution(self) -> np.ndarray:
        if self._sol is None:
            mdp = self.mdp
            self._sol = _policy_iteration(
                np.asarray(mdp.idx), np.asarray(mdp.probs),
                np.asarray(mdp.R), mdp.gamma)
        return self._sol

    def device_block_plan(self, indices, mode: str):
        sl = as_block_slice(indices)
        if sl is None:
            return None  # scattered selection: host path
        return _VIDevicePlan(self, sl.start, sl.stop, mode)

    def factory_spec(self):
        ctor = getattr(self.mdp, "_ctor", None)
        if ctor is None:
            return None
        return (_rebuild_vi, (type(self.mdp), ctor), {})

    # --- structure ------------------------------------------------------ #
    def dependency_counts(self) -> np.ndarray:
        idx = np.asarray(self.mdp.idx).reshape(self.n, -1)
        return np.asarray(
            [len(np.unique(np.append(row, i))) for i, row in enumerate(idx)],
            dtype=np.int64,
        )

    def dependency_indices(self, i: int) -> np.ndarray:
        row = np.asarray(self.mdp.idx)[i].reshape(-1)
        return np.unique(np.append(row, i))


class PolicyEvaluationProblem(ValueIterationProblem):
    """Linear fixed point V = r_pi + gamma P_pi V (no max operator).

    Anderson applies cleanly via the Walker–Ni GMRES equivalence while the
    linf contraction remains — isolates non-smoothness from norm mismatch.
    """

    def __init__(self, mdp: GarnetMDP, policy: Optional[np.ndarray] = None):
        super().__init__(mdp)
        if policy is None:
            V_star = ValueIterationProblem(mdp).exact_solution()
            policy = mdp.greedy_policy(V_star)
        self.policy = jnp.asarray(policy.astype(np.int32))

    def factory_spec(self):
        ctor = getattr(self.mdp, "_ctor", None)
        if ctor is None:
            return None
        return (_rebuild_policy_eval,
                (type(self.mdp), ctor, np.asarray(self.policy)), {})

    def full_map(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(_bellman_policy(
            jnp.asarray(x), self.mdp.idx, self.mdp.probs, self.mdp.R,
            self.mdp.gamma, self.policy))

    def device_block_plan(self, indices, mode: str):
        # The fused kernel computes the max backup; the policy backup is a
        # different operator — host path only.
        return None

    def exact_solution(self) -> np.ndarray:
        if self._sol is None:
            # Direct linear solve of (I - gamma P_pi) V = r_pi.
            S = self.n
            idx = np.asarray(self.mdp.idx)
            probs = np.asarray(self.mdp.probs)
            R = np.asarray(self.mdp.R)
            pi = np.asarray(self.policy)
            P = np.zeros((S, S))
            r = np.empty(S)
            for s in range(S):
                a = pi[s]
                np.add.at(P[s], idx[s, a], probs[s, a])
                r[s] = R[s, a]
            self._sol = np.linalg.solve(np.eye(S) - self.mdp.gamma * P, r)
        return self._sol
