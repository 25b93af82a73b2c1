"""The paper's three testbeds: correctness against independent references."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AndersonConfig,
    RunConfig,
    block_internal_coupling,
    coupling_density,
    run_fixed_point,
)
from repro.problems import (
    GarnetMDP,
    GridWorldMDP,
    JacobiProblem,
    PolicyEvaluationProblem,
    PPPChain,
    SCFProblem,
    ValueIterationProblem,
)


# --------------------------------------------------------------------- #
# Jacobi
# --------------------------------------------------------------------- #
class TestJacobi:
    def test_full_map_is_jacobi_sweep(self):
        p = JacobiProblem(grid=8, seed=1)
        x = np.random.default_rng(0).standard_normal(p.n)
        g = p.full_map(x)
        # manual dense check
        xg = x.reshape(8, 8)
        pad = np.pad(xg, 1)
        nb = pad[:-2, 1:-1] + pad[2:, 1:-1] + pad[1:-1, :-2] + pad[1:-1, 2:]
        expect = (p._b.reshape(8, 8) + nb) / 4.0
        np.testing.assert_allclose(g, expect.reshape(-1), rtol=1e-12)

    def test_solves_linear_system(self):
        p = JacobiProblem(grid=16, sweeps=5)
        r = run_fixed_point(p, RunConfig(mode="sync", tol=1e-9, max_updates=2_000_000,
                                         compute_time=1e-4))
        assert r.converged
        np.testing.assert_allclose(r.x, p.exact_solution(), atol=1e-6)

    def test_block_sweeps_fixed_point_consistency(self):
        """At the exact solution, block sweeps must be a no-op."""
        p = JacobiProblem(grid=10, sweeps=7)
        x = p.exact_solution()
        blocks = p.default_blocks(2)
        for idx in blocks:
            vals = p.block_update(x, idx)
            np.testing.assert_allclose(vals, x[idx], atol=1e-9)

    def test_multisweep_matches_repeated_restriction(self):
        """One block sweep with frozen halo == full sweep restricted, when
        the rest of the state is frozen."""
        p = JacobiProblem(grid=10, sweeps=1)
        rng = np.random.default_rng(2)
        x = rng.standard_normal(p.n)
        idx = p.default_blocks(2)[0]
        vals = p.block_update(x, idx)
        np.testing.assert_allclose(vals, p.full_map(x)[idx], rtol=1e-12)

    def test_spectral_radius(self):
        p = JacobiProblem(grid=100)
        assert p.spectral_radius == pytest.approx(np.cos(np.pi / 101))

    def test_coupling_density_is_low(self):
        p = JacobiProblem(grid=30)
        assert coupling_density(p) < 0.01  # O(1/N)

    def test_block_internal_coupling_increases_with_rows(self):
        p = JacobiProblem(grid=30)
        c_many_blocks = block_internal_coupling(p, p.default_blocks(15))  # 2 rows
        c_few_blocks = block_internal_coupling(p, p.default_blocks(3))  # 10 rows
        assert c_few_blocks > 0.9
        assert c_many_blocks < c_few_blocks

    def test_residual_is_b_minus_Ax(self):
        p = JacobiProblem(grid=6)
        assert p.residual_norm(p.exact_solution()) < 1e-8

    @pytest.mark.parametrize("x_kind", ["zeros", "random", "exact"])
    @pytest.mark.parametrize("grid", [16, 17, 64])
    def test_residual_norm_is_one_device_scalar(self, grid, x_kind):
        """The fused on-device norm equals the host norm of the residual
        vector and a plain numpy 5-point stencil, and only a 0-d float64
        leaves the jitted function."""
        import jax

        from repro.problems.jacobi import _residual_norm

        p = JacobiProblem(grid=grid, seed=3)
        x = {"zeros": np.zeros(p.n),
             "random": np.random.default_rng(grid).standard_normal(p.n),
             "exact": p.exact_solution()}[x_kind]
        got = p.residual_norm(x)
        assert type(got) is float

        xg = x.reshape(grid, grid)
        ax = 4 * xg
        ax[1:] -= xg[:-1]
        ax[:-1] -= xg[1:]
        ax[:, 1:] -= xg[:, :-1]
        ax[:, :-1] -= xg[:, 1:]
        plain = np.linalg.norm(p._b - ax.ravel())
        # At the exact solution the residual is round-off of b - A x whose
        # digits follow the stencil's summation order, so gaps are taken
        # against the larger of the norm and ||b|| (the norm at x = 0).
        scale = max(plain, np.linalg.norm(p._b))
        assert abs(got - np.linalg.norm(p.residual(x))) <= 1e-13 * scale
        assert abs(got - plain) <= 1e-13 * scale

        out = jax.eval_shape(
            lambda v: _residual_norm(v, p._b_j, grid),
            jax.ShapeDtypeStruct((p.n,), np.float64))
        assert out.shape == () and out.dtype == np.float64


# --------------------------------------------------------------------- #
# Value iteration
# --------------------------------------------------------------------- #
class TestValueIteration:
    def test_bellman_is_sup_norm_contraction(self):
        mdp = GarnetMDP(S=60, A=3, b=4, gamma=0.9, seed=0)
        rng = np.random.default_rng(1)
        for _ in range(5):
            u, v = rng.standard_normal((2, 60)) * 10
            lhs = np.max(np.abs(mdp.bellman(u) - mdp.bellman(v)))
            assert lhs <= 0.9 * np.max(np.abs(u - v)) + 1e-12

    @given(seed=st.integers(0, 1000), gamma=st.sampled_from([0.8, 0.9, 0.95]))
    @settings(max_examples=8, deadline=None)
    def test_contraction_property(self, seed, gamma):
        mdp = GarnetMDP(S=30, A=2, b=3, gamma=gamma, seed=seed)
        rng = np.random.default_rng(seed + 1)
        u, v = rng.standard_normal((2, 30)) * 5
        lhs = np.max(np.abs(mdp.bellman(u) - mdp.bellman(v)))
        assert lhs <= gamma * np.max(np.abs(u - v)) + 1e-12

    def test_gridworld_closed_form(self):
        mdp = GridWorldMDP(g=6, gamma=0.9)
        prob = ValueIterationProblem(mdp)
        r = run_fixed_point(prob, RunConfig(mode="sync", tol=1e-12,
                                            max_updates=200000, compute_time=1e-4))
        np.testing.assert_allclose(r.x, mdp.optimal_values(), atol=1e-9)

    def test_async_converges_to_optimal(self):
        mdp = GarnetMDP(S=80, A=4, b=5, gamma=0.9, seed=2)
        prob = ValueIterationProblem(mdp)
        r = run_fixed_point(prob, RunConfig(mode="async", tol=1e-9,
                                            max_updates=200000, compute_time=1e-4))
        assert r.converged
        np.testing.assert_allclose(r.x, prob.exact_solution(), atol=1e-7)

    def test_policy_evaluation_linear_solve(self):
        mdp = GarnetMDP(S=50, A=3, b=4, gamma=0.9, seed=3)
        prob = PolicyEvaluationProblem(mdp)
        r = run_fixed_point(prob, RunConfig(mode="sync", tol=1e-11,
                                            max_updates=500000, compute_time=1e-4))
        np.testing.assert_allclose(r.x, prob.exact_solution(), atol=1e-8)

    def test_anderson_accelerates_sync_vi(self):
        mdp = GarnetMDP(S=100, A=4, b=5, gamma=0.95, seed=4)
        prob = ValueIterationProblem(mdp)
        plain = run_fixed_point(prob, RunConfig(mode="sync", tol=1e-8,
                                                max_updates=100000, compute_time=1e-4))
        acc = run_fixed_point(prob, RunConfig(mode="sync", tol=1e-8,
                                              max_updates=100000, compute_time=1e-4,
                                              accel=AndersonConfig(m=5)))
        assert acc.converged
        assert acc.rounds < plain.rounds / 1.2  # paper: 1.2-1.7x reduction

    def test_coupling_density_moderate(self):
        mdp = GarnetMDP(S=100, A=4, b=5, gamma=0.95, seed=5)
        prob = ValueIterationProblem(mdp)
        d = coupling_density(prob)
        assert 20 / 100 * 0.5 < d < 0.5  # ~A*b distinct successors of S


# --------------------------------------------------------------------- #
# SCF / PPP
# --------------------------------------------------------------------- #
class TestSCF:
    def test_density_trace_is_electron_count(self):
        chain = PPPChain(n_atoms=8, U=2.0)
        prob = SCFProblem(chain)
        P1 = prob.full_map(prob.initial()).reshape(8, 8)
        assert np.trace(P1) == pytest.approx(8.0)  # 2 * n_occ

    def test_density_idempotency(self):
        """P/2 is a projector: (P/2)^2 = P/2 for the map output."""
        chain = PPPChain(n_atoms=8, U=2.0)
        prob = SCFProblem(chain)
        P = prob.full_map(prob.initial()).reshape(8, 8)
        np.testing.assert_allclose((P / 2) @ (P / 2), P / 2, atol=1e-10)

    def test_fock_symmetric(self):
        chain = PPPChain(n_atoms=8, U=2.0)
        P = np.asarray(chain.core_guess())
        F = np.asarray(chain.fock(P))
        np.testing.assert_allclose(F, F.T, atol=1e-12)

    def test_converged_commutator_vanishes(self):
        chain = PPPChain(n_atoms=8, U=2.0)
        prob = SCFProblem(chain)
        x = prob.reference_solution()
        assert prob.residual_norm(x) < 1e-9

    def test_sync_diis_converges_fast_weak_correlation(self):
        chain = PPPChain(n_atoms=8, U=2.0)
        prob = SCFProblem(chain)
        r = run_fixed_point(prob, RunConfig(mode="sync", tol=1e-10,
                                            max_updates=5000, compute_time=1e-4,
                                            accel=AndersonConfig(m=8)))
        assert r.converged
        assert r.rounds < 60  # paper: 28 iterations

    def test_energy_variational_bound(self):
        """HF energy from any idempotent trial density >= converged energy."""
        chain = PPPChain(n_atoms=8, U=2.0)
        prob = SCFProblem(chain)
        e_ref = prob.energy(prob.reference_solution())
        e_guess = prob.energy(prob.initial())
        assert e_guess >= e_ref - 1e-10

    def test_async_diis_corrects_bias(self):
        """Paper §5.3: async+DIIS reaches the correct energy."""
        from repro.core import FaultProfile

        chain = PPPChain(n_atoms=8, U=2.0)
        prob = SCFProblem(chain)
        e_ref = prob.energy(prob.reference_solution())
        faults = {0: FaultProfile(delay_mean=0.02)}
        r = run_fixed_point(prob, RunConfig(
            mode="async", tol=1e-9, max_updates=60000, compute_time=1e-3,
            accel=AndersonConfig(m=8), fire_every=4, faults=faults, seed=0))
        assert r.converged
        assert abs(prob.energy(r.x) - e_ref) < 1e-6

    def test_coupling_density_dense(self):
        chain = PPPChain(n_atoms=8, U=2.0)
        assert coupling_density(SCFProblem(chain)) == 1.0

    def test_hopping_only_limit(self):
        """U=0: Fock == core Hamiltonian, energy is the tight-binding sum."""
        chain = PPPChain(n_atoms=6, U=1e-12)
        P = np.asarray(chain.core_guess())
        F = np.asarray(chain.fock(P))
        np.testing.assert_allclose(F, np.asarray(chain.H), atol=1e-10)
        w = np.linalg.eigvalsh(np.asarray(chain.H))
        e_tb = 2 * w[:3].sum()
        assert chain.energy(P.reshape(-1)) == pytest.approx(e_tb + chain.e_core, abs=1e-8)
