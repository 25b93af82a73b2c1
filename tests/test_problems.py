"""The paper's three testbeds and HPCG's operator: correctness against
independent references."""

import importlib.util
from pathlib import Path

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
    HPCGProblem,
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
# HPCG's 27-point operator
# --------------------------------------------------------------------- #
def _hpcg_reference(grid: int, sweeps: int):
    """The benchmark's plain numpy reference (``chipbench/problems/
    hpcg3d.py``, which imports nothing of the program), in float64."""
    path = (Path(__file__).resolve().parents[1] / "chipbench" / "problems"
            / "hpcg3d.py")
    spec = importlib.util.spec_from_file_location("hpcg3d_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Reference({"grid": grid, "sweeps": sweeps}, seed=0)


class TestHPCG:
    @pytest.mark.parametrize("grid", [8, 16])
    def test_b_is_A_times_ones(self, grid):
        p = HPCGProblem(grid=grid)
        ref = _hpcg_reference(grid, 1)
        np.testing.assert_array_equal(p._b, ref.b.ravel())
        # interior 0, faces 9, edges 15, corners 19
        assert sorted(set(p._b)) == [0.0, 9.0, 15.0, 19.0]
        assert np.sum(p._b == 19.0) == 8
        assert np.sum(p._b == 0.0) == (grid - 2) ** 3

    @pytest.mark.parametrize("grid,workers", [(8, 4), (12, 3), (16, 4)])
    def test_plane_and_host_path_agree_with_reference(self, grid, workers):
        """Each z-slab's update, by the host ``block_update`` and by the
        device plan's ``refresh`` + ``step`` (``jnp``), on a seeded random
        iterate: bitwise equal to each other, within 1e-12 of numpy."""
        p = HPCGProblem(grid=grid, sweeps=4)
        ref = _hpcg_reference(grid, 4)
        x = np.random.default_rng(grid).standard_normal(p.n)
        for idx in p.default_blocks(workers):
            host = p.block_update(x, idx)
            plan = p.device_block_plan(idx, "jnp")
            plane = grid * grid  # the halos are the two neighbouring planes
            halos = [sl for sl in (slice(idx[0] - plane, idx[0]),
                                   slice(idx[-1] + 1, idx[-1] + 1 + plane))
                     if 0 <= sl.start and sl.stop <= p.n]
            assert plan.needs == halos
            plan.refresh(x[idx])
            dev, sq = plan.step(*[np.copy(x[s]) for s in plan.needs])
            np.testing.assert_array_equal(dev, host)
            want = ref.block_step(x, idx)
            assert np.max(np.abs(host - want)) <= 1e-12 * np.max(np.abs(want))
            assert sq == pytest.approx(np.sum((dev - x[idx]) ** 2),
                                       rel=1e-12)
            ref_plan = p.device_block_plan(idx, "ref")
            ref_plan.refresh(x[idx])
            got, _ = ref_plan.step(*[np.copy(x[s]) for s in ref_plan.needs])
            assert np.max(np.abs(got - want)) <= 1e-12 * np.max(np.abs(want))

    def test_one_sweep_is_the_full_map_restricted(self):
        p = HPCGProblem(grid=8, sweeps=1)
        x = np.random.default_rng(3).standard_normal(p.n)
        g = p.full_map(x)
        ref = _hpcg_reference(8, 1)
        np.testing.assert_allclose(g, ref.block_step(x, np.arange(p.n)),
                                   rtol=0, atol=1e-13)
        for idx in p.default_blocks(2):
            np.testing.assert_array_equal(p.block_update(x, idx), g[idx])
        part = np.arange(5, 100)  # not whole planes: one-sweep restriction
        assert p.device_block_plan(part, "jnp") is None
        np.testing.assert_array_equal(
            HPCGProblem(grid=8, sweeps=5).block_update(x, part), g[part])

    @pytest.mark.parametrize("x_kind", ["zeros", "random", "ones"])
    def test_residual_norm_is_one_device_scalar(self, x_kind):
        import jax

        from repro.problems.hpcg import _residual_norm27

        p = HPCGProblem(grid=12)
        x = {"zeros": np.zeros(p.n),
             "random": np.random.default_rng(5).standard_normal(p.n),
             "ones": np.ones(p.n)}[x_kind]
        got = p.residual_norm(x)
        assert type(got) is float
        scale = np.linalg.norm(p._b)  # the norm at x = 0
        assert abs(got - _hpcg_reference(12, 1).residual_norm(x)) \
            <= 1e-13 * max(got, scale)
        assert abs(got - np.linalg.norm(p.residual(x))) \
            <= 1e-13 * max(got, scale)
        if x_kind == "ones":
            assert got == 0.0 and p.residual_norm(p.exact_solution()) == 0.0
        out = jax.eval_shape(lambda v: _residual_norm27(v, p._b_j, 12),
                             jax.ShapeDtypeStruct((p.n,), np.float64))
        assert out.shape == () and out.dtype == np.float64

    def test_dependency_pattern_is_27_point(self):
        p = HPCGProblem(grid=6)
        counts = p.dependency_counts()
        assert [len(p.dependency_indices(i)) for i in range(p.n)] \
            == list(counts)
        assert counts.max() == 27 and counts.min() == 8
        # every dependency is a neighbour in each coordinate
        i = 2 * 36 + 3 * 6 + 4
        zyx = np.stack(np.unravel_index(p.dependency_indices(i), (6, 6, 6)))
        assert np.all(np.abs(zyx - np.array([[2], [3], [4]])) <= 1)
        assert coupling_density(p) < 0.2
        assert block_internal_coupling(p, p.default_blocks(2)) > 0.8

    def test_pallas_and_interpret_planes_raise(self):
        p = HPCGProblem(grid=8)
        for mode in ("pallas", "interpret"):
            with pytest.raises(ValueError, match="64-bit"):
                p.device_block_plan(p.default_blocks(2)[0], mode)

    def test_async_thread_run_on_the_plane_reaches_tol(self):
        """Through ``run_fixed_point`` on the thread executor, every
        update a device-plane dispatch, with the executor's ``block_eval``
        (``path`` plane) and ``record`` spans, to an error below 1e-5."""
        p = HPCGProblem(grid=8, sweeps=5)
        res = run_fixed_point(p, RunConfig(
            mode="async", executor="thread", n_workers=4, tol=1e-8,
            max_updates=20000, device_plane="jnp", telemetry=True))
        assert res.converged and res.error_norm < 1e-5
        assert res.device_dispatches == res.worker_updates > 0
        evals = [e for e in res.telemetry.events if e["k"] == "block_eval"]
        assert evals and {e["path"] for e in evals} == {"plane"}
        records = [e for e in res.telemetry.events if e["k"] == "record"]
        assert len(records) == len(res.history)


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
