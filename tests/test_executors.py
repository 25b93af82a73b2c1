"""Executor subsystem: registry, virtual-time parity, real-concurrency backends.

The golden values below were captured from the pre-refactor monolithic
``async_engine`` at fixed seeds; the extracted ``VirtualTimeExecutor`` must
reproduce them bit-for-bit (same WU, same float wall time, same iterate
bytes).  The thread backend is checked for fixed-point parity (p=1) and for
the paper's §5.1 ordering: async beats sync wall-clock under a real 100 ms
straggler.  Every registered backend (including process, and ray when it is
installed) must converge Jacobi and VI to the same tolerance under a
no-fault config; unavailable backends must parameterize to a clean SKIP,
never an error.
"""

import hashlib
import os

import numpy as np
import pytest

from repro.core import (
    FaultProfile,
    ProcessPoolExecutor,
    RunConfig,
    ThreadPoolExecutor,
    VirtualTimeExecutor,
    available_executors,
    get_executor,
    known_executors,
    run_fixed_point,
)
from conftest import ToyContraction

# Every backend the engine knows about, available here or not.  Unavailable
# ones (ray without the optional dependency) parameterize to a clean skip.
ALL_BACKENDS = ["virtual", "thread", "process", "ray"]


def backend_params(names=ALL_BACKENDS):
    return [
        pytest.param(n, marks=[] if n in available_executors()
                     else pytest.mark.skip(reason=known_executors().get(
                         n, f"executor {n!r} not registered")))
        for n in names
    ]


def _sha(x: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()


class TestRegistry:
    def test_real_backends_registered(self):
        names = available_executors()
        assert {"virtual", "thread", "process"} <= set(names)

    def test_get_executor_instances(self):
        assert isinstance(get_executor("virtual"), VirtualTimeExecutor)
        assert isinstance(get_executor("thread"), ThreadPoolExecutor)
        assert isinstance(get_executor("process"), ProcessPoolExecutor)

    def test_unknown_executor_raises(self):
        with pytest.raises(ValueError, match="unknown executor"):
            get_executor("nope")
        with pytest.raises(ValueError, match="unknown executor"):
            run_fixed_point(ToyContraction(), RunConfig(executor="nope"))

    def test_every_known_backend_available_or_explained(self):
        known = known_executors()
        assert set(ALL_BACKENDS) <= set(known)
        for name, status in known.items():
            if name in available_executors():
                assert status == "available"
            else:
                assert status != "available"  # a human-readable reason

    def test_ray_absent_degrades_cleanly(self):
        """Without ray installed the name must stay out of the registry and
        get_executor must explain the missing dependency, not crash."""
        if "ray" in available_executors():
            pytest.skip("ray is installed; absence behaviour untestable")
        assert known_executors()["ray"].startswith("requires")
        with pytest.raises(ValueError, match="unavailable.*ray"):
            get_executor("ray")

    def test_compat_shim_reexports(self):
        from repro.core import async_engine

        assert async_engine.run_fixed_point is run_fixed_point
        assert async_engine.VirtualTimeExecutor is VirtualTimeExecutor
        assert async_engine.ProcessPoolExecutor is ProcessPoolExecutor


class TestVirtualTimeParity:
    """Fixed-seed runs are bit-identical to the pre-refactor engine."""

    # (mode, WU, wall_time, sha256 of x bytes) captured at the seed commit.
    GOLDEN_FAULTY = {
        "sync": (20000, 20.15845536704202,
                 "0bbb2369aad1384eb9b25f63e88b666a3c3bb58e624db3c3309d12fa676adc94"),
        "async": (20000, 15.040602464125524,
                  "f0a75168480fdb33e47b58725734f81739c6eedbdcc6c50fde4cbeec060fda09"),
    }
    GOLDEN_CLEAN = (368, 0.09200000000000007,
                    "1a9cce7b826f9254d25f89966ad039c055ca54595bd4af5e483fb86168e0762d")

    @pytest.mark.parametrize("mode", ["sync", "async"])
    def test_faulty_run_bit_identical(self, mode):
        wu, wall, sha = self.GOLDEN_FAULTY[mode]
        p = ToyContraction()
        f = FaultProfile(delay_mean=0.002, delay_std=0.001, noise_std=1e-9)
        r = run_fixed_point(p, RunConfig(mode=mode, tol=1e-10, max_updates=20000,
                                         compute_time=1e-3, faults=f, seed=42))
        assert r.worker_updates == wu
        assert r.wall_time == wall
        assert _sha(r.x) == sha

    def test_clean_async_run_bit_identical(self):
        wu, wall, sha = self.GOLDEN_CLEAN
        p = ToyContraction()
        r = run_fixed_point(p, RunConfig(mode="async", tol=1e-10,
                                         max_updates=20000, compute_time=1e-3,
                                         seed=3))
        assert r.converged
        assert (r.worker_updates, r.wall_time, _sha(r.x)) == (wu, wall, sha)

    def test_default_executor_is_virtual(self):
        p = ToyContraction()
        cfg = RunConfig(mode="async", tol=1e-8, compute_time=1e-3, seed=5)
        via_api = run_fixed_point(p, cfg)
        direct = VirtualTimeExecutor().run(p, cfg)
        np.testing.assert_array_equal(via_api.x, direct.x)
        assert via_api.wall_time == direct.wall_time


class TestThreadBackend:
    def test_single_worker_matches_sync_fixed_point(self):
        p = ToyContraction()
        r = run_fixed_point(p, RunConfig(mode="async", executor="thread",
                                         n_workers=1, tol=1e-10,
                                         max_updates=50000))
        s = run_fixed_point(p, RunConfig(mode="sync", executor="virtual",
                                         n_workers=1, tol=1e-10,
                                         max_updates=50000, compute_time=1e-4))
        assert r.converged and s.converged
        assert np.linalg.norm(r.x - s.x) < 1e-8
        assert np.linalg.norm(r.x - p.x_star) < 1e-8

    def test_async_threads_converge_to_fixed_point(self):
        """Async thread runs reach the fixed point within an update budget.

        Regression note: a flat ``max_updates=50000`` was a machine lottery —
        on a 1-core box the GIL serializes the 4 workers, every snapshot is
        maximally stale, and the run needs ~48k updates (measured right at
        the budget's edge; reproduced at seed HEAD).  The budget is now
        core-count-aware: convergence is gated on *arrivals*, scaled by how
        oversubscribed the worker threads are, never on wall time.
        """
        p = ToyContraction()
        n_workers = 4
        oversub = max(1, -(-n_workers // (os.cpu_count() or 1)))  # ceil div
        budget = 50000 * oversub
        r = run_fixed_point(p, RunConfig(mode="async", executor="thread",
                                         n_workers=n_workers,
                                         tol=1e-10, max_updates=budget))
        assert r.converged, (
            f"no convergence in {r.worker_updates}/{budget} updates "
            f"(cpu_count={os.cpu_count()})"
        )
        assert np.linalg.norm(r.x - p.x_star) < 1e-8
        assert r.wall_time > 0.0
        assert r.rounds == r.worker_updates

    def test_sync_threads_converge_to_fixed_point(self):
        p = ToyContraction()
        r = run_fixed_point(p, RunConfig(mode="sync", executor="thread",
                                         tol=1e-10, max_updates=50000))
        assert r.converged
        assert np.linalg.norm(r.x - p.x_star) < 1e-8

    def test_straggler_speedup_on_jacobi(self):
        """Paper §5.1 ordering on real hardware: one 100 ms straggler makes
        async > 1.5x faster than sync in measured wall-clock."""
        from repro.problems import JacobiProblem

        prob = JacobiProblem(grid=16, sweeps=10)
        faults = {0: FaultProfile(delay_mean=0.1)}
        kw = dict(executor="thread", tol=1e-3, max_updates=10**6, faults=faults)
        s = run_fixed_point(prob, RunConfig(mode="sync", **kw))
        a = run_fixed_point(prob, RunConfig(mode="async", **kw))
        assert s.converged and a.converged
        assert s.wall_time > 1.5 * a.wall_time, (
            f"async speedup only {s.wall_time / a.wall_time:.2f}x"
        )


class TestBackendParity:
    """Every registered backend solves the paper's problems to the same
    tolerance under a no-fault config; unavailable backends skip cleanly."""

    @pytest.mark.parametrize("backend", backend_params())
    def test_jacobi_parity(self, backend):
        from repro.problems import JacobiProblem

        prob = JacobiProblem(grid=8, sweeps=5)
        tol = 1e-6
        kw = {"compute_time": 1e-3} if backend == "virtual" else {}
        cfg = RunConfig(mode="async", executor=backend, n_workers=2, tol=tol,
                        max_updates=10**5, **kw)
        r = run_fixed_point(prob, cfg)
        assert r.converged
        assert prob.residual_norm(r.x) < tol
        # All backends land on the same fixed point (error scale set by the
        # Laplacian's conditioning, not by scheduling nondeterminism).
        assert r.error_norm < 1e-3
        if backend == "process":
            # Process workers are host workers: their JAX is pinned to the
            # CPU platform, whatever accelerator the parent holds.
            from repro.core.engine.poolreg import payload_key
            from repro.core.engine.process import pool_stats, problem_payload

            key = payload_key(problem_payload(prob), cfg)
            assert pool_stats()[key]["platforms"] == ["cpu", "cpu"]

    @pytest.mark.parametrize("backend", backend_params())
    def test_value_iteration_parity(self, backend):
        from repro.problems import GarnetMDP, ValueIterationProblem

        prob = ValueIterationProblem(
            GarnetMDP(S=60, A=4, b=5, gamma=0.8, seed=0))
        tol = 1e-5
        kw = {"compute_time": 1e-3} if backend == "virtual" else {}
        r = run_fixed_point(prob, RunConfig(
            mode="async", executor=backend, n_workers=2, tol=tol,
            max_updates=10**5, **kw))
        assert r.converged
        assert prob.residual_norm(r.x) < tol
        # sup-norm contraction gives ||x - V*||_inf <= tol / (1 - gamma);
        # error_norm is l2, so allow the sqrt(n) norm-equivalence factor.
        assert r.error_norm < tol / (1 - 0.8) * np.sqrt(prob.n) * 1.01


class TestWorkerEvalParity:
    """``accel_eval="worker"`` rows of the backend-parity matrix: with the
    accel/record evaluations offloaded to workers, every real backend must
    still converge the paper's problems to tolerance (ray rows skip
    cleanly when the dependency is absent).  The default virtual path is
    pinned separately by tests/test_hotpath_goldens.py."""

    WORKER_EVAL_BACKENDS = ["thread", "process", "ray"]

    @pytest.mark.parametrize("backend", backend_params(WORKER_EVAL_BACKENDS))
    def test_jacobi_worker_eval_parity(self, backend):
        from repro.core import AndersonConfig
        from repro.problems import JacobiProblem

        prob = JacobiProblem(grid=8, sweeps=5)
        tol = 1e-6
        r = run_fixed_point(prob, RunConfig(
            mode="async", executor=backend, n_workers=2, tol=tol,
            max_updates=10**5, accel=AndersonConfig(m=3), fire_every=4,
            accel_eval="worker"))
        assert r.converged
        assert prob.residual_norm(r.x) < tol
        assert r.error_norm < 1e-3

    @pytest.mark.parametrize("backend", backend_params(WORKER_EVAL_BACKENDS))
    def test_value_iteration_worker_eval_parity(self, backend):
        from repro.core import AndersonConfig
        from repro.problems import GarnetMDP, ValueIterationProblem

        prob = ValueIterationProblem(
            GarnetMDP(S=60, A=4, b=5, gamma=0.8, seed=0))
        tol = 1e-5
        r = run_fixed_point(prob, RunConfig(
            mode="async", executor=backend, n_workers=2, tol=tol,
            max_updates=10**5, accel=AndersonConfig(m=3), fire_every=4,
            accel_eval="worker"))
        assert r.converged
        assert prob.residual_norm(r.x) < tol
        assert r.error_norm < tol / (1 - 0.8) * np.sqrt(prob.n) * 1.01


class TestControllerParity:
    """``controller=target_staleness`` rows of the backend-parity matrix:
    a closed-loop autoscaling policy reshaping the membership mid-run must
    leave the fixed point intact on every in-container backend (virtual,
    thread, process).  Membership accounting must balance: every applied
    decision is counted, joins never exceed preemptions plus the fleet."""

    CONTROLLER_BACKENDS = ["virtual", "thread", "process"]

    @staticmethod
    def _controller():
        from repro.autoscale import get_policy

        # Shrink to 3 of 4 at tick 0, then PI-regulate around p95=2.0 —
        # small enough problems that the controller provably acts.
        return get_policy("target_staleness", target=2.0, initial_size=3)

    @pytest.mark.parametrize("backend", backend_params(CONTROLLER_BACKENDS))
    def test_jacobi_controller_parity(self, backend):
        from repro.problems import JacobiProblem

        prob = JacobiProblem(grid=8, sweeps=5)
        tol = 1e-6
        kw = {"compute_time": 1e-3} if backend == "virtual" else {}
        ctl = self._controller()
        r = run_fixed_point(prob, RunConfig(
            mode="async", executor=backend, n_workers=4, tol=tol,
            max_updates=10**5, controller=ctl, **kw))
        assert r.converged
        assert prob.residual_norm(r.x) < tol
        assert r.error_norm < 1e-3
        # Membership accounting balances across the decision loop.
        assert r.controller_actions == len(ctl.decision_log)
        assert r.controller_actions >= 1  # the tick-0 shrink always applies
        assert 0 <= r.joins <= r.preemptions + 4
        assert 0.0 < r.worker_seconds <= 4 * r.wall_time + 1e-9

    @pytest.mark.parametrize("backend", backend_params(CONTROLLER_BACKENDS))
    def test_value_iteration_controller_parity(self, backend):
        from repro.problems import GarnetMDP, ValueIterationProblem

        prob = ValueIterationProblem(
            GarnetMDP(S=60, A=4, b=5, gamma=0.8, seed=0))
        tol = 1e-5
        kw = {"compute_time": 1e-3} if backend == "virtual" else {}
        ctl = self._controller()
        r = run_fixed_point(prob, RunConfig(
            mode="async", executor=backend, n_workers=4, tol=tol,
            max_updates=10**5, controller=ctl, **kw))
        assert r.converged
        assert prob.residual_norm(r.x) < tol
        assert r.error_norm < tol / (1 - 0.8) * np.sqrt(prob.n) * 1.01
        assert r.controller_actions == len(ctl.decision_log)
        assert r.controller_actions >= 1
        assert 0 <= r.joins <= r.preemptions + 4
        assert 0.0 < r.worker_seconds <= 4 * r.wall_time + 1e-9


class TestProcessBackend:
    """Process-specific machinery: payloads, shared-memory snapshots."""

    def test_pickle_fallback_payload(self):
        """A plain-numpy problem with no factory_spec ships by pickling."""
        from repro.core.engine.process import problem_payload

        kind, _ = problem_payload(ToyContraction())
        assert kind == "pickle"

    def test_factory_spec_payload(self):
        from repro.core.engine.process import problem_payload, rebuild_problem
        from repro.problems import JacobiProblem

        prob = JacobiProblem(grid=8, sweeps=3, seed=7)
        payload = problem_payload(prob)
        assert payload[0] == "factory"
        clone = rebuild_problem(payload)
        assert clone.g == 8 and clone.sweeps == 3
        np.testing.assert_array_equal(clone._b, prob._b)

    def test_unpicklable_problem_raises_helpfully(self):
        from repro.core.engine.process import problem_payload

        class Opaque(ToyContraction):
            def __init__(self):
                super().__init__()
                self.fn = lambda x: x  # defeats pickle

        with pytest.raises(ValueError, match="factory_spec"):
            problem_payload(Opaque())

    def test_sync_process_converges(self):
        p = ToyContraction()
        r = run_fixed_point(p, RunConfig(mode="sync", executor="process",
                                         n_workers=2, tol=1e-8,
                                         max_updates=50000))
        assert r.converged
        assert np.linalg.norm(r.x - p.x_star) < 1e-6


class TestCrashChurn:
    """FaultProfile crash/restart semantics on all real backends."""

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_crash_restart_converges(self, executor):
        p = ToyContraction()
        faults = {0: FaultProfile(crash_prob=0.2, restart_after=0.001)}
        kw = {} if executor == "thread" else {"compute_time": 1e-3}
        r = run_fixed_point(p, RunConfig(mode="async", executor=executor,
                                         tol=1e-8, max_updates=50000,
                                         faults=faults, **kw))
        assert r.converged
        assert r.crashes > 0
        # A worker that crashes right as the run converges may exit without
        # rejoining, so restarts can trail crashes by the in-flight ones.
        assert 0 < r.restarts <= r.crashes

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_permanent_crash_terminates_unconverged(self, executor):
        p = ToyContraction()
        faults = FaultProfile(crash_prob=1.0)  # every worker dies on return
        kw = {} if executor == "thread" else {"compute_time": 1e-3}
        r = run_fixed_point(p, RunConfig(mode="async", executor=executor,
                                         tol=1e-10, max_updates=50000,
                                         faults=faults, **kw))
        assert not r.converged
        assert r.crashes == 4
        assert r.restarts == 0
        assert r.worker_updates == 0

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_all_crash_churn_terminates_at_max_wall(self, executor):
        """Regression: a worker set that crashes on every return (but keeps
        restarting) must still hit the stop checks — the thread backend's
        crash path used to skip them and spin forever."""
        p = ToyContraction()
        faults = FaultProfile(crash_prob=1.0, restart_after=0.001)
        kw = {} if executor == "thread" else {"compute_time": 1e-3}
        r = run_fixed_point(p, RunConfig(mode="async", executor=executor,
                                         tol=1e-10, max_updates=100,
                                         max_wall=0.5, faults=faults, **kw))
        assert not r.converged
        assert r.worker_updates == 0
        assert r.crashes > 0

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_all_crash_churn_terminates_on_arrival_cap(self, executor):
        """Liveness: max_updates only counts applied updates, so an
        all-crashing churn run must stop at the max_arrivals guard even
        with no max_wall set."""
        p = ToyContraction()
        faults = FaultProfile(crash_prob=1.0, restart_after=0.001)
        kw = {} if executor == "thread" else {"compute_time": 1e-3}
        r = run_fixed_point(p, RunConfig(mode="async", executor=executor,
                                         tol=1e-10, max_updates=50,
                                         faults=faults, **kw))
        assert not r.converged
        assert r.worker_updates == 0
        assert r.crashes >= 500  # 10 * max_updates arrivals, all crashed

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_drop_all_terminates_on_arrival_cap(self, executor):
        """Liveness guard under drop_prob=1.0: every return is dropped, so
        max_updates never advances — the run must stop at the max_arrivals
        cap on every backend (not just implicitly on virtual)."""
        p = ToyContraction()
        faults = FaultProfile(drop_prob=1.0)
        kw = {} if executor == "thread" else {"compute_time": 1e-3}
        r = run_fixed_point(p, RunConfig(mode="async", executor=executor,
                                         tol=1e-10, max_updates=30,
                                         faults=faults, **kw))
        assert not r.converged
        assert r.worker_updates == 0
        assert r.drops == 300  # 10 * max_updates arrivals, all dropped

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_drop_all_explicit_arrival_cap(self, executor):
        """Same guard with an explicit (small) max_arrivals."""
        p = ToyContraction()
        kw = {} if executor == "thread" else {"compute_time": 1e-3}
        r = run_fixed_point(p, RunConfig(mode="async", executor=executor,
                                         tol=1e-10, max_updates=10**6,
                                         max_arrivals=12,
                                         faults=FaultProfile(drop_prob=1.0),
                                         **kw))
        assert not r.converged
        assert r.worker_updates == 0
        assert r.drops == 12

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_all_crash_explicit_arrival_cap(self, executor):
        """All-crash churn against an explicit max_arrivals on the real
        backends (the thread/process guard was previously only covered via
        the 10x-max_updates default)."""
        p = ToyContraction()
        faults = FaultProfile(crash_prob=1.0, restart_after=0.001)
        kw = {} if executor == "thread" else {"compute_time": 1e-3}
        r = run_fixed_point(p, RunConfig(mode="async", executor=executor,
                                         tol=1e-10, max_updates=10**6,
                                         max_arrivals=8, faults=faults, **kw))
        assert not r.converged
        assert r.worker_updates == 0
        assert r.crashes == 8

    @pytest.mark.parametrize("executor", ["virtual", "thread", "process"])
    def test_sync_crash_restart(self, executor):
        p = ToyContraction()
        faults = {0: FaultProfile(crash_prob=0.3, restart_after=0.0)}
        kw = {} if executor == "thread" else {"compute_time": 1e-4}
        r = run_fixed_point(p, RunConfig(mode="sync", executor=executor,
                                         tol=1e-8, max_updates=50000,
                                         faults=faults, **kw))
        assert r.converged
        assert r.crashes > 0
        assert r.restarts == r.crashes
