"""Device-resident data plane: resolver gating, bit-compat, pin modes.

Covers ``RunConfig.device_plane`` end to end: the resolver's opt-in /
exclusion matrix, the hard bit-identity contract (virtual runs ignore the
knob; a device dispatch reproduces ``block_update`` bitwise), the thread
and process resident-block loops, and the copy-on-write pin machinery
(``pin="lazy"`` / ``pin="ref"`` + the ``_x_spare`` double buffer).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import repro.problems  # noqa: F401  (enables jax x64 before any jnp use)
from repro.core.anderson import AndersonConfig
from repro.core.engine.coordinator import Coordinator
from repro.core.engine.device_plane import (
    AUTO_THRESHOLD,
    resolve_device_plane,
)
from repro.core.engine.process import ProcessPoolExecutor
from repro.core.engine.threadpool import ThreadPoolExecutor
from repro.core.engine.types import FaultProfile, RunConfig
from repro.core.engine.virtual_time import VirtualTimeExecutor
from repro.problems.hpcg import HPCGProblem
from repro.problems.jacobi import JacobiProblem

RNG = np.random.default_rng(42)


def _cfg(**kw):
    kw.setdefault("mode", "async")
    kw.setdefault("n_workers", 2)
    kw.setdefault("max_updates", 40)
    return RunConfig(**kw)


# --------------------------------------------------------------------- #
# resolver
# --------------------------------------------------------------------- #
class TestResolver:
    def setup_method(self):
        self.p = JacobiProblem(grid=16, sweeps=2)

    def test_explicit_on_resolves(self):
        for mode, want in [("on", "jnp"), ("jnp", "jnp"),
                           ("pallas", "pallas"),
                           ("interpret", "interpret"), ("ref", "ref")]:
            cfg = _cfg(device_plane=mode)
            assert resolve_device_plane(self.p, cfg, "thread") == want
            assert resolve_device_plane(self.p, cfg, "process") == want

    def test_off_and_unknown(self):
        assert resolve_device_plane(self.p, _cfg(device_plane="off"),
                                    "thread") is None
        with pytest.raises(ValueError):
            resolve_device_plane(self.p, _cfg(device_plane="gpu"), "thread")

    def test_never_on_virtual_backend(self):
        for mode in ("on", "auto", "pallas"):
            assert resolve_device_plane(self.p, _cfg(device_plane=mode),
                                        "virtual") is None

    @pytest.mark.parametrize("kw", [
        dict(mode="sync"),
        dict(selection="uniform", selection_k=8),
        dict(return_mode="full_map"),
        dict(capture_trace=True),
        dict(accel_eval="worker"),
        dict(checkpoint_every=10, checkpoint_dir="/tmp"),
    ])
    def test_exclusions(self, kw):
        cfg = _cfg(device_plane="on", **kw)
        assert resolve_device_plane(self.p, cfg, "thread") is None

    def test_auto_threshold(self):
        cfg = _cfg(device_plane="auto")
        assert resolve_device_plane(self.p, cfg, "thread") is None

        class Big:
            n = AUTO_THRESHOLD

            def is_projection_trivial(self):
                return True

        assert resolve_device_plane(Big(), cfg, "thread") == "jnp"

    def test_nontrivial_projection_excluded(self):
        class Proj:
            n = AUTO_THRESHOLD

            def is_projection_trivial(self):
                return False

        assert resolve_device_plane(Proj(), _cfg(device_plane="on"),
                                    "thread") is None


# --------------------------------------------------------------------- #
# bit-identity contracts
# --------------------------------------------------------------------- #
class TestBitIdentity:
    def test_virtual_runs_ignore_knob(self):
        """The golden contract: device_plane can never perturb a virtual
        run — on/off/auto produce bit-identical iterates and histories."""
        p = JacobiProblem(grid=20, sweeps=3)
        runs = {}
        for mode in ("off", "auto", "on"):
            cfg = _cfg(device_plane=mode, max_updates=60, seed=3)
            r = VirtualTimeExecutor().run(p, cfg)
            runs[mode] = r
            assert r.device_dispatches == 0
            assert r.device_refreshes == 0
        np.testing.assert_array_equal(runs["off"].x, runs["on"].x)
        np.testing.assert_array_equal(runs["off"].x, runs["auto"].x)
        assert runs["off"].worker_updates == runs["on"].worker_updates

    def test_device_step_matches_block_update(self):
        """One fused device dispatch == the host-path block_update slice,
        bitwise, for every whole-rows block of a 2-worker split."""
        p = JacobiProblem(grid=24, sweeps=4)
        x = RNG.standard_normal(p.n)
        xg = x.reshape(p.g, p.g)
        for blk in p.default_blocks(2):
            plan = p.device_block_plan(blk, "jnp")
            assert plan is not None
            plan.refresh(x[blk])
            vals, norm = plan.step(*[np.copy(x[s]) for s in plan.needs])
            want = p.block_update(x, blk)
            np.testing.assert_array_equal(vals, want)
            r0 = int(blk[0]) // p.g
            assert norm == pytest.approx(
                float(np.sum((want - x[blk]) ** 2)), rel=1e-12)
            assert all(isinstance(s, slice) for s in plan.needs)
            # halos are O(g) not O(n)
            assert sum(s.stop - s.start for s in plan.needs) <= 2 * p.g
            del r0

    def test_interpret_kernel_step_matches_block_update(self):
        p = JacobiProblem(grid=16, sweeps=3)
        x = RNG.standard_normal(p.n)
        blk = p.default_blocks(2)[1]
        plan = p.device_block_plan(blk, "interpret")
        plan.refresh(x[blk])
        vals, _ = plan.step(*[np.copy(x[s]) for s in plan.needs])
        np.testing.assert_array_equal(vals, p.block_update(x, blk))

    def test_non_row_block_returns_none(self):
        p = JacobiProblem(grid=16, sweeps=2)
        assert p.device_block_plan(np.array([0, 2, 4]), "jnp") is None


# --------------------------------------------------------------------- #
# resident-block executor loops
# --------------------------------------------------------------------- #
class TestExecutorLoops:
    def _converges(self, res, p):
        r0 = p.residual_norm(p.initial())
        assert p.residual_norm(res.x) < 0.5 * r0

    def test_thread_device_run(self):
        p = JacobiProblem(grid=32, sweeps=3)
        cfg = _cfg(device_plane="jnp", max_updates=120, seed=1)
        res = ThreadPoolExecutor().run(p, cfg)
        assert res.device_dispatches >= 120
        # each worker refreshes at least once (first dispatch is stale)
        assert res.device_refreshes >= cfg.n_workers
        # steady state ships halos only: most dispatches skip the refresh
        assert res.device_refreshes < res.device_dispatches
        self._converges(res, p)

    def test_thread_device_run_with_accel(self):
        p = JacobiProblem(grid=32, sweeps=3)
        cfg = _cfg(device_plane="jnp", max_updates=150, seed=2,
                   accel=AndersonConfig(m=3), fire_every=20)
        res = ThreadPoolExecutor().run(p, cfg)
        assert res.device_dispatches > 0
        assert res.accel_fires > 0
        # every commit invalidates residents: refreshes follow fires
        assert res.device_refreshes >= res.accel_accepts
        self._converges(res, p)

    def test_process_device_run(self):
        p = JacobiProblem(grid=32, sweeps=3)
        cfg = _cfg(device_plane="jnp", max_updates=80, seed=1)
        res = ProcessPoolExecutor().run(p, cfg)
        assert res.device_dispatches >= 80
        assert res.device_refreshes < res.device_dispatches
        self._converges(res, p)

    def test_faulty_profile_forces_refresh(self):
        """Noisy applies break the verbatim contract, so the resident
        block must be reshipped — no divergence between device and x."""
        p = JacobiProblem(grid=32, sweeps=3)
        prof = FaultProfile(noise_std=1e-9)
        cfg = _cfg(device_plane="jnp", max_updates=60, seed=4, faults=prof)
        res = ThreadPoolExecutor().run(p, cfg)
        # every apply is non-verbatim => every dispatch after the first
        # reships the block
        assert res.device_refreshes >= res.device_dispatches - cfg.n_workers
        self._converges(res, p)


# --------------------------------------------------------------------- #
# the device-resident iterate: returns, halos and records on the device
# --------------------------------------------------------------------- #
class TestResidentIterate:
    def _converges(self, res, p):
        assert p.residual_norm(res.x) < 0.5 * p.residual_norm(p.initial())

    @pytest.mark.parametrize("make", [lambda: JacobiProblem(grid=64),
                                      lambda: HPCGProblem(grid=16)],
                             ids=["jacobi", "hpcg"])
    def test_one_worker_run_matches_the_host_path(self, make):
        """One worker: the same trajectory bit for bit, with the iterate
        on the device and with it on the host."""
        p = make()
        runs = {mode: ThreadPoolExecutor().run(p, _cfg(
            device_plane=mode, n_workers=1, max_updates=30, tol=1e-300))
            for mode in ("jnp", "off")}
        on, off = runs["jnp"], runs["off"]
        assert on.device_applies == on.worker_updates == 30
        assert off.device_applies == off.device_dispatches == 0
        np.testing.assert_array_equal(on.x, off.x)
        assert on.residual_norm == off.residual_norm
        assert [h[1:] for h in on.history] == [h[1:] for h in off.history]

    def test_four_worker_run_records_its_own_iterate(self):
        p = JacobiProblem(grid=32, sweeps=3)
        res = ThreadPoolExecutor().run(p, _cfg(
            device_plane="jnp", n_workers=4, max_updates=120, seed=1))
        # every applied arrival was written on the device, none on the host
        assert res.device_applies == res.worker_updates >= 120
        assert res.device_refreshes < res.device_dispatches
        assert res.residual_norm == pytest.approx(p.residual_norm(res.x),
                                                  rel=1e-12)
        assert res.x.flags.writeable
        self._converges(res, p)

    @pytest.mark.parametrize("kw", [
        dict(accel=AndersonConfig(m=3), fire_every=20),
        dict(faults=FaultProfile(noise_std=1e-9)),
        dict(sdc_guard=True),
        dict(block_damping=0.8),
        dict(converge_on="error"),
    ], ids=["accel", "noise", "sdc_guard", "damping", "error"])
    def test_host_values_keep_the_iterate_on_the_host(self, kw):
        p = JacobiProblem(grid=32, sweeps=3)
        res = ThreadPoolExecutor().run(p, _cfg(
            device_plane="jnp", max_updates=80, seed=2, **kw))
        assert res.device_dispatches > 0 and res.device_applies == 0
        self._converges(res, p)


# --------------------------------------------------------------------- #
# pin modes: ref / lazy (COW) / spare-buffer recycling
# --------------------------------------------------------------------- #
class TestPinModes:
    def _coord(self, grid=12):
        p = JacobiProblem(grid=grid, sweeps=2)
        cfg = _cfg(accel=AndersonConfig(m=3), max_updates=100)
        return p, Coordinator(p, cfg)

    def test_lazy_pin_reconstructs_begin_snapshot(self):
        """COW pin == eager copy, bit for bit, including a twice-written
        block (replay must be newest-first)."""
        p, coord = self._coord()
        prof = FaultProfile()
        plan = coord.accel_begin(0.0, pin="lazy")
        eager = coord.x.copy()
        blk0, blk1 = coord.blocks[0], coord.blocks[1]
        # two arrivals on block 0 (tests reversed replay) + one on block 1
        coord.apply_return(blk0, RNG.standard_normal(blk0.size), prof, 0)
        coord.apply_return(blk1, RNG.standard_normal(blk1.size), prof, 0)
        coord.apply_return(blk0, RNG.standard_normal(blk0.size), prof, 0)
        coord.materialize_pin(plan)
        np.testing.assert_array_equal(plan.x_pin, eager)
        assert coord.pin_cow_saves == 3
        assert plan.x_pin is not coord.x

    def test_lazy_pin_no_arrivals_is_plain_copy(self):
        p, coord = self._coord()
        plan = coord.accel_begin(0.0, pin="lazy")
        eager = coord.x.copy()
        coord.materialize_pin(plan)
        np.testing.assert_array_equal(plan.x_pin, eager)
        coord.materialize_pin(plan)  # idempotent
        np.testing.assert_array_equal(plan.x_pin, eager)

    def test_ref_pin_counts_avoided_copies(self):
        p, coord = self._coord()
        plan = coord.accel_begin(0.0, pin="ref")
        assert plan.x_pin is coord.x
        assert coord.pin_copies_avoided == 1

    def test_run_counters_surface_on_result(self):
        p = JacobiProblem(grid=24, sweeps=2)
        cfg = _cfg(max_updates=120, seed=5,
                   accel=AndersonConfig(m=3), fire_every=15)
        res = ThreadPoolExecutor().run(p, cfg)
        # inline coordinator fires pin by reference: one avoided O(n)
        # copy per fire
        assert res.accel_fires > 0
        assert res.pin_copies_avoided >= res.accel_fires
        assert res.pin_copies_avoided + res.pin_cow_saves > 0


# --------------------------------------------------------------------- #
# band-sharded resident blocks (multi-device shard_map leg)
# --------------------------------------------------------------------- #
_BAND_CHECK = r"""
import repro.problems  # x64
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.distributed.sharding import band_mesh, band_sharded_jacobi_sweeps
from repro.kernels.ref import ref_jacobi_halo_sweeps
rng = np.random.default_rng(0)
rows, g, sweeps = 8, 16, 5
blk = rng.standard_normal((rows, g)); top = rng.standard_normal(g)
bot = rng.standard_normal(g); bg = rng.standard_normal((rows, g))
mesh = band_mesh(rows)
assert mesh is not None
new, norm = band_sharded_jacobi_sweeps(blk, top, bot, bg,
                                       sweeps=sweeps, mesh=mesh)
rnew, rnorm = ref_jacobi_halo_sweeps(blk, top, bot, bg, sweeps=sweeps)
assert np.array_equal(np.asarray(new), rnew)
assert abs(float(norm) - rnorm) <= 1e-9 * max(1.0, abs(rnorm))
assert band_mesh(7) is None   # devices must divide rows
assert band_mesh(2) is None   # too few rows per device
print("BAND-OK")
"""


def test_band_sharded_parity_two_devices():
    """shard_map band sweep == numpy ref on a forced 2-device host."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])))
    out = subprocess.run([sys.executable, "-c", _BAND_CHECK], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "BAND-OK" in out.stdout
