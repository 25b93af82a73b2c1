#!/usr/bin/env python3
"""Drive the asynchronous fixed-point engine end to end on a TPU.

    python chip_smoke.py               # four phases on one chip
    python chip_smoke.py --four-chips  # the band-sharded Jacobi path only,
                                       # on a host with four chips

Every phase goes through ``run_fixed_point`` on the ``thread`` executor,
the one executor whose workers share the process that holds the chip (the
script starts no other process), and checks what comes out against a plain
float64 reference that does not use the engine's code:

* ``jacobi_device`` -- 4096 x 4096 Laplacian, block Jacobi on the
  device-resident data plane; the final residual is recomputed with scipy
  and one interior block step is compared with the numpy oracle.
* ``vi_anderson_device`` -- Garnet value iteration, S = 2**20, with
  coordinator Anderson; compared with numpy value iteration.
* ``scf_straggler`` -- PPP-SCF (20 atoms, U = 4) with DIIS and one 100 ms
  straggler, sync and async; energies compared with a numpy SCF.  A
  host-path check: the SCF algebra is numpy (``problems/scf.py`` says
  why), so this phase dispatches nothing to the chip.
* ``pallas_f32`` -- the compiled float32 Pallas halo-sweep and Anderson
  kernels at their real shapes, compared with numpy.

Each phase prints one JSON line: its name, set-up and compile seconds,
wall seconds, updates applied, device dispatches, and each agreement error
beside its limit.  The last line is ``{"ok": true, "device": {...}}``.  The
script exits non-zero, without that line, when JAX finds no TPU or any
phase fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402


class PhaseFailed(AssertionError):
    pass


def _check(checks: dict, name: str, err: float, limit: float) -> None:
    checks[name] = {"err": float(err), "limit": limit}


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def _run(problem, cfg):
    """``run_fixed_point``; returns (result, seconds outside its clock)."""
    from repro.core import run_fixed_point

    t0 = time.perf_counter()
    res = run_fixed_point(problem, cfg)
    return res, time.perf_counter() - t0 - res.wall_time


# --------------------------------------------------------------------- #
# plain float64 references
# --------------------------------------------------------------------- #
def laplacian(g: int):
    """The 5-point Dirichlet Laplacian on a g x g grid, scipy CSR."""
    import scipy.sparse as sp

    lap = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(g, g))
    return sp.kronsum(lap, lap, format="csr")


def numpy_value_iteration(idx, probs, R, gamma: float, tol: float,
                          V: np.ndarray, max_iter: int = 10_000) -> np.ndarray:
    """V* by plain value iteration from ``V``, to a sup-norm step below
    ``tol``; the result is then within gamma / (1 - gamma) * tol of V*
    whatever the start (the start only sets the number of steps)."""
    for _ in range(max_iter):
        TV = np.max(R + gamma * np.einsum("sab,sab->sa", probs, V[idx]),
                    axis=-1)
        if np.max(np.abs(TV - V)) < tol:
            return TV
        V = TV
    raise PhaseFailed(f"reference value iteration did not reach {tol}")


def numpy_scf_energy(n_atoms: int, U: float, t: float = 1.0,
                     tol: float = 1e-12, m: int = 8,
                     max_iter: int = 500) -> float:
    """Closed-shell PPP Hartree-Fock energy by Pulay DIIS in numpy."""
    n, nocc = n_atoms, n_atoms // 2
    H = np.zeros((n, n))
    i = np.arange(n - 1)
    H[i, i + 1] = H[i + 1, i] = -t
    dist = np.abs(np.subtract.outer(np.arange(n), np.arange(n)))
    gam = U / np.sqrt(1.0 + (U * dist) ** 2)  # Ohno, lattice units

    def fock(P):
        return H + np.diag(gam @ np.diag(P)) - 0.5 * P * gam

    def density(F):
        C = np.linalg.eigh(F)[1][:, :nocc]
        return 2.0 * C @ C.T

    P = density(H)
    fs, es = [], []
    for _ in range(max_iter):
        F = fock(P)
        e = F @ P - P @ F
        if np.linalg.norm(e) < tol:
            return 0.5 * np.sum(P * (H + F)) + np.sum(np.triu(gam, 1))
        fs, es = (fs + [F])[-m:], (es + [e])[-m:]
        k = len(fs)
        B = np.zeros((k + 1, k + 1))
        B[:k, :k] = [[np.sum(a * b) for b in es] for a in es]
        B[:k, k] = B[k, :k] = 1.0
        rhs = np.zeros(k + 1)
        rhs[k] = 1.0
        c = np.linalg.lstsq(B, rhs, rcond=None)[0][:k]
        P = density(sum(ci * Fi for ci, Fi in zip(c, fs)))
    raise PhaseFailed(f"reference SCF did not reach {tol}")


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def phase_jacobi_device(grid: int = 4096, sweeps: int = 10,
                        n_workers: int = 4, max_updates: int = 400) -> dict:
    from repro.core import RunConfig
    from repro.kernels.ref import ref_jacobi_halo_sweeps
    from repro.problems import JacobiProblem

    t0 = time.perf_counter()
    p = JacobiProblem(grid=grid, sweeps=sweeps, seed=0)
    build = time.perf_counter() - t0
    res, setup = _run(p, RunConfig(mode="async", executor="thread",
                                   n_workers=n_workers,
                                   max_updates=max_updates))
    _require(res.device_dispatches > 0, "no dispatch reached the device")

    checks: dict = {}
    b = np.random.default_rng(0).standard_normal(grid * grid)
    r = float(np.linalg.norm(b - laplacian(grid) @ res.x))
    _check(checks, "residual_rel", abs(r - res.residual_norm) / r, 1e-10)

    # One interior block through the device plan, on the final iterate.
    blk = p.default_blocks(n_workers)[1]
    r0, r1 = int(blk[0]) // grid, (int(blk[-1]) + 1) // grid
    plan = p.device_block_plan(blk, "jnp")
    plan.refresh(res.x[blk])
    vals, _ = plan.step(*[np.copy(res.x[s]) for s in plan.needs])
    xg = res.x.reshape(grid, grid)
    want, _ = ref_jacobi_halo_sweeps(xg[r0:r1], xg[r0 - 1], xg[r1],
                                     b.reshape(grid, grid)[r0:r1],
                                     sweeps=sweeps)
    _check(checks, "block_max_abs", np.max(np.abs(vals - want.ravel())),
           1e-12)
    return dict(phase="jacobi_device", setup_s=build + setup,
                wall_s=res.wall_time, updates=res.worker_updates,
                device_dispatches=res.device_dispatches, checks=checks,
                x_max_abs=float(np.max(np.abs(res.x))))


def phase_vi_anderson_device(S: int = 2 ** 20, A: int = 4, b: int = 5,
                             gamma: float = 0.95, tol: float = 1e-6,
                             max_wall: float = 400.0) -> dict:
    from repro.core import AndersonConfig, RunConfig
    from repro.problems import GarnetMDP, ValueIterationProblem

    t0 = time.perf_counter()
    mdp = GarnetMDP(S=S, A=A, b=b, gamma=gamma, seed=0)
    p = ValueIterationProblem(mdp)
    build = time.perf_counter() - t0
    res, setup = _run(p, RunConfig(mode="async", executor="thread",
                                   n_workers=4, accel=AndersonConfig(m=5),
                                   tol=tol, max_wall=max_wall))
    _require(res.converged, f"not converged: residual {res.residual_norm}")
    _require(res.accel_accepts > 0, "no Anderson extrapolation accepted")

    checks: dict = {}
    v_star = numpy_value_iteration(np.asarray(mdp.idx), np.asarray(mdp.probs),
                                   np.asarray(mdp.R), gamma, tol=1e-9,
                                   V=np.array(res.x))
    _check(checks, "v_max_abs", np.max(np.abs(res.x - v_star)),
           tol / (1.0 - gamma))
    return dict(phase="vi_anderson_device", setup_s=build + setup,
                wall_s=res.wall_time, updates=res.worker_updates,
                device_dispatches=res.device_dispatches, checks=checks,
                accel_fires=res.accel_fires, accel_accepts=res.accel_accepts)


def phase_scf_straggler(n_atoms: int = 20, U: float = 4.0, tol: float = 1e-6,
                        delay: float = 0.1, max_wall: float = 120.0) -> dict:
    from repro.core import AndersonConfig, FaultProfile, RunConfig
    from repro.problems import PPPChain, SCFProblem

    t0 = time.perf_counter()
    p = SCFProblem(PPPChain(n_atoms=n_atoms, U=U))
    build = time.perf_counter() - t0
    e_ref = numpy_scf_energy(n_atoms, U)
    checks: dict = {}
    out = dict(phase="scf_straggler", setup_s=build, wall_s=0.0, updates=0,
               device_dispatches=0, checks=checks)
    walls = {}
    for mode in ("sync", "async"):
        res, setup = _run(p, RunConfig(
            mode=mode, executor="thread", n_workers=4,
            accel=AndersonConfig(m=5), tol=tol, max_wall=max_wall,
            faults={0: FaultProfile(delay_mean=delay)}))
        _require(res.converged,
                 f"{mode} not converged: residual {res.residual_norm}")
        _check(checks, f"{mode}_energy_abs", abs(p.energy(res.x) - e_ref),
               1e-8)
        walls[mode] = res.wall_time
        out["setup_s"] += setup
        out["wall_s"] += res.wall_time
        out["updates"] += res.worker_updates
        out["device_dispatches"] += res.device_dispatches
        out[f"{mode}_wall_s"] = res.wall_time
    out["async_over_sync_wall"] = walls["async"] / walls["sync"]
    return out


def phase_pallas_f32(rows=(1024, 1365), g: int = 4096, sweeps: int = 10,
                     h: int = 6, n: int = 1 << 22) -> dict:
    """The halo kernel on each block height in ``rows`` (1365 rows, a
    3-worker block, leaves the last row tile padded) and the Anderson
    combine, compiled, against numpy."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.ref import ref_jacobi_halo_sweeps

    rng = np.random.default_rng(0)
    f32 = np.float32
    blocks = [[rng.standard_normal(s).astype(f32)
               for s in ((r, g), (g,), (g,), (r, g))] for r in rows]
    X, G = (rng.standard_normal((h, n)).astype(f32) for _ in range(2))
    alpha = rng.dirichlet(np.ones(h)).astype(f32)
    t0 = time.perf_counter()
    halo_in = [[jnp.asarray(a) for a in blk] for blk in blocks]
    mix_in = [jnp.asarray(a) for a in (X, G, alpha)]

    def dispatch():  # every kernel once, results back on the host
        halos = [ops.jacobi_halo_sweeps(*a, sweeps=sweeps) for a in halo_in]
        mix = ops.anderson_mix(*mix_in)
        return [(np.asarray(v), float(m)) for v, m in halos], np.asarray(mix)

    dispatch()  # set-up: transfers, tracing, Mosaic compiles, a first run
    t1 = time.perf_counter()
    halos, mix = dispatch()
    wall = time.perf_counter() - t1

    checks: dict = {}
    for r, blk, (new, norm) in zip(rows, blocks, halos):
        want, wnorm = ref_jacobi_halo_sweeps(*blk, sweeps=sweeps)
        _check(checks, f"halo{r}_max_abs", np.max(np.abs(new - want)), 1e-5)
        _check(checks, f"halo{r}_norm_rel", abs(norm - wnorm) / wnorm, 1e-5)
    want_mix = alpha.astype(np.float64) @ G.astype(np.float64)
    _check(checks, "mix_max_abs", np.max(np.abs(mix - want_mix)), 1e-5)
    return dict(phase="pallas_f32", setup_s=t1 - t0, wall_s=wall, updates=0,
                device_dispatches=len(rows) + 1, checks=checks)


def phase_jacobi_band_sharded(grid: int = 4096, sweeps: int = 10,
                              n_workers: int = 4,
                              max_updates: int = 400) -> dict:
    """The band-sharded resident block: every block spans all devices."""
    import jax

    from repro.core import RunConfig
    from repro.distributed.sharding import (band_mesh,
                                            band_sharded_jacobi_sweeps)
    from repro.problems import JacobiProblem
    from repro.problems.jacobi import _halo_sweeps

    t0 = time.perf_counter()
    p = JacobiProblem(grid=grid, sweeps=sweeps, seed=0)
    build = time.perf_counter() - t0
    blk = p.default_blocks(n_workers)[1]
    rows = blk.size // grid
    mesh = band_mesh(rows)
    _require(mesh is not None and mesh.size == len(jax.devices()),
             f"no band mesh over {len(jax.devices())} devices")

    checks: dict = {}
    rng = np.random.default_rng(1)
    xb, bg = rng.standard_normal((rows, grid)), rng.standard_normal((rows, grid))
    top, bot = rng.standard_normal(grid), rng.standard_normal(grid)
    new, _ = band_sharded_jacobi_sweeps(xb, top, bot, bg, sweeps=sweeps,
                                        mesh=mesh)
    _require(len(new.sharding.device_set) == mesh.size,
             "band-sharded block does not span the mesh")
    dev0 = jax.devices()[0]
    one, _ = _halo_sweeps(*(jax.device_put(a, dev0)
                            for a in (xb, top, bot, bg)), sweeps)
    _check(checks, "band_vs_device0_max_abs",
           np.max(np.abs(np.asarray(new) - np.asarray(one))), 1e-12)

    res, setup = _run(p, RunConfig(mode="async", executor="thread",
                                   n_workers=n_workers,
                                   max_updates=max_updates))
    _require(res.device_dispatches > 0, "no dispatch reached the device")
    b = np.random.default_rng(0).standard_normal(grid * grid)
    r = float(np.linalg.norm(b - laplacian(grid) @ res.x))
    _check(checks, "residual_rel", abs(r - res.residual_norm) / r, 1e-10)
    return dict(phase="jacobi_band_sharded", setup_s=build + setup,
                wall_s=res.wall_time, updates=res.worker_updates,
                device_dispatches=res.device_dispatches, checks=checks,
                devices=mesh.size)


PHASES = [phase_jacobi_device, phase_vi_anderson_device, phase_scf_straggler,
          phase_pallas_f32]


def run_phase(fn, **kw) -> dict:
    """Run one phase and print its line; ``ok`` is False on any failure."""
    try:
        out = fn(**kw)
        out["ok"] = all(c["err"] <= c["limit"]
                        for c in out["checks"].values())
    except Exception as e:  # reported on the phase's line, then exit 1
        traceback.print_exc()
        out = dict(phase=fn.__name__[len("phase_"):], ok=False,
                   error=f"{type(e).__name__}: {e}")
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the band-sharded Jacobi path, on a host "
                         "with four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r} "
              f"({len(devices)} device(s)), not a TPU", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    use_compile_cache()

    phases = [phase_jacobi_band_sharded] if args.four_chips else PHASES
    results = [run_phase(fn) for fn in phases]
    if not all(r["ok"] for r in results):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
