"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own that this module finds by name:

* ``configs/<config>.json`` -- sizes, precision, fleet, faults, the limits
  of the check; its ``family`` names ``problems/<family>.py``, which builds
  the program's problem and holds the plain reference and kernel costs;
* ``mixes/<traffic>.json`` -- the run's mode, device plane and straggler
  delays;
* ``metrics/<metric>.py`` -- ``read(window)``, the number or None.

The window is a closed loop of one caller: solves from x0 = 0 back to back
through ``run_fixed_point`` on the thread executor, each given the time
left as its ``max_wall``, until the window's seconds are used up.
"""

from __future__ import annotations

import importlib.util
import json
import math
import shutil
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(kind: str, name: str, base: Path = HERE):
    """``<base>/<kind>/<name>.py`` as a module."""
    path = base / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(base: Path, kind: str, name: str) -> dict:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    family: object
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]
    base: Path = HERE


def _reports(metric: dict, cell: str) -> bool:
    """An end-to-end metric with no ``workloads`` key is reported by every
    cell; a per-layer metric names its cells."""
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench: Optional[dict] = None, base: Path = HERE,
              overrides: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files.

    ``overrides`` replaces configuration keys (a test runs a cell at a
    tiny size this way)."""
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    config = dict(_json(base, "configs", wl["config"]), **(overrides or {}))
    for m in bench["per_layer"]:
        if "workloads" not in m:
            raise KeyError(f"per-layer metric {m['name']!r} names no "
                           "workloads")
    e2e = [m["name"] for m in bench["end_to_end"] if _reports(m, name)]
    per = [m["name"] for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name=name, chips=wl["chips"], config=config,
                mix=_json(base, "mixes", wl["traffic"]),
                family=load_module("problems", config["family"], base),
                end_to_end=e2e, per_layer=per,
                units={m["name"]: m["unit"]
                       for m in bench["end_to_end"] + bench["per_layer"]},
                base=base)


def run_config(cell: Cell, seed: int, **kw):
    """The ``RunConfig`` the mix gives this cell's configuration."""
    from repro.core import FaultProfile, RunConfig

    mix = cell.mix
    return RunConfig(
        executor="thread", mode=mix["mode"],
        n_workers=cell.config["n_workers"], tol=cell.config["tol"],
        device_plane=mix["device_plane"], seed=seed,
        faults={int(w): FaultProfile(delay_mean=d)
                for w, d in mix["delay_s"].items()}, **kw)


# --------------------------------------------------------------------- #
# what a window produced, as the metric readers see it
# --------------------------------------------------------------------- #
@dataclass
class Window:
    cell: Cell
    seconds: float  # caller's clock, start of the first solve to the end
    setup_s: float
    solves: list  # RunResult of each solve, in order
    trace: Optional[object] = None  # trace.TraceSummary of a traced run
    device_kind: str = ""

    @property
    def updates(self) -> int:
        return sum(r.worker_updates for r in self.solves)

    @property
    def decades(self) -> float:
        """Residual decades gained, each solve from its first record to
        its last (a solve the window cut off counts its progress so far)."""
        return sum(math.log10(r.history[0][2] / r.history[-1][2])
                   for r in self.solves)

    def events(self, kind: str) -> List[dict]:
        return [e for r in self.solves if r.telemetry is not None
                for e in r.telemetry.events if e["k"] == kind]

    def roofline(self, kernel: str) -> Optional[float]:
        """Percent of the least time that ``kernel`` took in the trace."""
        from chipbench.peaks import least_seconds

        cost = getattr(self.cell.family, "KERNELS", {}).get(kernel)
        if self.trace is None or cost is None \
                or kernel not in self.trace.kernels:
            return None
        secs, calls = self.trace.kernels[kernel]
        ops, nbytes = cost(self.cell.config)
        return 100.0 * calls * least_seconds(self.device_kind, ops,
                                             nbytes) / secs


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed directory
    in the checkout (or ``JAX_COMPILATION_CACHE_DIR``), keeping every
    program however fast it compiles, so that a second run of a cell in
    this checkout compiles nothing."""
    import jax
    from repro.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# --------------------------------------------------------------------- #
# compiles seen by this process
# --------------------------------------------------------------------- #
_LOWERINGS = [0]
_LISTENING = [False]


def compiles() -> int:
    """Programs lowered so far in this process (each in-process cache miss
    of a jitted function or primitive lowers once)."""
    if not _LISTENING[0]:
        from jax import monitoring

        def count(name: str, *_a, **_kw) -> None:
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                _LOWERINGS[0] += 1

        monitoring.register_event_duration_secs_listener(count)
        _LISTENING[0] = True
    return _LOWERINGS[0]


# --------------------------------------------------------------------- #
# the check
# --------------------------------------------------------------------- #
def program_block_step(problem, rc, indices: np.ndarray,
                       x: np.ndarray) -> np.ndarray:
    """One block update of ``x`` through the path the window's workers
    took: the device plan the executor resolves for this run, or the host
    ``block_update``."""
    from repro.core.engine.device_plane import resolve_device_plane

    mode = resolve_device_plane(problem, rc, "thread")
    plan = problem.device_block_plan(indices, mode) if mode else None
    if plan is None:
        return np.asarray(problem.block_update(x, indices))
    plan.refresh(x[indices])
    vals, _ = plan.step(*[np.copy(x[s]) for s in plan.needs])
    return np.asarray(vals)


def check(cell: Cell, seed: int, problem, rc, solves: list) -> dict:
    """Each solve's answer against the family's float64 reference.

    ``residual_gap``: the program's first and last recorded residual
    against the reference's at x0 and at the final iterate, as a share of
    the reference's residual at x0.  ``block_step_gap``: one update of
    every block of the final iterate through the program's path against
    the reference's, as a share of the largest reference value.  Returns
    the worst reading of each over the solves, and the solves that failed.
    """
    ref = cell.family.Reference(cell.config, seed)
    limits = cell.config["limits"]
    blocks = problem.default_blocks(cell.config["n_workers"])
    r0 = ref.residual_norm(problem.initial())
    worst = {k: 0.0 for k in limits}
    failed = 0
    for res in solves:
        x = np.asarray(res.x)
        gap = {"residual_gap": max(
            abs(res.history[0][2] - r0),
            abs(res.residual_norm - ref.residual_norm(x))) / r0}
        steps = [(program_block_step(problem, rc, blk, x),
                  ref.block_step(x, blk)) for blk in blocks]
        gap["block_step_gap"] = max(
            float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            for got, want in steps)
        failed += any(not gap[k] <= limits[k] for k in limits)
        for k in limits:  # a NaN reading stays NaN
            if not gap[k] <= worst[k]:
                worst[k] = gap[k]
    return {"readings": worst, "failed": failed}


# --------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------- #
@dataclass
class Outcome:
    line: dict  # the result line
    window: dict  # counts seen inside the window


def _device(trace: bool, summary) -> dict:
    import jax

    devs = jax.devices()
    peaks = [d.memory_stats() or {} for d in devs]
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs),
           "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                    for s in peaks)}
    if trace and summary is not None:
        out["busy_s"] = summary.busy_s
        out["window_s"] = summary.window_s
    return out


def _profile(logdir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the coordinator is Python: keep it fast
    jax.profiler.start_trace(logdir, profiler_options=opts)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, patch: Optional[Callable] = None) -> Outcome:
    """Set up, measure ``seconds``, check, and read the cell's metrics.

    ``t_start`` is the process's start on ``time.perf_counter``.
    ``patch(problem)``, where given, breaks the timed path in place (the
    control and the planted faults of ``faults.py``)."""
    import jax
    from repro.core import run_fixed_point

    fam = cell.family
    problem = fam.build(cell.config, seed)
    if patch is not None:
        patch(problem)
    fam.prepare(problem)
    # Warm-up: one short solve of the cell's own run shape compiles (or
    # loads from the persistent cache) every program the window drives.
    run_fixed_point(problem, run_config(
        cell, seed, max_updates=cell.config["n_workers"],
        telemetry=trace or None))

    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            _profile(logdir)
        c0 = compiles()
        t0 = time.perf_counter()
        solves = []
        with jax.profiler.TraceAnnotation("window"):
            while (left := t0 + seconds - time.perf_counter()) > 0:
                with jax.profiler.TraceAnnotation("solve"):
                    solves.append(run_fixed_point(problem, run_config(
                        cell, seed, max_wall=left, telemetry=trace or None)))
        t1 = time.perf_counter()
        n_compiles = compiles() - c0
        summary = None
        if trace:
            from chipbench import trace as tr

            jax.profiler.stop_trace()
            summary = tr.summarize(tr.load_events(logdir))
    finally:
        if logdir is not None:
            shutil.rmtree(logdir, ignore_errors=True)
    device = _device(trace, summary)

    win = Window(cell=cell, seconds=t1 - t0, setup_s=t0 - t_start,
                 solves=solves, trace=summary, device_kind=device["kind"])
    counts = {"seconds": win.seconds, "solves": len(solves),
              "updates": win.updates,
              "device_dispatches": sum(r.device_dispatches for r in solves),
              "compiles": n_compiles}
    verdict = check(cell, seed, problem, run_config(cell, seed), solves)
    checks = {k: {"value": v, "limit": cell.config["limits"][k]}
              for k, v in verdict["readings"].items()}

    metrics = {}
    for name in (cell.per_layer if trace else cell.end_to_end):
        value = load_module("metrics", name, cell.base).read(win)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": cell.units[name]}
    line = {"correct": bool(solves) and verdict["failed"] == 0,
            "attempted": len(solves), "failed": verdict["failed"],
            "metrics": metrics, "device": device}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary.top_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = checks
    return Outcome(line=line, window=counts)
