"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own that this module finds by name:

* ``configs/<config>.json`` -- sizes, precision, fleet, faults, the limits
  of the check; its ``family`` names ``problems/<family>.py``, which builds
  the program's problem and holds the plain reference, the kernel costs
  and ``TINY``, the configuration keys that shrink it for the CPU tests;
* ``mixes/<traffic>.json`` -- the keys of ``MIX_KEYS``: the run's
  ``mode``, ``device_plane`` and straggler ``delay_s``; optionally
  ``accel`` (``AndersonConfig`` fields), ``limits`` (the limits of the
  checks the mix adds, beside the configuration's) and ``why`` (free
  text, read by nothing);
* ``metrics/<metric>.py`` -- ``read(window)``, the number or None.

The window is a closed loop of one caller: solves from x0 = 0 back to back
through ``run_fixed_point`` on the thread executor, each given the time
left as its ``max_wall``, until the window's seconds are used up.
"""

from __future__ import annotations

import collections
import contextlib
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


#: the keys a mix file may hold (see the module docstring)
MIX_KEYS = ("mode", "device_plane", "delay_s", "accel", "limits", "why")


@dataclass
class Cell:
    """One cell: its configuration, its mix, the family module the
    configuration names, and the metrics it reports.

    The mix holds ``mode``, ``device_plane`` and ``delay_s`` and may hold
    ``accel`` (see ``run_config``), ``limits`` (see ``limits``) and
    ``why``: ``MIX_KEYS``."""

    name: str
    chips: int
    config: dict
    mix: dict
    family: object
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]
    base: Path = HERE

    @property
    def limits(self) -> Dict[str, float]:
        """The check's limits: the configuration's and the mix's.  A mix
        limits only the checks it adds: naming a limit the configuration
        sets raises ``KeyError``, so that no mix loosens it."""
        own, mix = self.config["limits"], self.mix.get("limits", {})
        both = sorted(set(own) & set(mix))
        if both:
            raise KeyError(f"mix of {self.name!r} sets the limits {both} "
                           "that its configuration sets")
        return {**own, **mix}


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
    """The ``RunConfig`` the mix gives this cell's configuration.

    ``mode``, ``device_plane`` and ``delay_s`` (worker -> seconds added
    per update) are required; ``accel`` becomes ``RunConfig.accel =
    AndersonConfig(**accel)``; ``limits`` is the check's and ``why`` is
    read by nothing.  A key outside ``MIX_KEYS`` raises ``KeyError``,
    so that a misspelt key cannot run a plain window."""
    from repro.core import AndersonConfig, FaultProfile, RunConfig

    mix = cell.mix
    unknown = sorted(set(mix) - set(MIX_KEYS))
    if unknown:
        raise KeyError(f"mix of {cell.name!r} has unknown keys {unknown}; "
                       f"known: {list(MIX_KEYS)}")
    accel = AndersonConfig(**mix["accel"]) if "accel" in mix else None
    return RunConfig(
        executor="thread", mode=mix["mode"],
        n_workers=cell.config["n_workers"], tol=cell.config["tol"],
        device_plane=mix["device_plane"], seed=seed,
        faults={int(w): FaultProfile(delay_mean=d)
                for w, d in mix["delay_s"].items()}, accel=accel, **kw)


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


#: points of the iterate at which the window's Anderson steps are kept
ANDERSON_SAMPLE = 2 ** 16


def anderson_sample(n: int, seed: int) -> np.ndarray:
    """The sorted points, drawn from the seed, at which ``Recorded`` keeps
    the window's Anderson steps: every point where ``n`` is small."""
    if n <= ANDERSON_SAMPLE:
        return np.arange(n)
    rng = np.random.default_rng([seed, 0xA5])
    return np.unique(rng.integers(0, n, ANDERSON_SAMPLE))


class Recorded:
    """The Anderson state that the coordinator of one solve holds, kept
    for the check.

    ``state`` is the program's ``AndersonState``, or what a fault puts in
    its place.  At the points ``at`` this keeps each (x, g, f) that the
    coordinator pushed (``pushed``, the last m + 1, oldest first) and the
    step and coefficients of the last ``propose()`` (``step``, None where
    it gave no step).  A sample and no copy, so that a fire in the window
    pays a gather of ``at`` and no O(n) copy.  Everything else is the
    state's own."""

    def __init__(self, state, at: np.ndarray, m: int):
        self.state, self.at = state, at
        self.pushed = collections.deque(maxlen=m + 1)
        self.step = None

    def push(self, x, g, f) -> None:
        self.pushed.append(tuple(np.array(np.asarray(v)[self.at], np.float64)
                                 for v in (x, g, f)))
        self.state.push(x, g, f)

    def propose(self):
        out = self.state.propose()
        alpha = self.state.last_alpha
        self.step = None if out is None or alpha is None else (
            np.array(np.asarray(out)[self.at], np.float64),
            np.array(alpha, np.float64))
        return out

    def __getattr__(self, name):
        return getattr(self.state, name)


def anderson_gap(accel: dict, rec: Optional[Recorded]) -> float:
    """The last Anderson step of the solve whose state ``rec`` kept,
    against the plain reference (``chipbench/anderson.py``).

    The larger of (a) the window: each row of the state's own window
    (``snapshot()``'s X, G, F) against what the coordinator pushed, at
    the sample, over the pushed rows' largest value; and (b)
    ``chipbench/anderson.py``'s ``gap`` of the step with the state's own
    coefficients: its combine of the pushed rows at the sample, its
    least-squares solve on the state's whole F.  No step, or a window
    that is not the pushed one's length, reads infinite."""
    from chipbench import anderson as ref

    if rec is None or rec.step is None or not rec.pushed:
        return math.inf
    snap = rec.state.snapshot()
    if snap.get("F") is None or len(snap["F"]) != len(rec.pushed):
        return math.inf
    pushed = [np.stack(rows) for rows in zip(*rec.pushed)]  # X, G, F
    window = max(float(np.max(np.abs(np.asarray(snap[k])[:, rec.at] - p))
                       / np.max(np.abs(p)))
                 for k, p in zip("XGF", pushed))
    F = np.asarray(snap["F"], np.float64)
    x_acc, alpha = rec.step
    if len(F) == 1:  # a window of one steps to its map value
        alpha = np.ones(1)
    if len(alpha) != len(F):
        return math.inf
    return max(window, ref.gap(pushed[0], pushed[1], F, x_acc, alpha, accel))


def check(cell: Cell, seed: int, problem, rc, solves: list,
          anderson: Optional[Recorded] = None) -> dict:
    """Each solve's answer against the family's float64 reference.

    ``residual_gap``: the program's first and last recorded residual
    against the reference's at x0 and at the final iterate, as a share of
    the reference's residual at x0.  ``block_step_gap``: one update of
    every block of the final iterate through the program's path against
    the reference's, as a share of the largest reference value.  Where the
    mix has ``accel``: ``anderson_gap``, read once, at the last fire of the
    last solve, from ``anderson``, the state that solve's coordinator held
    (see ``anderson_gap``); and ``acceptless_solves``, the solves in which
    no Anderson step was accepted, limit 0.  Returns the worst reading of
    each over the solves, the limits, and the solves that failed.
    """
    ref = cell.family.Reference(cell.config, seed)
    accel = cell.mix.get("accel")
    limits = dict(cell.limits)
    blocks = problem.default_blocks(cell.config["n_workers"])
    r0 = ref.residual_norm(problem.initial())
    worst = {"residual_gap": 0.0, "block_step_gap": 0.0}
    if accel is not None:
        limits["acceptless_solves"] = 0
        worst.update(anderson_gap=0.0, acceptless_solves=0)
    if set(worst) != set(limits):
        raise KeyError(f"{cell.name}: the check reads {sorted(worst)}, the "
                       f"limits name {sorted(limits)}")
    failed = 0
    for i, res in enumerate(solves):
        x = np.asarray(res.x)
        gap = {"residual_gap": max(
            abs(res.history[0][2] - r0),
            abs(res.residual_norm - ref.residual_norm(x))) / r0}
        steps = [(program_block_step(problem, rc, blk, x),
                  ref.block_step(x, blk)) for blk in blocks]
        gap["block_step_gap"] = max(
            float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            for got, want in steps)
        if accel is not None and i == len(solves) - 1:
            gap["anderson_gap"] = anderson_gap(accel, anderson)
        bad = any(not gap[k] <= limits[k] for k in gap)
        if accel is not None and res.accel_accepts == 0:
            worst["acceptless_solves"] += 1
            bad = True
        failed += bad
        for k in gap:  # a NaN reading stays NaN
            if not gap[k] <= worst[k]:
                worst[k] = gap[k]
    return {"readings": worst, "limits": limits, "failed": failed}


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


@contextlib.contextmanager
def anderson_kept(cell: Cell, seed: int, problem,
                  state_of: Optional[Callable] = None):
    """Inside, where the mix accelerates, the coordinator of each solve
    builds its Anderson state through ``state_of(config)`` (the program's
    ``AndersonState`` where None) and holds it wrapped in a ``Recorded``.
    Yields a list whose one item is the newest solve's ``Recorded``."""
    newest = [None]
    if "accel" not in cell.mix:
        yield newest
        return
    from repro.core.engine import coordinator

    program = coordinator.AndersonState
    make, at = state_of or program, anderson_sample(problem.n, seed)

    def build(config):
        newest[0] = Recorded(make(config), at, config.m)
        return newest[0]

    coordinator.AndersonState = build
    try:
        yield newest
    finally:
        coordinator.AndersonState = program


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, patch: Optional[Callable] = None) -> Outcome:
    """Set up, measure ``seconds``, check, and read the cell's metrics.

    ``t_start`` is the process's start on ``time.perf_counter``.
    ``patch(problem)``, where given, breaks the timed path in place (the
    control and the planted faults of ``faults.py``) and returns the
    factory of the Anderson state that the window's coordinators build in
    the program's place, or None."""
    import jax
    from repro.core import run_fixed_point

    fam = cell.family
    problem = fam.build(cell.config, seed)
    state_of = patch(problem) if patch is not None else None
    fam.prepare(problem)
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        with anderson_kept(cell, seed, problem, state_of) as newest:
            # Warm-up: one short solve of the cell's own run shape compiles
            # (or loads from the persistent cache) every program the window
            # drives.
            run_fixed_point(problem, run_config(
                cell, seed, max_updates=cell.config["n_workers"],
                telemetry=trace or None))
            if trace:
                _profile(logdir)
            c0 = compiles()
            t0 = time.perf_counter()
            solves = []
            with jax.profiler.TraceAnnotation("window"):
                while (left := t0 + seconds - time.perf_counter()) > 0:
                    with jax.profiler.TraceAnnotation("solve"):
                        solves.append(run_fixed_point(problem, run_config(
                            cell, seed, max_wall=left,
                            telemetry=trace or None)))
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
    if cell.mix.get("accel") is not None:
        counts["accel_fires"] = sum(r.accel_fires for r in solves)
        counts["accel_accepts"] = sum(r.accel_accepts for r in solves)
    verdict = check(cell, seed, problem, run_config(cell, seed), solves,
                    newest[0])
    checks = {k: {"value": v, "limit": verdict["limits"][k]}
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
