"""Shared engine datatypes: fault profiles, run configuration, run results.

These are backend-agnostic: the same :class:`RunConfig` drives the
deterministic virtual-time simulator and the real-concurrency thread,
process, and Ray backends (``cfg.executor`` selects which — see
:mod:`repro.core.engine.base`).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

from ..anderson import AndersonConfig

__all__ = ["FaultProfile", "RunConfig", "RunResult", "CoordinatorCrash"]


class CoordinatorCrash(RuntimeError):
    """The control plane died mid-solve.

    Raised out of a backend's coordinator loop when a chaos scenario's
    ``coordinator_crash`` event fires: the session fails (workers keep
    draining into their bounded buffers and are torn down with the loop),
    and any checkpoints written so far stay on disk.  The serve layer's
    crash-retry policy (``ServiceConfig.crash_retries``) catches exactly
    this type and resubmits the solve from the latest checkpoint.
    """


@dataclass
class FaultProfile:
    """Per-worker fault injection (paper §4).

    ``delay``/``noise``/``drop``/``max_staleness`` are the paper's four
    fault channels.  ``crash_prob``/``restart_after`` extend them with
    worker churn: with probability ``crash_prob`` per update the worker
    crashes — its in-flight result is lost — and it rejoins after
    ``restart_after`` seconds (``None`` means it never comes back).  Every
    backend honours the same semantics; the virtual-time backend charges
    virtual seconds for delays and downtime, the thread/process/ray
    backends sleep through real ones.  ``RunResult.restarts`` counts a
    restart when the downtime *ends* on every backend, so a run that stops
    while a worker is still down never reports a restart that did not
    rejoin.
    """

    delay_mean: float = 0.0  # seconds added per update (virtual or real)
    delay_std: float = 0.0
    noise_std: float = 0.0  # additive N(0, std) on returned components
    drop_prob: float = 0.0  # probability a returned update is lost
    max_staleness: Optional[int] = None  # in worker-updates; older => dropped
    crash_prob: float = 0.0  # probability per update the worker crashes
    restart_after: Optional[float] = None  # seconds down; None => permanent
    # Evaluation-service fault channel (``RunConfig.accel_eval="worker"``):
    # probability that one offloaded full-map / residual-norm evaluation is
    # lost in flight.  The coordinator falls back to evaluating that item
    # itself, so a lossy eval service degrades throughput, never correctness.
    eval_crash_prob: float = 0.0
    # Silent-data-corruption channel (Coleman & Sosonkina-style faults that
    # *corrupt* data instead of delaying it): with probability
    # ``corrupt_prob`` per returned update, the worker's value block is
    # corrupted in flight.  Unlike delay/staleness this is not a bounded
    # perturbation — a single corrupted block poisons the iterate and every
    # subsequent Anderson window unless the coordinator-side guard
    # (``RunConfig.sdc_guard``) rejects it.  Modes: ``"bitflip"`` flips one
    # random bit of one float64 element, ``"nan"`` overwrites one element
    # with NaN, ``"scale"`` multiplies one element by 1e8.
    corrupt_prob: float = 0.0
    corrupt_mode: str = "bitflip"  # "bitflip" | "nan" | "scale"

    def sample_delay(self, rng: np.random.Generator) -> float:
        if self.delay_mean == 0.0 and self.delay_std == 0.0:
            return 0.0
        return max(0.0, rng.normal(self.delay_mean, self.delay_std))

    def sample_crash(self, rng: np.random.Generator) -> bool:
        """Draw a crash event; consumes randomness only when enabled."""
        return self.crash_prob > 0.0 and rng.random() < self.crash_prob

    def sample_corrupt(self, rng: np.random.Generator) -> bool:
        """Draw an SDC event; consumes randomness only when enabled."""
        return self.corrupt_prob > 0.0 and rng.random() < self.corrupt_prob

    def corrupt(self, values: np.ndarray,
                rng: np.random.Generator) -> np.ndarray:
        """Return a corrupted *copy* of ``values`` (one element hit)."""
        v = np.array(values, dtype=np.float64)
        i = int(rng.integers(v.size))
        if self.corrupt_mode == "nan":
            v[i] = np.nan
        elif self.corrupt_mode == "scale":
            v[i] *= 1e8
        elif self.corrupt_mode == "bitflip":
            bit = np.uint64(int(rng.integers(64)))
            u = v.view(np.uint64)
            u[i] ^= np.uint64(1) << bit
        else:
            raise ValueError(f"unknown corrupt_mode {self.corrupt_mode!r}")
        return v


@dataclass
class RunConfig:
    """One (a)synchronous run of a fixed-point problem."""

    n_workers: int = 4
    mode: str = "async"  # "sync" | "async"
    # --- execution backend (see repro.core.engine.base) ------------------- #
    executor: str = "virtual"  # "virtual" | "thread" | "process" | "ray"
    # --- acceleration -------------------------------------------------- #
    accel: Optional[AndersonConfig] = None
    accel_mode: str = "coordinator"  # "monitor" | "coordinator" | "periodic"
    fire_every: int = 1  # E: fire each E worker returns (async) / rounds (sync)
    # --- damping -------------------------------------------------------- #
    block_damping: Optional[float] = None  # damped application of block updates
    # --- selection (paper §5.2 / Fig. 6) --------------------------------- #
    selection: str = "fixed"  # "fixed" | "uniform" | "greedy"
    selection_k: Optional[int] = None  # block size for uniform/greedy
    # --- worker return mode (paper §6 future work) ----------------------- #
    return_mode: str = "block"  # "block" | "full_map"
    # --- evaluation pipeline placement (paper §6 redesign) ---------------- #
    # Where the accel/record full-map and safeguard-residual evaluations run
    # in async mode.  "coordinator" (default) evaluates them inline — the
    # pre-existing behaviour, bit-identical on the virtual backend — while
    # "worker" offloads them through the backend's EvalService so fires and
    # residual records overlap with arrivals (the evaluations then see a
    # pinned, slightly stale iterate: evaluation-level staleness only).
    # Sync mode always evaluates coordinator-side (workers idle at the
    # barrier anyway, so there is nothing to overlap with).
    accel_eval: str = "coordinator"  # "coordinator" | "worker"
    # Staleness guard for offloaded fires: if more than this many worker
    # updates were applied between accel_begin and accel_commit, the fire is
    # discarded instead of overwriting the fresher blocks (this is what
    # keeps offload an evaluation-level perturbation rather than
    # iterate-level corruption).  None => 4 * n_workers.
    accel_stale_limit: Optional[int] = None
    # Virtual backend only: seconds of virtual time one offloaded (or, with
    # accel_eval="coordinator", one coordinator-side) full-map /
    # residual-norm evaluation costs.  Setting it (or accel_eval="worker")
    # opts the async virtual loop into the evaluation-cost event model that
    # predicts the offload speedup; None with coordinator eval keeps the
    # golden-tested event loop byte-for-byte.
    eval_time: Optional[float] = None
    # --- termination ------------------------------------------------------ #
    tol: float = 1e-6
    max_updates: int = 200_000
    # Liveness guard: total worker returns (applied + dropped + stale +
    # crashed) before the run stops.  max_updates only counts *applied*
    # updates, so a run whose returns never apply (drop_prob=1, all-crash
    # churn) would otherwise spin forever.  None => 10 * max_updates.
    max_arrivals: Optional[int] = None
    max_wall: Optional[float] = None  # seconds (virtual or real)
    record_every: Optional[int] = None  # residual check cadence (default p)
    # --- determinism / timing --------------------------------------------- #
    seed: int = 0
    compute_time: Optional[float] = None  # virtual s/update; None => measure
    sync_overhead: float = 0.0  # per-round barrier cost (BSP coordination)
    async_overhead: float = 0.0  # per-dispatch cost in async mode
    faults: Union[None, FaultProfile, Dict[int, FaultProfile]] = None
    converge_on: str = "residual"  # "residual" | "error"
    # --- chaos scenarios (repro.chaos) ------------------------------------ #
    # A FaultScenario of timestamped events (set_profile / preempt / join /
    # pause / resume and delay-trace segments) interpreted against virtual
    # time on the virtual backend and wall time on thread/process/ray, so
    # one script means the same thing everywhere.  Preempted workers'
    # blocks are reassigned to the least-loaded survivors (elastic
    # membership) and handed back on join.  Requires selection="fixed";
    # composes with accel_eval="worker" on the real backends (a fire whose
    # begin->commit window crossed a membership change commits only to the
    # blocks whose ownership did not move), while the virtual chaos loop
    # still evaluates coordinator-side.  None keeps every default loop
    # untouched.
    scenario: Optional[object] = None  # repro.chaos.FaultScenario
    # --- closed-loop autoscaling (repro.autoscale) ------------------------ #
    # A Controller policy observing ControlSignals (arrival rate, staleness
    # histogram, accel discard rates, queue depth) at arrival ticks and
    # emitting the same join/preempt/pause/set_profile events scenarios
    # script — actuated through apply_scenario_event on every backend, so
    # one policy means the same thing everywhere and composes with a
    # scripted scenario (script = weather, controller = pilot; the
    # coordinator's safety rails stop a policy from resurrecting workers
    # the script reclaimed or wedging the membership).  Requires
    # selection="fixed".  None keeps every default loop untouched and
    # bit-identical.
    controller: Optional[object] = None  # repro.autoscale.Controller
    # Record the run's event trace (dispatches, arrivals + dispositions,
    # crashes, fires, records, offloads) into RunResult.trace for
    # deterministic postmortem replay (repro.chaos.replay_trace).  Async
    # mode with selection="fixed" only.
    capture_trace: bool = False
    # --- durable solves (repro.recover) ----------------------------------- #
    # Write a SolveCheckpoint (JSON + npz under checkpoint_dir) every this
    # many applied worker updates: a consistent coordinator snapshot taken
    # at an arrival boundary (iterate, rng, Anderson window, membership,
    # accounting, and — on the virtual backend — the event heap, so a
    # resumed virtual run is bit-identical to the uninterrupted one).
    # None disables checkpointing and leaves every default loop untouched.
    checkpoint_every: Optional[int] = None
    checkpoint_dir: Optional[str] = None  # required when checkpoint_every set
    # Resume handle: a repro.recover.SolveCheckpoint (or a path to one).
    # The backend restores the coordinator from it before entering its loop
    # instead of starting from problem.initial_state(); use
    # repro.recover.resume_fixed_point rather than setting this directly.
    resume_from: Optional[object] = None
    # --- SDC quarantine (coordinator-side guard) --------------------------- #
    # Screen every arriving block for NaN/Inf and for update norms that
    # diverge from a windowed baseline of recently accepted update norms;
    # rejected arrivals count RunResult.sdc_rejects (never applied), and a
    # worker collecting sdc_strikes rejections is quarantined — preempted
    # through the elastic-membership machinery, its blocks rebalanced to
    # the survivors (RunResult.quarantined).  Off by default: the guard
    # consumes no randomness and default paths stay bit-identical.
    sdc_guard: bool = False
    sdc_window: int = 32  # baseline window (accepted update norms)
    sdc_threshold: float = 25.0  # reject when norm > threshold * median
    sdc_strikes: int = 3  # rejections before quarantine (0 => never)
    # --- device-resident data plane (kernels + real backends) -------------- #
    # Keep each worker's block resident as a JAX array across the dispatch
    # loop, shipping only halo/dependency slices per dispatch and running
    # the fused block-update(+local-residual) kernels instead of
    # re-materializing the full iterate host-side.  Modes:
    #   "off"        — host numpy path everywhere (pre-existing behaviour)
    #   "auto"       — (default) flips the jnp device path on for real
    #                  backends once n >= 2**20 and the run shape qualifies
    #                  (async, fixed selection, block returns, identity
    #                  projection, no scenario/controller/trace); otherwise
    #                  identical to "off"
    #   "jnp"/"on"   — force the fused jitted-jnp device step
    #   "pallas"     — force the fused Pallas kernels, compiled by Mosaic;
    #                  raises for a float64 iterate (Mosaic lowers no 64-bit
    #                  types) and for value iteration (no general gather)
    #   "interpret"  — force the Pallas kernels in interpret mode (CPU
    #                  validation of the exact kernel bodies; slow)
    # The virtual backend always ignores this knob — fixed-seed virtual
    # runs stay bit-identical to the goldens whatever it is set to.
    device_plane: str = "auto"
    # Unified telemetry plane (repro.telemetry): None (default, zero-cost
    # — no recorder is ever constructed), True, or a TelemetryConfig.
    # When set, the coordinator owns a TelemetryRecorder collecting typed
    # spans + metric series; the full capture lands on RunResult.telemetry
    # and a compact digest on RunResult.telemetry_summary.  The recorder
    # consumes no rng and touches no iterate floats, so enabling it never
    # changes a trajectory on any backend.
    telemetry: Optional[object] = None


@dataclass
class RunResult:
    x: np.ndarray
    converged: bool
    worker_updates: int
    wall_time: float
    residual_norm: float
    history: List[Tuple[float, int, float]]  # (t, WU, residual norm)
    rounds: int = 0  # sync: barrier rounds; async: applied updates
    drops: int = 0
    stale_drops: int = 0
    accel_fires: int = 0
    accel_accepts: int = 0
    accel_rejects: int = 0
    coordinator_evals: int = 0  # full-map evaluations done by the coordinator
    mean_staleness: float = 0.0
    error_norm: Optional[float] = None
    crashes: int = 0  # worker crash events (in-flight update lost)
    restarts: int = 0  # crashed workers that rejoined
    # --- evaluation pipeline (accel_eval="worker") ------------------------ #
    offloaded_evals: int = 0  # eval items served worker-side
    accel_discards: int = 0  # fires dropped by the commit staleness guard
    # Fires whose begin->commit window crossed a membership change and
    # committed restricted to the blocks whose ownership did not move
    # (chaos scenarios composed with accel_eval="worker").
    accel_partial_commits: int = 0
    # Fraction of the run the coordinator spent doing its own work (apply,
    # inline fires/records, commit bookkeeping) — measured on the real
    # backends, modeled on the virtual eval-cost loop, 0.0 otherwise.
    coordinator_busy_frac: float = 0.0
    # Accumulated fire-window time (begin -> commit, backend clock) and the
    # worker updates applied inside those windows: arrivals/sec-while-firing
    # is fire_window_arrivals / fire_window_s (0 when fires are evaluated
    # inline — the coordinator blocks arrivals for the whole window).
    fire_window_s: float = 0.0
    fire_window_arrivals: int = 0
    # --- elastic membership (repro.chaos scenarios) ----------------------- #
    preemptions: int = 0  # workers removed from the membership by a scenario
    joins: int = 0  # workers that (re)joined the membership
    reassigned_blocks: int = 0  # block moves across preempt/join events
    preempt_discards: int = 0  # in-flight results discarded by a preemption
    # Fraction of applied worker updates each worker served (sums to ~1.0
    # over the workers that applied anything; static membership gives each
    # worker ~1/p).
    service_fractions: Dict[int, float] = field(default_factory=dict)
    # --- closed-loop autoscaling (repro.autoscale) ------------------------- #
    # Integral of |active - paused| over the run (the capacity actually
    # provisioned) — the cost model's first factor.  Metered only when a
    # controller is configured (the probe owns the meter); 0.0 otherwise.
    worker_seconds: float = 0.0
    controller_actions: int = 0  # applied controller decisions
    # --- durable solves (repro.recover) ------------------------------------ #
    sdc_rejects: int = 0  # corrupted arrivals rejected by the SDC guard
    quarantined: int = 0  # workers quarantined by the k-strikes policy
    checkpoints_written: int = 0  # SolveCheckpoints written this run
    resumed_from: Optional[str] = None  # checkpoint tag this run resumed from
    # --- device-resident data plane --------------------------------------- #
    # Inline (atomic) accel fires pin the iterate by reference instead of
    # copying all of x — one avoided O(n) copy per inline fire.
    pin_copies_avoided: int = 0
    # Offloaded fires pin lazily (copy-on-write): each counts one O(block)
    # save performed while the pin was unmaterialized, instead of the
    # eager O(n) begin-time copy.
    pin_cow_saves: int = 0
    device_dispatches: int = 0  # block updates served by the device plane
    device_refreshes: int = 0  # device blocks re-synced from the host iterate
    # --- trace capture (cfg.capture_trace) -------------------------------- #
    trace: Optional[object] = None  # repro.chaos.RunTrace
    # --- telemetry plane (cfg.telemetry) ----------------------------------- #
    telemetry: Optional[object] = None  # repro.telemetry.TelemetryCapture
    # Compact digest (staleness p50/p95, busy-frac series tail, span
    # counts, fire ledger) — small enough to ride every benchmark row.
    telemetry_summary: Optional[dict] = None

    # ------------------------------------------------------------------ #
    def to_dict(self, include_history: bool = True,
                include_x: bool = False) -> dict:
        """JSON-safe dict of this result (the one benchmark row schema).

        ``x`` is omitted unless ``include_x`` (it is O(n)); the trace and
        telemetry capture, when present, serialize through their own
        ``to_dict``.  Round-trips through :meth:`from_dict`.
        """
        out: dict = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "x":
                if include_x:
                    out["x"] = np.asarray(v, dtype=np.float64).tolist()
            elif f.name == "history":
                if include_history:
                    out["history"] = [[float(t), int(wu), float(r)]
                                      for t, wu, r in v]
            elif f.name in ("trace", "telemetry"):
                if v is not None:
                    out[f.name] = v.to_dict() if hasattr(v, "to_dict") else v
            elif f.name == "telemetry_summary":
                if v is not None:
                    out["telemetry_summary"] = dict(v)
            elif f.name == "service_fractions":
                out["service_fractions"] = {
                    str(k): float(sv) for k, sv in (v or {}).items()}
            elif f.name == "error_norm":
                out["error_norm"] = None if v is None else float(v)
            elif isinstance(v, (bool, int, str)) or v is None:
                out[f.name] = v
            else:
                out[f.name] = float(v)
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "RunResult":
        """Rebuild a result from :meth:`to_dict` output (e.g. parsed from a
        committed benchmark JSON).  Absent optional payloads come back
        empty: ``x`` as a zero-length array, ``history`` as ``[]``, the
        trace as the raw dict it was serialized to."""
        kw = dict(d)
        kw["x"] = np.asarray(kw.pop("x", []), dtype=np.float64)
        kw["history"] = [(float(t), int(wu), float(r))
                         for t, wu, r in kw.pop("history", [])]
        kw["service_fractions"] = {
            int(k): float(v)
            for k, v in (kw.pop("service_fractions", {}) or {}).items()}
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in kw.items() if k in known})

    def summary(self) -> str:
        return (
            f"converged={self.converged} WU={self.worker_updates} "
            f"wall={self.wall_time:.3f}s res={self.residual_norm:.3e} "
            f"fires={self.accel_fires} acc={self.accel_accepts} "
            f"rej={self.accel_rejects} stale_drops={self.stale_drops}"
        )


def _writable(a: np.ndarray) -> np.ndarray:
    """Return a float64 array that is safe to mutate in place.

    Problem maps are jitted JAX functions; ``np.asarray`` of their outputs
    yields read-only buffers, which the coordinator must not adopt directly.
    """
    a = np.asarray(a, dtype=np.float64)
    return a if a.flags.writeable else a.copy()


def _fault_for(cfg: RunConfig, worker: int) -> FaultProfile:
    if cfg.faults is None:
        return FaultProfile()
    if isinstance(cfg.faults, FaultProfile):
        return cfg.faults
    return cfg.faults.get(worker, FaultProfile())
