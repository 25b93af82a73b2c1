"""Real-parallelism process-pool executor with persistent worker pools.

Workers are separate Python interpreters, so evaluations escape the GIL
entirely — the closest local analogue of the paper's Ray deployment (§4).
Problem handles do not pickle wholesale (they close over jitted JAX
callables), so each worker rebuilds its own instance from the problem's
``factory_spec()`` recipe.  The coordinator (parent process) keeps the
apply/accel/record path of the thread backend.

Persistent pools
----------------
Spawning a worker costs an interpreter start, a JAX import and a jit
warm-up — easily seconds per worker, which made process-backend sweeps
minutes-long.  Workers are therefore pooled and reused across ``run()``
calls: a pool is keyed on ``(problem-payload fingerprint, n_workers,
return_mode)`` and survives until :func:`shutdown_pools` (registered via
``atexit``), an LRU eviction (``REPRO_PROCESS_POOLS`` pools are kept, default
4), or a worker death.  Each ``run()`` sends a per-run setup message (config,
fault seeds, the coordinator's memoized block partition) and reuses the
already-imported, already-jitted interpreters; a warm run spawns zero new
processes.  A worker whose fault draw says "permanent crash" only *simulates*
death for the remainder of that run — the interpreter stays pooled.

Shared memory
-------------
The global iterate ``x`` travels to workers through a pool-owned
shared-memory block (``shm[0]`` = applied-update counter at the
coordinator's last write, ``shm[1:]`` = x; snapshots are taken under a
cross-process lock so there are no torn reads), and each worker owns a
shared-memory *result slot* it writes its returned value block into — the
result queue carries only ``(worker, kind, length, snapshot_wu)``, so value
blocks are never pickled.  Staleness is measured exactly as in the thread
backend: ``coord.wu - wu_at_snapshot``.

Fault semantics mirror the thread backend exactly: per-worker rngs
(spawned from ``cfg.seed``, fresh each run for reproducibility) drive
delay and crash draws in async mode, the coordinator rng plans them in
sync mode, and drop/noise filtering stays coordinator-side in
``apply_return``.  An async restartable crash reports "crash"
immediately, sleeps out its downtime worker-side, then reports "rejoin" —
the parent counts the restart when that rejoin lands, so (like every
other backend) a run that stops mid-downtime never counts a restart that
did not rejoin.

EvalService (``cfg.accel_eval == "worker"``, async mode)
--------------------------------------------------------
Accel-fire and residual-record evaluations are offloaded to the pool over
an ``("eval", kind)`` message: the coordinator writes the pinned iterate
into the chosen worker's shared-memory *result slot*, the worker evaluates
the full map (result written back into the same slot — full-map arrays are
never pickled) or the residual norm (a scalar over the queue), and the
coordinator feeds the value through the begin/feed/commit pipeline while
every other worker's arrivals keep being applied.  The worker serving an
eval item is simply not redispatched a block task until the item returns —
offload diverts one worker, it never blocks the coordinator.  A simulated
eval-service fault (``FaultProfile.eval_crash_prob``, drawn by the worker)
reports ``eval_crash`` and the coordinator falls back to evaluating that
item itself; a run can lose every offloaded evaluation and still converge.

``cfg.compute_time`` is ignored — compute cost is whatever the hardware
takes.  Pool startup and per-run warm-up happen before ``t0``, so measured
wall-clock covers only the iteration itself.
"""

from __future__ import annotations

import atexit
import os
import queue as queue_mod
import sys
import time
from collections import deque
from multiprocessing import get_context, shared_memory
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ..fixedpoint import FixedPointProblem, as_block_slice
from .base import Executor, register_executor
from .coordinator import (
    AccelPlan,
    Coordinator,
    EvalItem,
    RecordPlan,
    problem_payload,
    rebuild_problem,
    warm_problem,
    worker_eval,
)
from .device_plane import resolve_device_plane
from .poolreg import PoolRegistry, payload_key
from .types import CoordinatorCrash, RunConfig, RunResult, _fault_for

__all__ = [
    "ProcessPoolExecutor",
    "problem_payload",
    "rebuild_problem",
    "shutdown_pools",
    "process_pools",
    "pool_stats",
]

_CTX = get_context("spawn")  # fork is unsafe once JAX/XLA threads exist
_READY_TIMEOUT_S = 300.0  # interpreter + jax import + jit warm-up per worker
_POLL_S = 5.0
#: how many idle pools to keep alive (LRU beyond this is closed)
_MAX_POOLS = max(1, int(os.environ.get("REPRO_PROCESS_POOLS", "4")))
#: grace window (s) a controller gets to revive an empty membership after
#: the script is exhausted, before the chaos loops declare the run dead —
#: mirrors the thread backend's constant of the same name.
_CTL_STALL_S = 2.0


def _attach_shm(name: str) -> shared_memory.SharedMemory:
    """Attach without registering with the resource tracker.

    Python < 3.13 tracks attached segments too, and the tracker would
    unlink the block when any child exits, destroying it for everyone
    (cpython #39959) — suppress registration during attach; the pool owner
    (the parent) unlinks the segments at pool close.
    """
    from multiprocessing import resource_tracker

    _orig_register = resource_tracker.register
    resource_tracker.register = (
        lambda name, rtype: None if rtype == "shared_memory"
        else _orig_register(name, rtype)
    )
    try:
        return shared_memory.SharedMemory(name=name)
    finally:
        resource_tracker.register = _orig_register


def _worker_main(
    w: int, payload, shm_name: str, slot_name: str, n: int,
    shm_lock, task_q, result_q,
) -> None:
    """Persistent worker body: rebuild once, then serve runs until poison.

    Messages in (``task_q``):
      ("run", cfg, seed_seq, my_block)   — per-run setup: warm + reseed
      ("async", idx_or_None)             — snapshot shm, eval, own-rng faults
      ("device", fresh)                  — device-plane dispatch: the block
                                           stays resident worker-side; read
                                           only the plan's halo/dependency
                                           slices from shm (plus the block
                                           itself when ``fresh`` is False)
      ("sync", idx_or_None, delay, crashed) — coordinator-planned faults
      ("eval", kind)                     — EvalService item: the input x is
                                           in this worker's result slot;
                                           kind is "full_map" | "res_norm"
      ("prof", profile)                  — chaos set_profile: delay/crash
                                           draws use ``profile`` from the
                                           next task on
      None                               — shut the interpreter down
    ``my_block`` is this worker's own row of the coordinator's memoized
    partition (the only one it ever evaluates); ``idx_or_None`` of None
    means "your own fixed block", so fixed-selection dispatches never
    pickle index arrays.

    Messages out (``result_q``): ``(w, kind, data, snap_wu)`` with kind in
    {"boot", "ready", "ok", "crash", "rejoin", "eval_ok", "eval_crash",
    "tel", "error"}; "boot" carries the platform the worker's JAX runs on
    ("cpu", or None for a worker that never imported JAX); for "ok" the
    values are in the shared result slot and ``data`` is their length;
    for "eval_ok" the full-map result is in the
    slot (``data`` = its length) or ``data`` is the residual-norm scalar.
    With ``cfg.telemetry`` set, the worker times its own evaluations with
    a local ``perf_counter`` and ships them as ``("tel", [(age_s, dur_s,
    kind), ...])`` batches over the same channel (flushed just before a
    result once ``worker_batch`` spans accumulate; the parent re-anchors
    them on its clock via ``TelemetryRecorder.merge_worker_batch``).  An
    unflushed tail at run end is dropped — span batches are best-effort
    observability, never part of the numeric protocol.
    An async restartable crash reports "crash" with ``data=True`` (it will
    rejoin), sleeps out its downtime, then reports "rejoin" — so the
    parent counts the restart when the downtime *ends*, the same
    convention as every other backend.
    """
    # Host worker: the accelerator belongs to the parent process, and a
    # second process cannot hold the chip, so every child runs JAX on the
    # CPU platform, set before the problem builds anything.
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:  # imported while unpickling the payload
        sys.modules["jax"].config.update("jax_platforms", "cpu")
    shm = slot = None
    try:
        problem = rebuild_problem(payload)
        shm = _attach_shm(shm_name)
        slot = _attach_shm(slot_name)
        view = np.ndarray(n + 1, dtype=np.float64, buffer=shm.buf)
        slot_view = np.ndarray(n, dtype=np.float64, buffer=slot.buf)
        jax_mod = sys.modules.get("jax")
        result_q.put((w, "boot",
                      None if jax_mod is None else jax_mod.default_backend(),
                      0))
        cfg = prof = rng = my_block = dplan = my_read = None
        tel_buf: List[Tuple[float, float, str]] = []  # (end_perf, dur, kind)
        tel_bs = 0  # telemetry batch size; 0 = telemetry off

        def tel_note(kind: str, start_perf: float) -> None:
            if tel_bs:
                end = time.perf_counter()
                tel_buf.append((end, end - start_perf, kind))

        def tel_flush() -> None:
            # Ship a full batch just before a result message, so the
            # parent only ever sees "tel" adjacent to real traffic (the
            # pre-run _await / post-run drain can discard strays safely).
            if tel_bs and len(tel_buf) >= tel_bs:
                now = time.perf_counter()
                result_q.put(
                    (w, "tel",
                     [(now - end, dur, kind) for end, dur, kind in tel_buf],
                     0))
                tel_buf.clear()

        while True:
            task = task_q.get()
            if task is None:
                return
            kind = task[0]
            if kind == "run":
                _, cfg, seed_seq, my_block = task
                tel_bs = (int(getattr(cfg.telemetry, "worker_batch", 32))
                          if cfg.telemetry else 0)
                tel_buf.clear()  # a previous run's unflushed tail
                # First run pays the jit compiles; later runs hit the
                # per-interpreter jit cache and this is near-free.
                warm_problem(problem, cfg, worker=0, blocks=[my_block])
                prof = _fault_for(cfg, w)
                rng = np.random.default_rng(seed_seq)
                # Device-resident data plane: same structural resolution
                # as the parent (the stripped cfg fields — controller,
                # resume_from — only ever relax it, so whenever the parent
                # dispatches ("device", ...) this plan exists).
                dplan = None
                my_read = my_block
                dmode = resolve_device_plane(problem, cfg, "process")
                if dmode is not None:
                    dplan = problem.device_block_plan(my_block, dmode)
                    if dplan is not None:
                        my_read = as_block_slice(my_block)
                        if my_read is None:
                            my_read = my_block
                        zx = np.zeros(n)  # warm the fused-kernel jit now
                        dplan.refresh(zx[my_read])
                        dplan.step(*[zx[s] for s in dplan.needs])
                result_q.put((w, "ready", None, 0))
                continue
            if kind == "prof":
                # Chaos scenario set_profile: applies from the next task.
                prof = task[1]
                continue
            if kind == "eval":
                # Offloaded accel/record evaluation: input x is whatever
                # the coordinator wrote into our (otherwise idle) slot.
                _, ekind = task
                xin = slot_view[:n].copy()
                if (prof.eval_crash_prob > 0.0
                        and rng.random() < prof.eval_crash_prob):
                    tel_flush()
                    result_q.put((w, "eval_crash", None, 0))
                    continue
                e0 = time.perf_counter()
                if ekind == "full_map":
                    g = np.asarray(problem.full_map(xin), dtype=np.float64)
                    slot_view[:n] = g
                    tel_note("eval", e0)
                    tel_flush()
                    result_q.put((w, "eval_ok", n, 0))
                else:
                    rnorm = float(problem.residual_norm(xin))
                    tel_note("eval", e0)
                    tel_flush()
                    result_q.put((w, "eval_ok", rnorm, 0))
                continue
            if kind == "sync":
                _, idx, delay, crashed = task
                idx = my_block if idx is None else idx
                with shm_lock:
                    snap = view.copy()
                c0 = time.perf_counter()
                vals = worker_eval(problem, cfg, snap[1:], idx)
                tel_note("compute", c0)
                if delay > 0.0:
                    time.sleep(delay)
                if crashed:
                    # BSP: the barrier stalls until the worker restarts;
                    # its in-flight result is lost either way.
                    if prof.restart_after is not None:
                        time.sleep(prof.restart_after)
                    tel_flush()
                    result_q.put((w, "crash", None, int(snap[0])))
                else:
                    slot_view[:len(vals)] = vals
                    tel_flush()
                    result_q.put((w, "ok", len(vals), int(snap[0])))
                continue
            if kind == "device":
                # Device-plane dispatch: the resident block advances on
                # the device; only the halo/dependency slices (plus the
                # block itself when the parent flagged it stale) cross
                # from shared memory — never the O(n) iterate.
                _, fresh = task
                with shm_lock:
                    snap_wu = int(view[0])
                    blk = None if fresh else np.copy(view[1:][my_read])
                    needs = [np.copy(view[1:][s]) for s in dplan.needs]
                c0 = time.perf_counter()
                if blk is not None:
                    dplan.refresh(blk)
                vals, dnorm = dplan.step(*needs)
                tel_note("compute", c0)
            else:
                _, idx = task
                idx = my_block if idx is None else idx
                with shm_lock:
                    snap = view.copy()
                snap_wu = int(snap[0])
                c0 = time.perf_counter()
                vals = worker_eval(problem, cfg, snap[1:], idx)
                tel_note("compute", c0)
                dnorm = None
            if cfg.async_overhead > 0.0:
                time.sleep(cfg.async_overhead)
            delay = prof.sample_delay(rng)
            if delay > 0.0:
                time.sleep(delay)
            if prof.sample_crash(rng):
                will_rejoin = prof.restart_after is not None
                tel_flush()
                result_q.put((w, "crash", will_rejoin, snap_wu))
                if not will_rejoin:
                    # Simulated permanent crash: dead for the rest of THIS
                    # run (the parent stops dispatching to us) but the
                    # interpreter survives for the next pooled run.
                    continue
                time.sleep(prof.restart_after)  # downtime before next task
                # Downtime over: report the rejoin so the parent counts
                # the restart now (downtime-end convention, all backends).
                result_q.put((w, "rejoin", None, 0))
                continue
            slot_view[:len(vals)] = vals
            tel_flush()
            if dnorm is None:
                result_q.put((w, "ok", len(vals), snap_wu))
            else:
                # "okd": an "ok" that also carries the fused block-local
                # residual norm the device kernel computed for free.
                result_q.put((w, "okd", (len(vals), dnorm), snap_wu))
    except Exception as e:  # surface rebuild/eval failures to the parent
        import traceback

        result_q.put((w, "error", f"{e!r}\n{traceback.format_exc()}", 0))
    finally:
        if shm is not None:
            shm.close()
        if slot is not None:
            slot.close()


class _WorkerPool:
    """A set of persistent worker interpreters for one (problem, p) pair."""

    def __init__(self, key: Tuple[str, int, str], payload, n: int):
        self.key = key
        self.payload = payload
        self.n = n
        self.n_workers = key[1]
        self.runs_served = 0
        self.shm = shared_memory.SharedMemory(create=True, size=8 * (n + 1))
        self.slots = [
            shared_memory.SharedMemory(create=True, size=8 * max(n, 1))
            for _ in range(self.n_workers)
        ]
        self.view = np.ndarray(n + 1, dtype=np.float64, buffer=self.shm.buf)
        self.slot_views = [
            np.ndarray(n, dtype=np.float64, buffer=s.buf) for s in self.slots
        ]
        self.shm_lock = _CTX.Lock()
        self.task_qs = [_CTX.Queue() for _ in range(self.n_workers)]
        self.result_q = _CTX.Queue()
        self.procs = [
            _CTX.Process(
                target=_worker_main,
                args=(w, payload, self.shm.name, self.slots[w].name, n,
                      self.shm_lock, self.task_qs[w], self.result_q),
                daemon=True, name=f"fp-pool-{w}",
            )
            for w in range(self.n_workers)
        ]
        try:
            for p in self.procs:
                p.start()
            boot = self._await(self.n_workers, {"boot"})
            self.platforms = [boot[w] for w in range(self.n_workers)]
        except Exception:
            self.close()  # don't leak half-booted interpreters / segments
            raise

    # ----------------------------------------------------------------- #
    def healthy(self) -> bool:
        return all(p.is_alive() for p in self.procs)

    def pids(self) -> List[int]:
        return [p.pid for p in self.procs]

    def setup_run(self, cfg: RunConfig, blocks) -> None:
        """Per-run worker (re)configuration: warm, reseed, re-profile.

        Each worker receives only its own block row — at large n the full
        partition is O(n) of int64 per queue, real serialization time on
        the warm-run path."""
        seeds = np.random.SeedSequence(cfg.seed).spawn(cfg.n_workers)
        if cfg.controller is not None or cfg.resume_from is not None:
            # Controllers live coordinator-side only and may hold
            # un-picklable hooks (e.g. a serve-queue depth closure);
            # resume checkpoints carry the coordinator's arrays, which
            # workers have no use for — strip both before the config
            # crosses the process boundary.
            import dataclasses as _dc

            cfg = _dc.replace(cfg, controller=None, resume_from=None)
        for w, q in enumerate(self.task_qs):
            q.put(("run", cfg, seeds[w], blocks[w]))
        self._await(self.n_workers, {"ready"})
        self.runs_served += 1

    def _await(self, count: int, kinds: Set[str]) -> Dict[int, object]:
        """Wait for ``count`` workers' ``kinds`` messages; returns each
        worker's message data."""
        deadline = time.monotonic() + _READY_TIMEOUT_S
        seen: Dict[int, object] = {}
        while len(seen) < count:
            w, kind, data, _ = self.get_result(deadline)
            if kind == "error":
                raise RuntimeError(f"worker {w} failed during startup: {data}")
            if kind == "tel":
                continue  # stray telemetry batch from a stopped run
            assert kind in kinds, f"unexpected pre-run message {kind!r}"
            seen[w] = data
        return seen

    def get_result(self, deadline: float):
        """Blocking result read that notices dead children and timeouts."""
        return self.get_result_wake(deadline, None)

    def drain(self, pending: Set[int], rejoins: Set[int] = frozenset()) -> None:
        """Consume (and discard) in-flight results so the next pooled run
        starts from empty queues.  In-flight work at stop time was equally
        lost by the old spawn-per-run teardown.  ``rejoins`` names workers
        that still owe a post-downtime "rejoin" message (a restartable
        crash whose downtime had not ended when the run stopped)."""
        deadline = time.monotonic() + _READY_TIMEOUT_S
        outstanding = set(pending)
        owed = set(rejoins)
        while outstanding or owed:
            w, kind, data, _ = self.get_result(deadline)
            if kind == "tel":
                continue  # drained telemetry batch: observability only
            if kind == "rejoin":
                owed.discard(w)
            else:
                outstanding.discard(w)
                if kind == "crash" and data:
                    # A drained restartable crash still owes its
                    # post-downtime "rejoin" message.
                    owed.add(w)

    def get_result_wake(self, deadline: float, wake_s: Optional[float]):
        """:meth:`get_result` that additionally returns None once
        ``wake_s`` seconds (from now) elapse with no result — the chaos
        loop's bounded wait, so scripted events are applied on time even
        while every worker is busy."""
        wake = None if wake_s is None else time.monotonic() + max(wake_s, 0.0)
        while True:
            now = time.monotonic()
            if deadline - now <= 0:
                raise RuntimeError(
                    "timed out waiting for process-backend worker results")
            timeout = min(_POLL_S, deadline - now)
            if wake is not None:
                if wake - now <= 0:
                    return None
                timeout = min(timeout, wake - now)
            try:
                return self.result_q.get(timeout=timeout)
            except queue_mod.Empty:
                if wake is not None and time.monotonic() >= wake:
                    return None
                if not any(p.is_alive() for p in self.procs):
                    try:  # drain results that raced with the exits
                        return self.result_q.get_nowait()
                    except queue_mod.Empty:
                        raise RuntimeError(
                            "all process-backend workers exited unexpectedly"
                        ) from None

    def write_x(self, coord: Coordinator) -> None:
        with self.shm_lock:
            self.view[0] = coord.wu
            self.view[1:] = coord.x

    def write_block(self, coord: Coordinator, ind) -> None:
        """O(block) shared-memory sync: mirror one just-applied block (and
        the update counter) instead of rewriting all of x.  Only valid
        when nothing outside ``ind`` changed since the last sync — i.e.
        identity-projection arrivals; commits and projections still go
        through :meth:`write_x`."""
        with self.shm_lock:
            self.view[0] = coord.wu
            self.view[1:][ind] = coord.x[ind]

    def close(self) -> None:
        for q in self.task_qs:
            try:
                q.put_nowait(None)
            except Exception:
                pass
        deadline = time.monotonic() + 10.0
        for p in self.procs:
            if p._popen is None:  # never started (aborted pool boot)
                continue
            p.join(timeout=max(0.1, deadline - time.monotonic()))
            if p.is_alive():
                p.terminate()
        for q in self.task_qs + [self.result_q]:
            q.cancel_join_thread()
            q.close()
        for s in [self.shm] + self.slots:
            s.close()
            try:
                s.unlink()
            except FileNotFoundError:  # pragma: no cover - double close
                pass


# --------------------------------------------------------------------- #
# Pool registry (shared LRU logic in .poolreg, atexit-cleaned)
# --------------------------------------------------------------------- #
_POOLS = PoolRegistry(_MAX_POOLS)


def _acquire_pool(payload, cfg: RunConfig, n: int):
    """Lease the pool for (payload, cfg) — shared, pinned, refcounted.

    Concurrent sessions of the same payload family share one warm pool
    (zero respawn): each takes a lease and serializes its exclusive fleet
    use on the lease's ``run_lock``.  While leased, the pool can neither
    be LRU-evicted nor torn down by a concurrent ``dispose``.
    """
    key = payload_key(payload, cfg)
    return _POOLS.acquire(key, lambda: _WorkerPool(key, payload, n))


def shutdown_pools() -> None:
    """Close every persistent worker pool (also registered via atexit)."""
    _POOLS.shutdown()


class process_pools:
    """Context manager scoping pool lifetime: ``with process_pools(): ...``
    runs any number of process-backend sweeps on warm pools and closes them
    all on exit (long-lived drivers that should not keep idle interpreters
    around; everyone else can rely on the atexit hook)."""

    def __enter__(self) -> "process_pools":
        return self

    def __exit__(self, *exc) -> None:
        shutdown_pools()


def pool_stats() -> Dict[Tuple[str, int, str], Dict[str, object]]:
    """Live pool inventory: pids, runs served and leases, per pool key."""
    return {
        key: {"pids": pool.pids(), "platforms": list(pool.platforms),
              "runs_served": pool.runs_served,
              "n_workers": pool.n_workers, "healthy": pool.healthy(),
              "leases": _POOLS.lease_count(key)}
        for key, pool in _POOLS.items()
    }


atexit.register(shutdown_pools)


# --------------------------------------------------------------------- #
@register_executor
class ProcessPoolExecutor(Executor):
    """Workers in separate interpreters; wall time is real seconds."""

    name = "process"

    def _execute(self, session) -> RunResult:
        problem, cfg = session.problem, session.cfg
        if cfg.mode not in ("sync", "async"):
            raise ValueError(f"unknown mode {cfg.mode!r}")
        payload = problem_payload(problem)
        coord = Coordinator(problem, cfg)
        coord.measure_fire_windows = True  # real clock: time inline fires
        if cfg.accel is not None:
            problem.full_map(coord.x)  # compile the parent-side accel path
            # off-clock (workers warm their own paths at run setup)
        if cfg.capture_trace and cfg.mode == "async":
            from ...chaos.trace import TraceRecorder

            coord.tracer = TraceRecorder(cfg, self.name, problem)
        lease = _acquire_pool(payload, cfg, problem.n)
        try:
            # Exclusive fleet use: concurrent same-family sessions queue
            # here and pipeline over the one warm pool, zero respawns.
            with lease.run_lock:
                pool = lease.pool
                if coord.telemetry is not None:
                    # Pool-plane counters at acquire time: how contended
                    # the warm pool is and whether this family ever had to
                    # respawn a fleet (0 respawns = pure warm reuse).
                    coord.telemetry.series_point(
                        "pool_leases", 0.0, _POOLS.lease_count(lease.key))
                    coord.telemetry.series_point(
                        "pool_respawns", 0.0,
                        max(0, _POOLS.created_count(lease.key) - 1))
                    coord.telemetry.meta["pool_runs_served"] = (
                        pool.runs_served)
                try:
                    pool.setup_run(cfg, coord.blocks)
                    pool.write_x(coord)
                    if cfg.mode == "sync":
                        if (cfg.scenario is not None
                                or cfg.controller is not None):
                            return self._run_sync_chaos(cfg, coord, pool)
                        return self._run_sync(cfg, coord, pool)
                    if cfg.scenario is not None or cfg.controller is not None:
                        # Hosts both eval placements; offloaded fires
                        # commit restricted to unmoved blocks.  Controller
                        # runs land here too (empty ScenarioClock when no
                        # script): only this loop handles elastic
                        # membership.
                        return self._run_async_chaos(cfg, coord, pool)
                    if cfg.accel_eval == "worker":
                        return self._run_async_offload(cfg, coord, pool)
                    if cfg.capture_trace:
                        return self._run_async_chaos(cfg, coord, pool)
                    return self._run_async(cfg, coord, pool)
                except CoordinatorCrash:
                    # coordinator_crash chaos event: the *control plane*
                    # died, not a worker.  The loop drained every in-flight
                    # result before unwinding, so the warm pool is clean
                    # and intact for the resumed session — keep it.
                    raise
                except Exception:
                    # A worker error (or timeout) leaves queues in an
                    # unknown state: retire the whole pool rather than
                    # reuse it (deferred while other sessions hold leases).
                    _POOLS.dispose(pool.key)
                    raise
        finally:
            lease.release()

    # ----------------------------------------------------------------- #
    def _run_sync(
        self, cfg: RunConfig, coord: Coordinator, pool: _WorkerPool
    ) -> RunResult:
        t0 = time.perf_counter()
        rounds = 0
        alive = set(range(cfg.n_workers))
        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(lambda: time.perf_counter() - t0)
        coord.record(0.0)
        while (coord.wu < cfg.max_updates and alive
               and coord.arrivals < coord.max_arrivals):
            rounds += 1
            pool.write_x(coord)
            plans = coord.plan_round(alive, coord.select_round_indices())
            by_worker: Dict[int, Tuple] = {}
            rs = time.perf_counter() - t0  # round dispatch time
            for w, prof, idx, delay, crashed in plans:
                by_worker[w] = (prof, idx, crashed)
                wire_idx = None if idx is coord.blocks[w] else idx
                pool.task_qs[w].put(("sync", wire_idx, delay, crashed))
            deadline = time.monotonic() + _READY_TIMEOUT_S
            remaining = len(plans)
            while remaining:
                w, kind, data, _snap = pool.get_result(deadline)
                if kind == "error":
                    raise RuntimeError(f"worker {w} failed: {data}")
                if kind == "tel":
                    if tel is not None:
                        tel.merge_worker_batch(
                            w, data, time.perf_counter() - t0)
                    continue
                remaining -= 1
                coord.arrivals += 1
                prof, idx, crashed = by_worker[w]
                if crashed:
                    coord.note_sync_crash(prof, w, alive)
                    if tel is not None:
                        tel.task_open(w, rs)
                        tel.task_close(w, disp="crash")
                    continue
                coord.apply_return(idx, pool.slot_views[w][:data], prof,
                                   staleness=0)
                if tel is not None:
                    tel.task_open(w, rs)
                    tel.task_close(w, disp="applied")
            t, verdict = coord.sync_round_tick(
                rounds, lambda: time.perf_counter() - t0)
            if verdict in ("diverged", "converged"):
                return coord.result(t, rounds, verdict == "converged")
            if verdict == "budget":
                break
        t = time.perf_counter() - t0
        return coord.result(t, rounds, coord.converged())

    # ----------------------------------------------------------------- #
    def _run_async(
        self, cfg: RunConfig, coord: Coordinator, pool: _WorkerPool
    ) -> RunResult:
        since_fire = 0
        alive = set(range(cfg.n_workers))
        if cfg.resume_from is not None:
            # Reconstruct a checkpointed solve on the (warm) pool: restore
            # the coordinator, push the restored iterate into shared
            # memory, and continue the wall clock from the checkpoint's
            # time so wall_time stays cumulative across the kill.  The
            # pool lease taken in _execute is the same one any other run
            # takes — a same-payload resume reuses the warm interpreters
            # with zero respawns.
            from ...recover.checkpoint import (
                resolve_checkpoint, restore_coordinator)

            ckpt = resolve_checkpoint(cfg.resume_from)
            restore_coordinator(coord, ckpt)
            loop = ckpt.loop
            if loop.get("kind") != "process_async":
                raise ValueError(
                    f"checkpoint loop state is {loop.get('kind')!r}, not "
                    "resumable on the process backend's async loop")
            since_fire = int(loop.get("since_fire", 0))
            alive = {int(w) for w in loop.get("alive", alive)}
            alive &= {w for w in range(cfg.n_workers)
                      if coord.dispatchable(w)}
            pool.write_x(coord)
            t0 = time.perf_counter() - ckpt.t
        else:
            t0 = time.perf_counter()
            coord.record(0.0)
        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(lambda: time.perf_counter() - t0)
        pending: Dict[int, np.ndarray] = {}  # worker -> dispatched indices
        rejoin_owed: Set[int] = set()  # restartable crashes mid-downtime
        stop = False
        # Device-resident data plane: workers whose block is served by a
        # resident device plan get ("device", fresh) dispatches — only the
        # halo/dependency slices cross shared memory per dispatch, and
        # arrivals sync shm with an O(block) write_block instead of the
        # O(n) write_x (full writes remain only after accel commits).
        # The workers resolve the same structural predicate in their "run"
        # setup, so dispatch kinds and resident plans always agree.
        dmode = resolve_device_plane(coord.problem, cfg, self.name)
        dev_workers: Set[int] = set()
        if dmode is not None:
            dev_workers = {
                w for w in range(cfg.n_workers)
                if coord.problem.device_block_plan(coord.blocks[w], dmode)
                is not None}
        dev_fresh = dict.fromkeys(dev_workers, False)
        dev_cver = dict.fromkeys(dev_workers, -1)

        def _loop_state():
            return ({"kind": "process_async", "since_fire": since_fire,
                     "alive": sorted(alive)}, {})

        def dispatch(w: int) -> None:
            idx = coord.select_indices(w)
            pending[w] = idx
            if tel is not None:
                tel.task_open(w, time.perf_counter() - t0)
            if w in dev_workers:
                fresh = (dev_fresh[w]
                         and coord.commit_version == dev_cver[w])
                coord.device_dispatches += 1
                if not fresh:
                    coord.device_refreshes += 1
                pool.task_qs[w].put(("device", fresh))
            else:
                wire_idx = None if idx is coord.blocks[w] else idx
                pool.task_qs[w].put(("async", wire_idx))

        for w in sorted(alive):
            dispatch(w)
        while alive and not stop:
            deadline = time.monotonic() + _READY_TIMEOUT_S
            w, kind, data, snap_wu = pool.get_result(deadline)
            if kind == "error":
                raise RuntimeError(f"worker {w} failed: {data}")
            if kind == "tel":
                if tel is not None:
                    tel.merge_worker_batch(w, data, time.perf_counter() - t0)
                continue
            if kind == "rejoin":
                # Downtime over: count the restart now (the same
                # downtime-end convention as thread/ray/virtual).
                coord.restarts += 1
                rejoin_owed.discard(w)
                if tel is not None:
                    tel.instant("restart", f"w{w}",
                                time.perf_counter() - t0)
                continue
            with coord.busy():
                prof = _fault_for(cfg, w)
                idx = pending.pop(w)
                redispatch = True
                if kind == "crash":
                    coord.crashes += 1
                    if tel is not None:
                        tel.task_close(w, disp="crash")
                    if w in dev_workers:
                        # The resident block advanced past the lost
                        # return; it no longer mirrors x.
                        dev_fresh[w] = False
                    if not data:  # data=True iff the worker will rejoin
                        alive.discard(w)
                        redispatch = False
                    else:
                        # The restart is counted when the worker's
                        # "rejoin" message lands; its redispatched task
                        # waits out the downtime in its queue.
                        rejoin_owed.add(w)
                else:
                    if kind == "okd":  # device arrival: data carries the
                        vlen, dnorm = data  # fused block-local norm too
                        coord.device_local_norms[w] = float(dnorm)
                    else:
                        vlen = data
                    staleness = coord.wu - snap_wu
                    applied = coord.apply_return(
                        idx, pool.slot_views[w][:vlen], prof,
                        staleness=staleness, worker=w)
                    if tel is not None:
                        # Close before any inline fire below, so its
                        # open-task count covers only the *other* workers.
                        tel.task_close(
                            w, disp="applied" if applied else "filtered",
                            staleness=staleness)
                    if w in dev_workers:
                        # Freshness granted before any commit below: a
                        # fire bumps commit_version and invalidates.
                        dev_fresh[w] = applied and coord.last_apply_verbatim
                        dev_cver[w] = coord.commit_version
                    cv0 = coord.commit_version
                    if applied:
                        since_fire += 1
                        if (coord.accel is not None
                                and since_fire >= cfg.fire_every):
                            coord.maybe_fire_accel()
                            since_fire = 0
                    if (coord.commit_version != cv0
                            or (applied and not coord._trivial_project)):
                        # A commit (or projection) rewrote x wholesale.
                        pool.write_x(coord)
                    elif applied:
                        # Identity-projection arrival: only this block
                        # moved — O(block) shared-memory sync.
                        pool.write_block(coord, idx)
                    # Nothing applied, nothing committed: shm already
                    # mirrors x; skip the write entirely.
                    if cfg.sdc_guard and not coord.dispatchable(w):
                        # Quarantined by the k-strikes policy: stop
                        # dispatching to it (the interpreter stays pooled,
                        # exactly like a simulated permanent crash).
                        alive.discard(w)
                        redispatch = False
                stop = coord.arrival_tick(time.perf_counter() - t0)
                if not stop and redispatch:
                    dispatch(w)
                coord.maybe_checkpoint(time.perf_counter() - t0, _loop_state)
        t = time.perf_counter() - t0
        # In-flight evaluations are discarded (same as the old teardown);
        # draining leaves the pool's queues empty for the next run.
        pool.drain(set(pending), rejoin_owed)
        coord.record(t)
        return coord.result(t, coord.wu, coord.converged())

    # ----------------------------------------------------------------- #
    def _run_sync_chaos(
        self, cfg: RunConfig, coord: Coordinator, pool: _WorkerPool
    ) -> RunResult:
        """BSP loop under a chaos scenario (events at round boundaries;
        see the thread backend's ``_run_sync_chaos`` for the semantics)."""
        from ...chaos.scenario import ScenarioClock

        clock = ScenarioClock(cfg.scenario)
        t0 = time.perf_counter()
        rounds = 0
        alive = set(range(cfg.n_workers))
        def elapsed() -> float:
            return time.perf_counter() - t0

        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(elapsed)
        coord.record(0.0)

        def apply_event(ev, now: float) -> None:
            coord.apply_scenario_event(ev, now)
            if ev.kind == "set_profile":
                targets = ([ev.worker] if ev.worker is not None
                           else range(cfg.n_workers))
                for wt in targets:
                    pool.task_qs[wt].put(("prof", ev.profile))

        idle_since = 0.0
        while (coord.wu < cfg.max_updates and alive
               and coord.arrivals < coord.max_arrivals):
            now = elapsed()
            for ev in clock.due(now):
                apply_event(ev, now)
            for cev in coord.controller_tick(now):
                if cev.kind == "set_profile":
                    targets = ([cev.worker] if cev.worker is not None
                               else range(cfg.n_workers))
                    for wt in targets:
                        pool.task_qs[wt].put(("prof", cev.profile))
            parts = [w for w in coord.round_participants() if w in alive]
            if not parts:
                nt = clock.next_time()
                if nt is None:
                    if cfg.controller is None:
                        break  # membership can never recover
                    # A controller may still rejoin workers — give it a
                    # bounded stall window of timed ticks.
                    now = elapsed()
                    if now - idle_since > _CTL_STALL_S:
                        break
                    if cfg.max_wall is not None and now > cfg.max_wall:
                        break
                    time.sleep(0.01)
                    continue
                time.sleep(max(0.0, nt - elapsed()))
                continue
            idle_since = elapsed()
            rounds += 1
            pool.write_x(coord)
            round_idx = {w: coord.round_assignment(w) for w in parts}
            plans = coord.plan_round(set(parts), round_idx)
            by_worker: Dict[int, Tuple] = {}
            rs = elapsed()  # round dispatch time
            for w, prof, idx, delay, crashed in plans:
                by_worker[w] = (prof, idx, crashed)
                wire_idx = None if idx is coord.blocks[w] else idx
                pool.task_qs[w].put(("sync", wire_idx, delay, crashed))
            deadline = time.monotonic() + _READY_TIMEOUT_S
            remaining = len(plans)
            while remaining:
                w, kind, data, _snap = pool.get_result(deadline)
                if kind == "error":
                    raise RuntimeError(f"worker {w} failed: {data}")
                if kind == "tel":
                    if tel is not None:
                        tel.merge_worker_batch(w, data, elapsed())
                    continue
                remaining -= 1
                coord.arrivals += 1
                prof, idx, crashed = by_worker[w]
                if crashed:
                    coord.note_sync_crash(prof, w, alive)
                    if tel is not None:
                        tel.task_open(w, rs, gen=coord.preempt_gen[w])
                        tel.task_close(w, disp="crash",
                                       gen=coord.preempt_gen[w])
                    continue
                coord.apply_return(idx, pool.slot_views[w][:data], prof,
                                   staleness=0, worker=w)
                if tel is not None:
                    tel.task_open(w, rs, gen=coord.preempt_gen[w])
                    tel.task_close(w, disp="applied",
                                   gen=coord.preempt_gen[w])
            t, verdict = coord.sync_round_tick(rounds, elapsed)
            if verdict in ("diverged", "converged"):
                return coord.result(t, rounds, verdict == "converged")
            if verdict == "budget":
                break
        t = elapsed()
        return coord.result(t, rounds, coord.converged())

    # ----------------------------------------------------------------- #
    def _run_async_chaos(
        self, cfg: RunConfig, coord: Coordinator, pool: _WorkerPool
    ) -> RunResult:
        """Async loop with chaos scenarios and/or trace capture.

        The parent's result wait is bounded by the next scripted event
        time (``get_result_wake``), so events apply on schedule even with
        every worker mid-task.  Preempted workers are simply not
        redispatched (their interpreters stay pooled, exactly like
        simulated permanent crashes); a result that raced its worker's
        preemption is discarded via ``preempt_gen``.  ``set_profile``
        events are forwarded to the worker interpreters as ``("prof", …)``
        messages, which apply from the worker's next task on.

        With ``cfg.accel_eval == "worker"`` the EvalService composes with
        chaos: fire/record evaluations ride the same single-item-in-flight
        pipeline as :meth:`_run_async_offload` (the serving worker must be
        dispatchable; preempted/paused workers never serve evals).  A fire
        whose begin→commit window spans a membership change commits
        restricted to the blocks that did not move (the coordinator's
        ``AccelPlan.mver`` guard).
        """
        from ...chaos.scenario import ScenarioClock

        offload = cfg.accel_eval == "worker"
        clock = ScenarioClock(cfg.scenario)
        t0 = time.perf_counter()
        coord.record(0.0)
        since_fire = 0
        alive = set(range(cfg.n_workers))
        pending: Dict[int, Tuple[np.ndarray, int]] = {}  # w -> (idx, gen)
        rejoin_owed: Set[int] = set()
        rejoin_gen: Dict[int, int] = {}  # incarnation that crashed
        parked: Set[int] = set()  # paused workers with no task in flight
        plans: "deque" = deque()  # eval pipelines; front is being served
        eval_worker: Optional[int] = None
        eval_item: Optional[EvalItem] = None
        stop = False
        crash_box: List[CoordinatorCrash] = []

        def elapsed() -> float:
            return time.perf_counter() - t0

        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(elapsed)

        def _loop_state():
            # Chaos-loop checkpoints resume on the *default* process loop
            # (the script's remaining events die with the control plane).
            return ({"kind": "process_async", "since_fire": since_fire,
                     "alive": sorted(alive)}, {})

        def dispatch(w: int) -> None:
            gen = coord.preempt_gen[w]
            bid, idx = coord.next_dispatch(w)
            pending[w] = (idx, gen)
            wire_idx = None if idx is coord.blocks[w] else idx
            if coord.tracer is not None:
                coord.tracer.dispatch(elapsed(), w, bid, gen)
            if tel is not None:
                tel.task_open(w, elapsed(), gen=gen, block=bid)
            pool.task_qs[w].put(("async", wire_idx))

        def service_eval(w: int) -> bool:
            """Hand dispatchable idle worker ``w`` the front plan's next
            item (its result slot is safe to write exactly now)."""
            nonlocal eval_worker, eval_item
            if eval_worker is not None:
                return False
            while plans:
                front = plans[0]
                if isinstance(front, AccelPlan):
                    # Lazy pin: snapshot now, just before the pinned
                    # iterate leaves the single-threaded parent.
                    coord.materialize_pin(front)
                item = front.next_item()
                if item is None:  # already complete (committed elsewhere)
                    plans.popleft()
                    continue
                pool.slot_views[w][:] = item.x
                pool.task_qs[w].put(("eval", item.kind))
                eval_worker, eval_item = w, item
                return True
            return False

        def idle_or_park(w: int, allow_eval: bool = True) -> None:
            """Redispatch an idle worker (possibly onto an eval item), or
            park it while paused."""
            if coord.dispatchable(w) and w in alive:
                if offload and allow_eval and service_eval(w):
                    return
                dispatch(w)
            elif w in coord.active and w in alive:
                parked.add(w)

        def plumb(ev) -> None:
            """Backend-side effects of a membership event (dispatching,
            parking, profile forwarding) — the coordinator-side state was
            already updated by ``apply_scenario_event``."""
            if ev.kind == "set_profile":
                targets = ([ev.worker] if ev.worker is not None
                           else range(cfg.n_workers))
                for wt in targets:
                    pool.task_qs[wt].put(("prof", ev.profile))
            elif ev.kind == "join":
                parked.discard(ev.worker)
                if (ev.worker not in pending and ev.worker in alive
                        and ev.worker != eval_worker):
                    # An eval-serving worker is redispatched when its item
                    # returns — queueing block work behind the eval would
                    # let the block result clobber the eval's result slot.
                    if coord.dispatchable(ev.worker):
                        dispatch(ev.worker)
                    elif ev.worker in coord.active:
                        parked.add(ev.worker)  # joined into a pause
            elif ev.kind == "resume":
                for wt in sorted(parked):
                    if coord.dispatchable(wt):
                        parked.discard(wt)
                        dispatch(wt)
            elif ev.kind == "preempt":
                parked.discard(ev.worker)

        def apply_event(ev, now: float) -> None:
            try:
                coord.apply_scenario_event(ev, now)
            except CoordinatorCrash as e:
                # The control plane just died.  Remember the crash and let
                # the loop fall through to the drain below: workers keep
                # draining into the pool's bounded queues, which must be
                # empty before the (kept-warm) pool can serve the resumed
                # session.
                crash_box.append(e)
                return
            plumb(ev)

        def ctl_tick(now: float) -> bool:
            """Controller tick: ``controller_tick`` samples signals and
            applies any admissible actions to the coordinator; the
            backend plumbing (dispatch/park) happens here."""
            actions = coord.controller_tick(now)
            for cev in actions:
                plumb(cev)
            return bool(actions)

        def arrival_tick_either() -> bool:
            """Record-cadence/stop tick (offload opens record plans)."""
            if not offload:
                return coord.arrival_tick(elapsed())
            tick_stop, record_due = coord.arrival_tick_offload(elapsed())
            if record_due and not any(isinstance(p, RecordPlan)
                                      for p in plans):
                plans.append(coord.record_begin(elapsed()))
            return tick_stop

        for ev in clock.due(0.0):
            apply_event(ev, 0.0)
        if not crash_box:
            ctl_tick(0.0)  # tick 0: fleet shaping before first dispatch
            for w in sorted(alive):
                if w in pending:
                    continue  # a t=0 join event already dispatched it
                if coord.dispatchable(w):
                    dispatch(w)
                elif w in coord.active:
                    parked.add(w)  # paused before first dispatch: resumable
        idle_since = 0.0
        while alive and not stop and not crash_box:
            now = elapsed()
            for ev in clock.due(now):
                apply_event(ev, now)
            if crash_box:
                break
            ctl_tick(now)
            nt = clock.next_time()
            if not pending and not rejoin_owed and eval_worker is None:
                if nt is None:
                    if cfg.controller is None:
                        break  # nothing in flight, no event can revive us
                    # A controller can still rejoin workers — bounded
                    # stall window of timed ticks, then give up.
                    now = elapsed()
                    if now - idle_since > _CTL_STALL_S:
                        break
                    if cfg.max_wall is not None and now > cfg.max_wall:
                        break
                    time.sleep(0.02)
                    if ctl_tick(elapsed()):
                        idle_since = elapsed()
                    continue
                time.sleep(max(0.0, nt - elapsed()))
                continue
            idle_since = elapsed()
            deadline = time.monotonic() + _READY_TIMEOUT_S
            wake = None if nt is None else nt - elapsed()
            if cfg.controller is not None:
                # Bound the wait so timed controller ticks (tick_dt) fire
                # even while every worker is mid-compute.
                wake = 0.05 if wake is None else min(wake, 0.05)
            res = pool.get_result_wake(deadline, wake)
            if res is None:
                continue  # an event/tick came due; handle at the loop top
            w, kind, data, snap_wu = res
            if kind == "error":
                raise RuntimeError(f"worker {w} failed: {data}")
            if kind == "tel":
                if tel is not None:
                    tel.merge_worker_batch(w, data, elapsed())
                continue
            if kind == "rejoin":
                rejoin_owed.discard(w)
                if rejoin_gen.pop(w, -1) == coord.preempt_gen[w]:
                    # Downtime ended inside the same incarnation: the
                    # restart rejoined (a worker preempted mid-downtime
                    # never did — same convention as the thread backend).
                    coord.restarts += 1
                    if coord.tracer is not None:
                        coord.tracer.restart(elapsed(), w)
                    if tel is not None:
                        g = coord.preempt_gen[w]
                        tel.instant(
                            "restart",
                            f"w{w}" if g == 0 else f"w{w}#r{g}", elapsed())
                continue
            if kind in ("eval_ok", "eval_crash"):
                with coord.busy():
                    plan = plans[0]
                    item = eval_item
                    eval_worker = eval_item = None
                    if kind == "eval_crash":
                        val = coord.eval_item(item)  # crash fallback
                        offloaded = False
                    elif item.kind == EvalItem.FULL_MAP:
                        val = pool.slot_views[w][:data].copy()
                        offloaded = True
                    else:
                        val = data  # residual-norm scalar over the queue
                        offloaded = True
                    if isinstance(plan, AccelPlan):
                        coord.accel_feed(plan, val, offloaded=offloaded)
                        if plan.next_item() is None:
                            plans.popleft()
                            # Restricted commit across membership changes:
                            # only unmoved blocks take the fire.
                            coord.accel_commit(plan, t=elapsed())
                            pool.write_x(coord)
                    else:
                        plans.popleft()
                        res_n = coord.record_commit(plan, val,
                                                    offloaded=offloaded)
                        if not np.isfinite(res_n) or res_n > 1e60:
                            stop = True
                        elif coord.converged():
                            # Confirm at the live iterate (inline-mode
                            # contract).
                            res_n = coord.record(elapsed())
                            if (not np.isfinite(res_n) or res_n > 1e60
                                    or coord.converged()):
                                stop = True
                    if not stop and w not in pending:
                        idle_or_park(w)
                continue
            with coord.busy():
                prof = coord.fault_for(w)
                idx, gen = pending.pop(w)
                if kind == "crash":
                    if data:  # data=True iff the worker will rejoin
                        rejoin_owed.add(w)
                        rejoin_gen[w] = gen
                    if gen != coord.preempt_gen[w]:
                        coord.preempt_discards += 1
                        if coord.tracer is not None:
                            coord.tracer.arrival(elapsed(), w,
                                                 "preempt_discard", gen=gen)
                        if tel is not None:
                            tel.task_close(w, disp="preempt_discard",
                                           gen=gen)
                        # A rejoined worker must get fresh work even though
                        # this (doomed) result was a crash report — its
                        # queued task just waits out the downtime.
                        idle_or_park(w, allow_eval=False)
                        continue
                    coord.crashes += 1
                    if coord.tracer is not None:
                        coord.tracer.arrival(elapsed(), w, "crash", gen=gen)
                    if tel is not None:
                        tel.task_close(w, disp="crash", gen=gen)
                    stop = arrival_tick_either()
                    if not data:
                        alive.discard(w)
                    elif not stop:
                        # The redispatched task waits out the downtime in
                        # the worker's queue (block work only: parking the
                        # single-slot eval service behind that sleep would
                        # systematically stale-discard fires).
                        idle_or_park(w, allow_eval=False)
                    continue
                if gen != coord.preempt_gen[w]:
                    # Preempted (and possibly rejoined) while in flight:
                    # the result predates the reassignment — discard it.
                    coord.preempt_discards += 1
                    if coord.tracer is not None:
                        coord.tracer.arrival(elapsed(), w, "preempt_discard",
                                             gen=gen)
                    if tel is not None:
                        tel.task_close(w, disp="preempt_discard", gen=gen)
                    idle_or_park(w)
                    continue
                staleness = coord.wu - snap_wu
                applied = coord.apply_return(
                    idx, pool.slot_views[w][:data], prof,
                    staleness=staleness, worker=w)
                if coord.tracer is not None:
                    coord.tracer.arrival(
                        elapsed(), w,
                        "applied" if applied else "filtered", staleness,
                        gen=gen)
                if tel is not None:
                    # Close before any fire below: open-task count then
                    # covers only the *other* workers' in-flight work.
                    tel.task_close(
                        w, disp="applied" if applied else "filtered",
                        staleness=staleness, gen=gen)
                if applied:
                    since_fire += 1
                    if (coord.accel is not None
                            and since_fire >= cfg.fire_every):
                        since_fire = 0
                        if offload:
                            # One fire in flight at a time; due fires
                            # while one is pending are coalesced.
                            if not any(isinstance(p, AccelPlan)
                                       for p in plans):
                                plan = coord.accel_begin(elapsed(),
                                                         pin="lazy")
                                if plan is not None:
                                    plans.append(plan)
                        else:
                            coord.maybe_fire_accel()
                pool.write_x(coord)
                stop = arrival_tick_either()
                if not stop:
                    idle_or_park(w)
                coord.maybe_checkpoint(elapsed(), _loop_state)
        t = elapsed()
        outstanding = set(pending)
        if eval_worker is not None:
            outstanding.add(eval_worker)
        pool.drain(outstanding, rejoin_owed)
        if crash_box:
            raise crash_box[0]
        coord.record(t)
        return coord.result(t, coord.wu, coord.converged())

    # ----------------------------------------------------------------- #
    def _run_async_offload(
        self, cfg: RunConfig, coord: Coordinator, pool: _WorkerPool
    ) -> RunResult:
        """Async loop with accel/record evaluations offloaded to the pool.

        The coordinator keeps applying arrivals while at most one eval
        item is in flight on one (momentarily idle) worker; an accel fire
        or residual record is a FIFO of such items (``plans``).  The
        serving worker is not redispatched block work until its item
        returns; every other worker's arrive->apply->redispatch loop is
        untouched — fires overlap with arrivals instead of stalling them.
        """
        t0 = time.perf_counter()
        coord.record(0.0)
        since_fire = 0
        alive = set(range(cfg.n_workers))
        pending: Dict[int, np.ndarray] = {}  # worker -> dispatched indices
        rejoin_owed: Set[int] = set()  # restartable crashes mid-downtime
        plans: "deque" = deque()  # eval pipelines; front is being served
        eval_worker: Optional[int] = None
        eval_item: Optional[EvalItem] = None
        stop = False

        def elapsed() -> float:
            return time.perf_counter() - t0

        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(elapsed)

        def dispatch(w: int) -> None:
            bid, idx = coord.next_dispatch(w)
            pending[w] = idx
            wire_idx = None if idx is coord.blocks[w] else idx
            if coord.tracer is not None:
                coord.tracer.dispatch(elapsed(), w, bid)
            if tel is not None:
                tel.task_open(w, elapsed(), block=bid)
            pool.task_qs[w].put(("async", wire_idx))

        def service_eval(w: int) -> bool:
            """Hand idle worker ``w`` the front plan's next item, if any.

            The input iterate goes through w's result slot, which is safe
            to write exactly now: w's last result has been consumed and it
            has no queued task that could write the slot concurrently.
            """
            nonlocal eval_worker, eval_item
            if eval_worker is not None:
                return False
            while plans:
                front = plans[0]
                if isinstance(front, AccelPlan):
                    # Lazy pin: reconstruct the begin-time snapshot now,
                    # right before the pinned iterate leaves the parent
                    # through the worker's slot (single-threaded parent:
                    # this is atomic with arrivals by construction).
                    coord.materialize_pin(front)
                item = front.next_item()
                if item is None:  # already complete (committed elsewhere)
                    plans.popleft()
                    continue
                pool.slot_views[w][:] = item.x
                pool.task_qs[w].put(("eval", item.kind))
                eval_worker, eval_item = w, item
                return True
            return False

        for w in sorted(alive):
            dispatch(w)
        while alive and not stop:
            deadline = time.monotonic() + _READY_TIMEOUT_S
            w, kind, data, snap_wu = pool.get_result(deadline)
            if kind == "error":
                raise RuntimeError(f"worker {w} failed: {data}")
            if kind == "tel":
                if tel is not None:
                    tel.merge_worker_batch(w, data, elapsed())
                continue
            if kind == "rejoin":
                coord.restarts += 1
                rejoin_owed.discard(w)
                if coord.tracer is not None:
                    coord.tracer.restart(elapsed(), w)
                if tel is not None:
                    tel.instant("restart", f"w{w}", elapsed())
                continue
            if kind in ("eval_ok", "eval_crash"):
                with coord.busy():
                    plan = plans[0]
                    item = eval_item
                    eval_worker = eval_item = None
                    if kind == "eval_crash":
                        # Crash fallback: the offloaded evaluation was
                        # lost — the coordinator evaluates the item itself
                        # and the pipeline continues.
                        val = coord.eval_item(item)
                        offloaded = False
                    elif item.kind == EvalItem.FULL_MAP:
                        val = pool.slot_views[w][:data].copy()
                        offloaded = True
                    else:
                        val = data  # residual-norm scalar over the queue
                        offloaded = True
                    if isinstance(plan, AccelPlan):
                        coord.accel_feed(plan, val, offloaded=offloaded)
                        if plan.next_item() is None:
                            plans.popleft()
                            coord.accel_commit(plan, t=elapsed())
                            pool.write_x(coord)
                    else:
                        plans.popleft()
                        res = coord.record_commit(plan, val,
                                                  offloaded=offloaded)
                        if not np.isfinite(res) or res > 1e60:
                            stop = True
                        elif coord.converged():
                            # Confirm at the live iterate: the offloaded
                            # record judged the pinned one and arrivals
                            # may have landed since (inline-mode contract).
                            res = coord.record(elapsed())
                            if (not np.isfinite(res) or res > 1e60
                                    or coord.converged()):
                                stop = True
                    if not stop and not service_eval(w):
                        dispatch(w)
                continue
            with coord.busy():
                prof = _fault_for(cfg, w)
                idx = pending.pop(w)
                redispatch = True
                if kind == "crash":
                    coord.crashes += 1
                    if coord.tracer is not None:
                        coord.tracer.arrival(elapsed(), w, "crash")
                    if tel is not None:
                        tel.task_close(w, disp="crash")
                    if not data:  # data=True iff the worker will rejoin
                        alive.discard(w)
                        redispatch = False
                    else:
                        rejoin_owed.add(w)
                else:
                    staleness = coord.wu - snap_wu
                    applied = coord.apply_return(
                        idx, pool.slot_views[w][:data], prof,
                        staleness=staleness, worker=w)
                    if coord.tracer is not None:
                        coord.tracer.arrival(
                            elapsed(), w,
                            "applied" if applied else "filtered", staleness)
                    if tel is not None:
                        tel.task_close(
                            w, disp="applied" if applied else "filtered",
                            staleness=staleness)
                    if applied:
                        since_fire += 1
                        if (coord.accel is not None
                                and since_fire >= cfg.fire_every):
                            since_fire = 0
                            # One fire in flight at a time; due fires
                            # while one is pending are coalesced.
                            if not any(isinstance(p, AccelPlan)
                                       for p in plans):
                                plan = coord.accel_begin(elapsed(),
                                                         pin="lazy")
                                if plan is not None:
                                    plans.append(plan)
                    pool.write_x(coord)
                tick_stop, record_due = coord.arrival_tick_offload(elapsed())
                if record_due and not any(isinstance(p, RecordPlan)
                                          for p in plans):
                    plans.append(coord.record_begin(elapsed()))
                if tick_stop:
                    stop = True
                if not stop and redispatch:
                    # A restartable crash redispatches block work only: the
                    # worker sleeps out its downtime before its next task,
                    # and parking the single-slot eval service behind that
                    # sleep would systematically stale-discard fires.
                    if kind == "crash" or not service_eval(w):
                        dispatch(w)
        t = time.perf_counter() - t0
        outstanding = set(pending)
        if eval_worker is not None:
            outstanding.add(eval_worker)
        pool.drain(outstanding, rejoin_owed)
        coord.record(t)
        return coord.result(t, coord.wu, coord.converged())
