"""Real-concurrency thread-pool executor.

Workers are OS threads evaluating ``block_update`` concurrently; straggler
delays are injected with real ``time.sleep`` and wall time is measured with
``time.perf_counter``.  This reproduces the paper's sync-vs-async speedups
on actual hardware (Hannah & Yin, arXiv:1708.05136; Assran et al.,
arXiv:2006.13838: asynchronous gains only manifest under genuine concurrency
with real stragglers) — the virtual-time simulator predicts them, this
backend measures them.

Coordinator state is protected by a single lock; worker evaluations (jitted
JAX / numpy kernels, which release the GIL) and injected sleeps run outside
it, so workers genuinely overlap.  ``cfg.compute_time`` is ignored — compute
cost is whatever the hardware takes.  Runs are NOT bit-reproducible across
invocations (arrival order is real scheduling), but with ``n_workers=1`` the
trajectory matches the synchronous one and converges to the same fixed
point, which is the parity contract tested in ``tests/test_executors.py``.

EvalService (``cfg.accel_eval == "worker"``, async mode): accel fires and
residual records run through the coordinator's begin/feed/commit pipeline
on a dedicated eval thread instead of inline under the lock — the full-map
and safeguard evaluations (which release the GIL) overlap with arrivals,
so the coordinator's lock-held work stays O(block).  A simulated eval-
service fault (``FaultProfile.eval_crash_prob``) makes the pipeline fall
back to coordinator-side evaluation for that item.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor as _Pool
from typing import Optional

import numpy as np

from ..fixedpoint import FixedPointProblem, as_block_slice
from .base import Executor, register_executor
from .coordinator import (
    LAZY_PIN_MIN_N,
    Coordinator,
    warm_problem,
    worker_eval,
)
from .types import (
    CoordinatorCrash,
    FaultProfile,
    RunConfig,
    RunResult,
    _fault_for,
)

__all__ = ["ThreadPoolExecutor"]

# With an autoscale controller and the script drained, an empty/paused
# membership is not necessarily final — the controller may join a spare or
# resume a pause at a later timed tick.  This is how long the loops wait
# for it to do so before declaring the run wedged and stopping; without a
# controller they stop immediately (the pre-existing behaviour).
_CTL_STALL_S = 2.0


@register_executor
class ThreadPoolExecutor(Executor):
    """Concurrent workers in a thread pool; wall time is real seconds."""

    name = "thread"

    def _execute(self, session) -> RunResult:
        problem, cfg = session.problem, session.cfg
        coord = Coordinator(problem, cfg)
        coord.measure_fire_windows = True  # real clock: time inline fires
        # Warm every jit specialization the run will hit (per-block shapes,
        # selection-sized blocks, the accel/residual full-map path) before
        # the clock starts, so compile time doesn't skew wall-clock.  The
        # coordinator's memoized partition is passed through so exactly the
        # dispatched block objects get warmed.
        tel = coord.telemetry
        if tel is not None:
            # Timed on the recorder's host clock; the loop's install_clock
            # re-bases it onto the run's clock, where it ends before the
            # loop starts.
            sec = tel.section("warm", "coord").open()
        warm_problem(problem, cfg, blocks=coord.blocks)
        if cfg.accel is not None:
            problem.full_map(coord.x)
        problem.residual_norm(coord.x)
        if tel is not None:
            sec.close()
        if cfg.capture_trace and cfg.mode == "async":
            from ...chaos.trace import TraceRecorder

            coord.tracer = TraceRecorder(cfg, self.name, problem)
        if cfg.mode == "sync":
            if cfg.scenario is not None or cfg.controller is not None:
                return self._run_sync_chaos(problem, cfg, coord)
            return self._run_sync(problem, cfg, coord)
        if cfg.mode == "async":
            if cfg.scenario is not None or cfg.controller is not None:
                # The chaos loop hosts both eval placements: with
                # accel_eval="worker" it opens fire/record plans and runs
                # them on the eval thread, and commits are restricted to
                # blocks whose ownership did not move (coordinator guard).
                # Controller-driven runs land here too (with an empty
                # ScenarioClock when there is no script): membership can
                # change mid-run, which only this loop's parking handles.
                return self._run_async_chaos(problem, cfg, coord)
            if cfg.accel_eval == "worker":
                return self._run_async_offload(problem, cfg, coord)
            if cfg.capture_trace:
                return self._run_async_chaos(problem, cfg, coord)
            return self._run_async(problem, cfg, coord)
        raise ValueError(f"unknown mode {cfg.mode!r}")

    # ----------------------------------------------------------------- #
    @staticmethod
    def _sync_task(
        problem: FixedPointProblem, cfg: RunConfig, x_snap: np.ndarray,
        idx: np.ndarray, delay: float, crashed: bool,
        profile: FaultProfile, tel=None, task: Optional[int] = None,
        worker: int = 0,
    ) -> Optional[np.ndarray]:
        if tel is not None:
            lane = f"w{worker}"
            sec = tel.section("block_eval", lane, task=task,
                              path="host").open()
        vals = worker_eval(problem, cfg, x_snap, idx)
        if tel is not None:
            sec.close()
        if delay > 0.0:
            if tel is not None:
                sec = tel.section("delay", lane, task=task).open()
            time.sleep(delay)
            if tel is not None:
                sec.close()
        if crashed:
            # BSP: the barrier stalls until the worker restarts; its
            # in-flight result is lost either way.
            if profile.restart_after is not None:
                time.sleep(profile.restart_after)
            return None
        return vals

    def _run_sync(
        self, problem: FixedPointProblem, cfg: RunConfig, coord: Coordinator
    ) -> RunResult:
        t0 = time.perf_counter()
        rounds = 0
        alive = set(range(cfg.n_workers))
        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(lambda: time.perf_counter() - t0)
        coord.record(0.0)
        with _Pool(max_workers=cfg.n_workers) as pool:
            while (coord.wu < cfg.max_updates and alive
                   and coord.arrivals < coord.max_arrivals):
                rounds += 1
                x_snap = coord.x.copy()
                rs = time.perf_counter() - t0
                plans = coord.plan_round(alive, coord.select_round_indices())
                futs = []
                for w, prof, idx, delay, crashed in plans:
                    tid = None
                    if tel is not None:
                        # The task spans the round: open at its start.
                        tid = tel.task_id()
                        tel.task_open(w, rs, task=tid)
                    futs.append(pool.submit(
                        self._sync_task, problem, cfg, x_snap, idx, delay,
                        crashed, prof, tel, tid, w))
                for (w, prof, idx, _, crashed), fut in zip(plans, futs):
                    vals = fut.result()
                    coord.arrivals += 1
                    if tel is not None:
                        tel.task_close(
                            w, disp="crash" if crashed else "applied")
                    if crashed:
                        coord.note_sync_crash(prof, w, alive)
                        continue
                    coord.apply_return(idx, vals, prof, staleness=0)
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
        self, problem: FixedPointProblem, cfg: RunConfig, coord: Coordinator
    ) -> RunResult:
        lock = threading.Lock()
        stop = threading.Event()
        state = {"since_fire": 0}  # arrival/record counters live on coord
        # Per-worker generators for delay/crash draws keep the coordinator
        # rng (drop/noise/selection) behind the lock and everything else out.
        seeds = np.random.SeedSequence(cfg.seed).spawn(cfg.n_workers)
        worker_rngs = [np.random.default_rng(s) for s in seeds]
        # Device-resident data plane (cfg.device_plane): when the run
        # shape qualifies, each worker keeps its block resident as a
        # device array and per dispatch ships only the halo/dependency
        # slices its update reads — the O(n) snapshot copy and full-x
        # transfer disappear from the hot loop.  Resolution is structural
        # (see engine.device_plane); problems opt in per block.  Where
        # every block has a plan and no arrival needs host values, the
        # iterate itself lives on the device too: returns stay there and
        # halos, refreshes and records read the device iterate.
        from .device_plane import resident_iterate, resolve_device_plane

        dmode = resolve_device_plane(problem, cfg, self.name)
        dplans = {}
        if dmode is not None:
            for dw in range(cfg.n_workers):
                dp = problem.device_block_plan(coord.blocks[dw], dmode)
                if dp is not None:
                    dplans[dw] = dp
        resident = len(dplans) == cfg.n_workers and resident_iterate(cfg)
        if cfg.resume_from is not None:
            # Reconstruct a checkpointed solve: the coordinator (and with
            # it the iterate, rng, Anderson window, counters) restores from
            # the snapshot; the wall clock continues from the checkpoint's
            # time so wall_time stays cumulative across the kill.  Worker
            # rngs re-derive from the seed — deterministic single-worker
            # fault-free runs continue bit-identically; faulty multi-worker
            # runs continue correctly (arrival order is real scheduling
            # either way).
            from ...recover.checkpoint import (
                resolve_checkpoint, restore_coordinator)

            ckpt = resolve_checkpoint(cfg.resume_from)
            restore_coordinator(coord, ckpt)
            loop = ckpt.loop
            if loop.get("kind") != "thread_async":
                raise ValueError(
                    f"checkpoint loop state is {loop.get('kind')!r}, not "
                    "resumable on the thread backend's async loop")
            state["since_fire"] = int(loop.get("since_fire", 0))
            t0 = time.perf_counter() - ckpt.t
        else:
            t0 = time.perf_counter()
            if resident:
                coord.attach_device_iterate()
            coord.record(0.0)

        def elapsed() -> float:
            return time.perf_counter() - t0

        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(elapsed)

        def _loop_state():
            return ({"kind": "thread_async",
                     "since_fire": state["since_fire"]}, {})

        if dplans:
            # Warm the fused-kernel specializations before the workers
            # start (mirrors warm_problem for the host path), and where the
            # iterate is on the device its slices and block write: each
            # block is written back unchanged.
            if tel is not None:
                sec = tel.section("warm", "coord").open()
            for dw, dp in dplans.items():
                blk_vals = coord.take(coord.blocks[dw])
                dp.refresh(blk_vals)
                dp.step(*[coord.take(s) for s in dp.needs])
                if resident:
                    coord.x_dev.write(as_block_slice(coord.blocks[dw]),
                                      blk_vals)
            if tel is not None:
                sec.close()

        def worker_loop(w: int) -> None:
            prof = _fault_for(cfg, w)
            rng = worker_rngs[w]
            dp = dplans.get(w)
            dev_fresh = False  # resident block mirrors x[block]?
            dev_cver = -1  # commit_version at the last freshness grant
            blk_vals = None  # block re-shipped this task (device plane)
            if tel is not None:
                lane = f"w{w}"
            while not stop.is_set():
                if tel is not None:
                    # Every section of this task carries its id; the lock
                    # waits are spans only (an annotation would claim the
                    # holder's time).
                    tid = tel.task_id()
                    t_wait = elapsed()
                with lock, coord.busy():
                    if tel is not None:
                        t_held = elapsed()
                    if stop.is_set():
                        return
                    if not coord.dispatchable(w):
                        # Quarantined by the k-strikes SDC policy, or out
                        # of a resumed membership: this thread is done
                        # (static fault-free runs never take this exit).
                        return
                    launch_wu = coord.wu
                    idx = coord.select_indices(w)
                    if tel is not None:
                        tel.span("lock_wait", lane, t_wait, t_held, task=tid,
                                 phase="dispatch")
                        tel.task_open(w, elapsed(), task=tid)
                    if dp is not None:
                        # Fresh resident block: ship only the halo slices
                        # (O(needs)); stale: re-ship the block (O(block)).
                        # Never the full iterate.  Device slices where the
                        # iterate is on the device.
                        blk_vals = None
                        if not (dev_fresh
                                and coord.commit_version == dev_cver):
                            blk_vals = coord.take(idx)
                        need_vals = [coord.take(s) for s in dp.needs]
                    else:
                        x_snap = coord.x.copy()
                if tel is not None:
                    sec = tel.section(
                        "block_eval", lane, task=tid,
                        path="host" if dp is None else "plane",
                        refresh=blk_vals is not None).open()
                if dp is not None:
                    if blk_vals is not None:
                        dp.refresh(blk_vals)
                    vals, dev_norm = dp.step(*need_vals)
                    if not resident:
                        vals = np.asarray(vals)  # the host iterate's copy
                else:
                    vals = worker_eval(problem, cfg, x_snap, idx)
                if tel is not None:
                    sec.close()
                if cfg.async_overhead > 0.0:
                    time.sleep(cfg.async_overhead)
                delay = prof.sample_delay(rng)
                if delay > 0.0:
                    if tel is not None:
                        sec = tel.section("delay", lane, task=tid).open()
                    time.sleep(delay)
                    if tel is not None:
                        sec.close()
                if prof.sample_crash(rng):
                    # A crash is still an arrival: it counts toward the
                    # record cadence and the stop checks must run, or an
                    # all-crashing worker set would spin forever.  The
                    # resident block advanced past the lost return, so it
                    # no longer mirrors x.
                    dev_fresh = False
                    with lock:
                        coord.crashes += 1
                        if tel is not None:
                            tel.task_close(w, disp="crash")
                        if coord.arrival_tick(elapsed()):
                            stop.set()
                    if prof.restart_after is None or stop.is_set():
                        return  # permanent crash (or run over): thread exits
                    time.sleep(prof.restart_after)
                    with lock:
                        if stop.is_set():
                            return  # run ended mid-downtime: never rejoined
                        coord.restarts += 1
                        if tel is not None:
                            tel.instant("restart", f"w{w}")
                    continue
                if tel is not None:
                    t_wait = elapsed()
                with lock, coord.busy():
                    if tel is not None:
                        tel.span("lock_wait", lane, t_wait, elapsed(),
                                 task=tid, phase="arrival")
                    if stop.is_set():
                        if tel is not None:
                            tel.task_close(w, disp="stopped")
                        return
                    if tel is not None:
                        sec = tel.section("apply", lane, task=tid).open()
                    staleness = coord.wu - launch_wu
                    applied = coord.apply_return(
                        idx, vals, prof, staleness=staleness, worker=w
                    )
                    if tel is not None:
                        # Before any inline fire below: its open-task count
                        # must cover only the *other* workers in flight.
                        tel.task_close(
                            w, disp="applied" if applied else "filtered",
                            staleness=staleness)
                    if dp is not None:
                        coord.device_dispatches += 1
                        if blk_vals is not None:
                            coord.device_refreshes += 1
                        coord.device_local_norms[w] = dev_norm
                        # Fresh iff our values landed verbatim; any commit
                        # after this point (own fire below or another
                        # worker's) bumps commit_version and invalidates.
                        dev_fresh = applied and coord.last_apply_verbatim
                        dev_cver = coord.commit_version
                    if applied:
                        state["since_fire"] += 1
                        if (coord.accel is not None
                                and state["since_fire"] >= cfg.fire_every):
                            coord.maybe_fire_accel()
                            state["since_fire"] = 0
                    if tel is not None:
                        sec.close(applied=applied)
                    if coord.arrival_tick(elapsed()):
                        stop.set()
                    coord.maybe_checkpoint(elapsed(), _loop_state)

        threads = [
            threading.Thread(target=worker_loop, args=(w,), daemon=True,
                             name=f"fp-worker-{w}")
            for w in range(cfg.n_workers)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        t = elapsed()
        with lock:
            coord.record(t)
            return coord.result(t, coord.wu, coord.converged())

    # ----------------------------------------------------------------- #
    def _run_sync_chaos(
        self, problem: FixedPointProblem, cfg: RunConfig, coord: Coordinator
    ) -> RunResult:
        """BSP loop under a chaos scenario: events apply at round
        boundaries (the barrier is the BSP granularity); preempted workers
        leave the round set with their blocks served by survivors, paused
        workers idle, and when nobody can take a round the loop sleeps to
        the next scripted event."""
        from ...chaos.scenario import ScenarioClock

        clock = ScenarioClock(cfg.scenario)
        t0 = time.perf_counter()
        rounds = 0
        idle_since = 0.0  # last time a round actually ran (stall window)
        alive = set(range(cfg.n_workers))

        def elapsed() -> float:
            return time.perf_counter() - t0

        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(elapsed)
        coord.record(0.0)

        with _Pool(max_workers=cfg.n_workers) as pool:
            while (coord.wu < cfg.max_updates and alive
                   and coord.arrivals < coord.max_arrivals):
                now = elapsed()
                for ev in clock.due(now):
                    coord.apply_scenario_event(ev, now)
                # Controller decisions land at round boundaries (the BSP
                # granularity); the round set below is re-derived from the
                # membership, so actions need no plumbing.
                coord.controller_tick(now)
                parts = [w for w in coord.round_participants() if w in alive]
                if not parts:
                    nt = clock.next_time()
                    if nt is None:
                        if cfg.controller is None:
                            break  # membership can never recover
                        # A controller may still rebuild the membership
                        # (join a spare, resume a pause) — give it a
                        # bounded stall window of timed ticks.
                        if now - idle_since > _CTL_STALL_S:
                            break
                        if (cfg.max_wall is not None
                                and elapsed() > cfg.max_wall):
                            break
                        time.sleep(0.01)
                        continue
                    time.sleep(max(0.0, nt - elapsed()))
                    continue
                idle_since = elapsed()
                rounds += 1
                x_snap = coord.x.copy()
                rs = elapsed()
                round_idx = {w: coord.round_assignment(w) for w in parts}
                plans = coord.plan_round(set(parts), round_idx)
                futs = [
                    pool.submit(self._sync_task, problem, cfg, x_snap, idx,
                                delay, crashed, prof)
                    for _, prof, idx, delay, crashed in plans
                ]
                for (w, prof, idx, _, crashed), fut in zip(plans, futs):
                    vals = fut.result()
                    coord.arrivals += 1
                    if tel is not None:
                        tel.task_open(w, rs, gen=coord.preempt_gen[w])
                        tel.task_close(
                            w, disp="crash" if crashed else "applied",
                            gen=coord.preempt_gen[w])
                    if crashed:
                        coord.note_sync_crash(prof, w, alive)
                        continue
                    coord.apply_return(idx, vals, prof, staleness=0, worker=w)
                t, verdict = coord.sync_round_tick(rounds, elapsed)
                if verdict in ("diverged", "converged"):
                    return coord.result(t, rounds, verdict == "converged")
                if verdict == "budget":
                    break
        t = elapsed()
        return coord.result(t, rounds, coord.converged())

    # ----------------------------------------------------------------- #
    def _run_async_chaos(
        self, problem: FixedPointProblem, cfg: RunConfig, coord: Coordinator
    ) -> RunResult:
        """Async loop with chaos scenarios and/or trace capture.

        A dedicated chaos-driver thread wakes at each scripted event time
        and applies it under the coordinator lock; worker threads park on
        a condition while they are preempted or paused (and exit once no
        future join can revive them).  A result computed across its
        worker's preemption is discarded at the apply point
        (``preempt_gen`` recognizes the stale incarnation), mirroring the
        virtual backend's semantics on wall clock.

        With ``cfg.accel_eval == "worker"`` the EvalService composes with
        chaos: due fires/records only *open* plans under the lock and
        evaluate on a dedicated eval thread (as in
        :meth:`_run_async_offload`).  A fire whose begin→commit window
        spans a membership change commits restricted to the blocks that
        did not move (the coordinator's ``AccelPlan.mver`` guard).
        """
        from ...chaos.scenario import ScenarioClock

        offload = cfg.accel_eval == "worker"
        lock = threading.Lock()
        cond = threading.Condition(lock)
        stop = threading.Event()
        state = {"since_fire": 0, "fire_plan": None, "rec_plan": None,
                 "crash": None}
        clock = ScenarioClock(cfg.scenario)
        seeds = np.random.SeedSequence(cfg.seed).spawn(cfg.n_workers + 1)
        worker_rngs = [np.random.default_rng(s) for s in seeds[:-1]]
        eval_rng = np.random.default_rng(seeds[-1])
        eval_pool = (_Pool(max_workers=1, thread_name_prefix="fp-eval")
                     if offload else None)
        t0 = time.perf_counter()
        with cond:
            for ev in clock.due(0.0):
                coord.apply_scenario_event(ev, 0.0)
            # Initial controller decision (tick 0) shapes the membership
            # before worker threads take their first dispatch; no plumbing
            # needed — threads park/dispatch off coord.dispatchable.
            coord.controller_tick(0.0)
        coord.record(0.0)

        def elapsed() -> float:
            return time.perf_counter() - t0

        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(elapsed)

        def eval_one(item, prof: FaultProfile):
            e0 = elapsed()
            if (prof.eval_crash_prob > 0.0
                    and eval_rng.random() < prof.eval_crash_prob):
                val, offloaded = coord.eval_item(item), False
            else:
                val, offloaded = coord.eval_item(item), True
            if tel is not None:
                tel.span("eval", "eval", e0, elapsed(), offload=offloaded)
            return val, offloaded

        def run_fire(plan, prof: FaultProfile) -> None:
            if plan._pin_lazy:
                # Lazy pin: snapshot atomically with arrivals, right before
                # the full-map item leaves the lock for the eval thread.
                # (_pin_lazy is set before the plan is submitted and only
                # ever cleared, so the unlocked check is race-free; eager
                # pins skip the lock round-trip entirely.)
                with cond, coord.busy():
                    coord.materialize_pin(plan)
            item = plan.next_item()
            while item is not None:
                val, offloaded = eval_one(item, prof)
                with cond, coord.busy():
                    coord.accel_feed(plan, val, offloaded=offloaded)
                item = plan.next_item()
            with cond, coord.busy():
                if not stop.is_set():
                    coord.accel_commit(plan, t=elapsed())
                state["fire_plan"] = None

        def run_record(plan, prof: FaultProfile) -> None:
            val, offloaded = eval_one(plan.next_item(), prof)
            with cond, coord.busy():
                state["rec_plan"] = None
                if stop.is_set():
                    return
                res = coord.record_commit(plan, val, offloaded=offloaded)
                if not np.isfinite(res) or res > 1e60:
                    stop.set()
                    cond.notify_all()
                elif coord.converged():
                    # Confirm at the live iterate (same contract as the
                    # scenario-free offload loop).
                    res = coord.record(elapsed())
                    if (not np.isfinite(res) or res > 1e60
                            or coord.converged()):
                        stop.set()
                        cond.notify_all()

        def arrival_tick_either(prof: FaultProfile) -> bool:
            """Record-cadence/stop tick; caller holds the lock."""
            if not offload:
                return coord.arrival_tick(elapsed())
            tick_stop, record_due = coord.arrival_tick_offload(elapsed())
            if record_due and state["rec_plan"] is None:
                state["rec_plan"] = coord.record_begin(elapsed())
                eval_pool.submit(run_record, state["rec_plan"], prof)
            return tick_stop

        def chaos_driver() -> None:
            # With a controller the driver doubles as its timed ticker:
            # arrivals normally drive decisions, but when every member is
            # down arrivals stall, and only these timed ticks let the
            # controller rebuild the membership (bounded by _CTL_STALL_S
            # once the script is drained and nothing is live).
            ctl = cfg.controller is not None
            idle_since: Optional[float] = None
            while not stop.is_set():
                nt = clock.next_time()
                if nt is None and not ctl:
                    with cond:
                        if not (coord.active - coord.paused):
                            # Nobody can ever take work again: the script
                            # ended with the membership empty/paused.
                            stop.set()
                            cond.notify_all()
                    return
                if nt is None and ctl:
                    if stop.wait(0.02):
                        return
                    with cond:
                        now = elapsed()
                        acted = bool(coord.controller_tick(now))
                        if acted:
                            cond.notify_all()
                        if (coord.active - coord.paused) or acted:
                            idle_since = None
                        elif idle_since is None:
                            idle_since = now
                        elif now - idle_since > _CTL_STALL_S:
                            stop.set()
                            cond.notify_all()
                            return
                        if cfg.max_wall is not None and now > cfg.max_wall:
                            stop.set()
                            cond.notify_all()
                            return
                    continue
                while True:
                    wait = nt - elapsed()
                    if wait <= 0:
                        break
                    if stop.wait(min(wait, 0.02) if ctl else wait):
                        return
                    if ctl:
                        with cond:
                            if coord.controller_tick(elapsed()):
                                cond.notify_all()
                with cond:
                    now = elapsed()
                    try:
                        for ev in clock.due(now):
                            coord.apply_scenario_event(ev, now)
                    except CoordinatorCrash as e:
                        # The control plane just died.  Stop every worker
                        # (they drain their in-flight results and exit —
                        # nothing commits past this point) and hand the
                        # crash to the main thread to re-raise.
                        state["crash"] = e
                        stop.set()
                        cond.notify_all()
                        return
                    if ctl:
                        coord.controller_tick(now)
                    cond.notify_all()

        def worker_loop(w: int) -> None:
            rng = worker_rngs[w]
            while not stop.is_set():
                with cond:
                    while not stop.is_set() and not coord.dispatchable(w):
                        if clock.exhausted and cfg.controller is None:
                            # join/resume only ever come from the script:
                            # an undispatchable worker with the script
                            # drained can never work again — exit so the
                            # run can finish even if every other worker
                            # is already gone.  (A controller can revive
                            # this worker at any later tick, so keep
                            # parking; the driver's stall window bounds
                            # the wait when nothing can ever recover.)
                            return
                        cond.wait(0.05)
                    if stop.is_set():
                        return
                    gen = coord.preempt_gen[w]
                    x_snap = coord.x.copy()
                    launch_wu = coord.wu
                    bid, idx = coord.next_dispatch(w)
                    prof = coord.fault_for(w)
                    if coord.tracer is not None:
                        coord.tracer.dispatch(elapsed(), w, bid, gen)
                    if tel is not None:
                        tel.task_open(w, elapsed(), gen=gen, block=bid)
                vals = worker_eval(problem, cfg, x_snap, idx)
                if cfg.async_overhead > 0.0:
                    time.sleep(cfg.async_overhead)
                delay = prof.sample_delay(rng)
                if delay > 0.0:
                    time.sleep(delay)
                if prof.sample_crash(rng):
                    with cond, coord.busy():
                        if stop.is_set():
                            return
                        if gen != coord.preempt_gen[w]:
                            coord.preempt_discards += 1
                            if coord.tracer is not None:
                                coord.tracer.arrival(elapsed(), w,
                                                     "preempt_discard",
                                                     gen=gen)
                            if tel is not None:
                                tel.task_close(w, disp="preempt_discard",
                                               gen=gen)
                            continue  # park at loop top until join
                        coord.crashes += 1
                        if coord.tracer is not None:
                            coord.tracer.arrival(elapsed(), w, "crash",
                                                 gen=gen)
                        if tel is not None:
                            tel.task_close(w, disp="crash", gen=gen)
                        if arrival_tick_either(prof):
                            stop.set()
                            cond.notify_all()
                        elif coord.controller_tick(elapsed()):
                            cond.notify_all()  # wake workers a join freed
                    if prof.restart_after is None or stop.is_set():
                        return  # permanent crash (or run over): thread exits
                    time.sleep(prof.restart_after)
                    with cond:
                        if stop.is_set():
                            return
                        if gen == coord.preempt_gen[w]:
                            # Downtime ended inside the same incarnation:
                            # the restart rejoins (downtime-end convention).
                            coord.restarts += 1
                            if coord.tracer is not None:
                                coord.tracer.restart(elapsed(), w)
                            if tel is not None:
                                tel.instant(
                                    "restart",
                                    f"w{w}" if gen == 0 else f"w{w}#r{gen}")
                    continue
                with cond, coord.busy():
                    if stop.is_set():
                        return
                    if gen != coord.preempt_gen[w]:
                        coord.preempt_discards += 1
                        if coord.tracer is not None:
                            coord.tracer.arrival(elapsed(), w,
                                                 "preempt_discard", gen=gen)
                        if tel is not None:
                            tel.task_close(w, disp="preempt_discard",
                                           gen=gen)
                        continue
                    staleness = coord.wu - launch_wu
                    applied = coord.apply_return(
                        idx, vals, prof, staleness=staleness, worker=w
                    )
                    if coord.tracer is not None:
                        coord.tracer.arrival(
                            elapsed(), w,
                            "applied" if applied else "filtered", staleness,
                            gen=gen)
                    if tel is not None:
                        tel.task_close(
                            w, disp="applied" if applied else "filtered",
                            staleness=staleness, gen=gen)
                    if applied:
                        state["since_fire"] += 1
                        if (coord.accel is not None
                                and state["since_fire"] >= cfg.fire_every):
                            if offload:
                                state["since_fire"] = 0
                                if state["fire_plan"] is None:
                                    plan = coord.accel_begin(
                                        elapsed(),
                                        pin=("lazy" if coord.x.size
                                             >= LAZY_PIN_MIN_N else "copy"))
                                    if plan is not None:
                                        state["fire_plan"] = plan
                                        eval_pool.submit(run_fire, plan, prof)
                            else:
                                coord.maybe_fire_accel()
                                state["since_fire"] = 0
                    if arrival_tick_either(prof):
                        stop.set()
                        cond.notify_all()
                    elif coord.controller_tick(elapsed()):
                        # The controller acted at this arrival: a preempt
                        # of this very worker parks it at the loop top (its
                        # gen is stale now); a join frees a parked worker.
                        cond.notify_all()
                    coord.maybe_checkpoint(
                        elapsed(),
                        lambda: ({"kind": "thread_async",
                                  "since_fire": state["since_fire"]}, {}))

        threads = [
            threading.Thread(target=worker_loop, args=(w,), daemon=True,
                             name=f"fp-worker-{w}")
            for w in range(cfg.n_workers)
        ]
        driver = threading.Thread(target=chaos_driver, daemon=True,
                                  name="fp-chaos-driver")
        for th in threads:
            th.start()
        driver.start()
        for th in threads:
            th.join()
        stop.set()  # in-flight plans must not commit after the final record
        driver.join(timeout=5.0)
        if eval_pool is not None:
            eval_pool.shutdown(wait=True)
        if state["crash"] is not None:
            # coordinator_crash scenario event: the run has no result — the
            # serve layer's retry policy resubmits from the latest
            # checkpoint (repro.recover).
            raise state["crash"]
        t = elapsed()
        with lock:
            coord.record(t)
            return coord.result(t, coord.wu, coord.converged())

    # ----------------------------------------------------------------- #
    def _run_async_offload(
        self, problem: FixedPointProblem, cfg: RunConfig, coord: Coordinator
    ) -> RunResult:
        """Async loop with the EvalService on a dedicated eval thread.

        Worker threads behave exactly as in :meth:`_run_async`, but a due
        fire only *opens* an :class:`AccelPlan` under the lock (a lazy
        copy-on-write pin — O(1) at begin, materialized on the eval thread
        right before its first evaluation) — its full-map/safeguard
        evaluations run on the eval thread, which feeds results back and
        commits with the staleness guard.
        Residual records take the same path.  At most one fire and one
        record are in flight; further due fires/records are coalesced.
        """
        lock = threading.Lock()
        stop = threading.Event()
        state = {"since_fire": 0, "fire_plan": None, "rec_plan": None}
        # Per-worker generators for delay/crash draws (as in _run_async);
        # one extra stream drives the eval service's simulated faults.
        seeds = np.random.SeedSequence(cfg.seed).spawn(cfg.n_workers + 1)
        worker_rngs = [np.random.default_rng(s) for s in seeds[:-1]]
        eval_rng = np.random.default_rng(seeds[-1])
        eval_pool = _Pool(max_workers=1, thread_name_prefix="fp-eval")
        t0 = time.perf_counter()
        coord.record(0.0)

        def elapsed() -> float:
            return time.perf_counter() - t0

        tel = coord.telemetry
        if tel is not None:
            tel.install_clock(elapsed)

        def eval_one(item, prof: FaultProfile):
            """Evaluate one pipeline item, simulating eval-service loss.

            Returns ``(value, offloaded)``: a crashed evaluation falls
            back to coordinator-side evaluation of the same item.
            """
            e0 = elapsed()
            if (prof.eval_crash_prob > 0.0
                    and eval_rng.random() < prof.eval_crash_prob):
                val, offloaded = coord.eval_item(item), False
            else:
                val, offloaded = coord.eval_item(item), True
            if tel is not None:
                tel.span("eval", "eval", e0, elapsed(), offload=offloaded)
            return val, offloaded

        def run_fire(plan, prof: FaultProfile) -> None:
            if plan._pin_lazy:
                # Lazy pin: snapshot atomically with arrivals, right before
                # the full-map item leaves the lock for the eval thread.
                # (_pin_lazy is set before the plan is submitted and only
                # ever cleared, so the unlocked check is race-free; eager
                # pins skip the lock round-trip entirely.)
                with lock, coord.busy():
                    coord.materialize_pin(plan)
            item = plan.next_item()
            while item is not None:
                val, offloaded = eval_one(item, prof)
                with lock, coord.busy():
                    coord.accel_feed(plan, val, offloaded=offloaded)
                item = plan.next_item()
            with lock, coord.busy():
                if not stop.is_set():
                    coord.accel_commit(plan, t=elapsed())
                state["fire_plan"] = None

        def run_record(plan, prof: FaultProfile) -> None:
            val, offloaded = eval_one(plan.next_item(), prof)
            with lock, coord.busy():
                state["rec_plan"] = None
                if stop.is_set():
                    return
                res = coord.record_commit(plan, val, offloaded=offloaded)
                if not np.isfinite(res) or res > 1e60:
                    stop.set()
                elif coord.converged():
                    # The offloaded record judged the *pinned* iterate;
                    # arrivals may have landed since.  Confirm at the live
                    # iterate so the final verdict matches the state the
                    # run actually returns (same contract as inline mode).
                    res = coord.record(elapsed())
                    if (not np.isfinite(res) or res > 1e60
                            or coord.converged()):
                        stop.set()

        def worker_loop(w: int) -> None:
            prof = _fault_for(cfg, w)
            rng = worker_rngs[w]
            while not stop.is_set():
                with lock, coord.busy():
                    if stop.is_set():
                        return
                    if not coord.dispatchable(w):
                        return  # quarantined by the k-strikes SDC policy
                    x_snap = coord.x.copy()
                    launch_wu = coord.wu
                    bid, idx = coord.next_dispatch(w)
                    if coord.tracer is not None:
                        coord.tracer.dispatch(elapsed(), w, bid)
                    if tel is not None:
                        tel.task_open(w, elapsed(), block=bid)
                vals = worker_eval(problem, cfg, x_snap, idx)
                if cfg.async_overhead > 0.0:
                    time.sleep(cfg.async_overhead)
                delay = prof.sample_delay(rng)
                if delay > 0.0:
                    time.sleep(delay)
                if prof.sample_crash(rng):
                    with lock, coord.busy():
                        coord.crashes += 1
                        if coord.tracer is not None:
                            coord.tracer.arrival(elapsed(), w, "crash")
                        if tel is not None:
                            tel.task_close(w, disp="crash")
                        tick_stop, record_due = coord.arrival_tick_offload(
                            elapsed())
                        if record_due and state["rec_plan"] is None:
                            state["rec_plan"] = coord.record_begin(elapsed())
                            eval_pool.submit(run_record, state["rec_plan"],
                                             prof)
                        if tick_stop:
                            stop.set()
                    if prof.restart_after is None or stop.is_set():
                        return
                    time.sleep(prof.restart_after)
                    with lock:
                        if stop.is_set():
                            return  # run ended mid-downtime: never rejoined
                        coord.restarts += 1
                        if coord.tracer is not None:
                            coord.tracer.restart(elapsed(), w)
                        if tel is not None:
                            tel.instant("restart", f"w{w}")
                    continue
                with lock, coord.busy():
                    if stop.is_set():
                        return
                    staleness = coord.wu - launch_wu
                    applied = coord.apply_return(
                        idx, vals, prof, staleness=staleness, worker=w
                    )
                    if coord.tracer is not None:
                        coord.tracer.arrival(
                            elapsed(), w,
                            "applied" if applied else "filtered", staleness)
                    if tel is not None:
                        tel.task_close(
                            w, disp="applied" if applied else "filtered",
                            staleness=staleness)
                    if applied:
                        state["since_fire"] += 1
                        if (coord.accel is not None
                                and state["since_fire"] >= cfg.fire_every):
                            state["since_fire"] = 0
                            if state["fire_plan"] is None:
                                plan = coord.accel_begin(
                                    elapsed(),
                                    pin=("lazy" if coord.x.size
                                         >= LAZY_PIN_MIN_N else "copy"))
                                if plan is not None:
                                    state["fire_plan"] = plan
                                    eval_pool.submit(run_fire, plan, prof)
                    tick_stop, record_due = coord.arrival_tick_offload(
                        elapsed())
                    if record_due and state["rec_plan"] is None:
                        state["rec_plan"] = coord.record_begin(elapsed())
                        eval_pool.submit(run_record, state["rec_plan"], prof)
                    if tick_stop:
                        stop.set()

        threads = [
            threading.Thread(target=worker_loop, args=(w,), daemon=True,
                             name=f"fp-worker-{w}")
            for w in range(cfg.n_workers)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        stop.set()  # in-flight plans must not commit after the final record
        eval_pool.shutdown(wait=True)
        t = elapsed()
        with lock:
            coord.record(t)
            return coord.result(t, coord.wu, coord.converged())
