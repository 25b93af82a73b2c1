"""Unified telemetry plane: recorder, instrumentation, exporters, CLI.

Covers the acceptance contract of the observability PR:

- zero cost when off: the default config never constructs a recorder and
  serialized results carry no telemetry keys; bit-identity of the virtual
  goldens with telemetry off *and* on (the recorder consumes no rng and
  touches no floats), plus sync-mode off/on parity on the thread and
  process backends;
- ``RunResult.telemetry_summary`` round trips through to_dict/from_dict,
  tolerates unknown keys, and feeds ``benchmarks.common.result_row``;
- the inline observability gap is closed: ``accel_eval="coordinator"``
  runs populate ``coordinator_busy_frac`` and ``fire_window_arrivals``
  when telemetry is on;
- exporters: Chrome trace-event schema (one lane per worker incarnation),
  JSONL stream, Prometheus exposition for the serve layer, and the
  ``python -m repro.launch.run_report`` CLI;
- taxonomy coverage: every scenario event kind and trace event kind has a
  telemetry span mapping, and every emitted series is a registered
  metric;
- the autoscale ``SignalProbe`` shares the recorder's staleness window
  (one buffer for both planes); checkpoint/restore spans; process worker
  span batches (``src="worker"``) and warm-pool lease/respawn series.
"""

import json
import os
import time

import numpy as np
import pytest

from repro.autoscale import get_policy
from repro.chaos import spot_wave
from repro.chaos.scenario import EVENT_KINDS
from repro.chaos.trace import TRACE_EVENT_KINDS
from repro.core import (
    FaultProfile,
    RunConfig,
    RunResult,
    available_executors,
    run_fixed_point,
)
from repro.core.anderson import AndersonConfig
from repro.core.engine.coordinator import Coordinator
from repro.launch.run_report import main as run_report_main
from repro.problems import JacobiProblem
from repro.telemetry import (
    ANNOTATION_PREFIX,
    METRICS,
    SCENARIO_SPAN_MAP,
    SPAN_KINDS,
    TRACE_SPAN_MAP,
    TelemetryCapture,
    TelemetryConfig,
    TelemetryRecorder,
    as_telemetry_config,
    to_chrome_trace,
    to_jsonl,
    to_prometheus,
    validate_chrome_trace,
    worker_lane,
)
from repro.telemetry.export import parse_prometheus, trace_lanes

from conftest import ToyContraction


def _virt_cfg(**kw):
    # compute_time pinned: the virtual clock must be deterministic for
    # the off/on bit-identity comparisons to be exact.
    kw.setdefault("executor", "virtual")
    kw.setdefault("mode", "async")
    kw.setdefault("n_workers", 4)
    kw.setdefault("tol", 1e-300)
    kw.setdefault("max_updates", 400)
    kw.setdefault("compute_time", 1e-3)
    kw.setdefault("seed", 9)
    kw.setdefault("faults", FaultProfile(delay_mean=2e-3, delay_std=1e-3))
    return RunConfig(**kw)


# --------------------------------------------------------------------- #
class TestZeroCostOff:
    def test_default_run_has_no_recorder(self):
        res = run_fixed_point(ToyContraction(n=16), _virt_cfg())
        assert res.telemetry is None
        assert res.telemetry_summary is None
        d = res.to_dict()
        assert "telemetry" not in d and "telemetry_summary" not in d

    def test_virtual_bit_identity_off_and_on(self):
        prob = JacobiProblem(grid=12, sweeps=4, seed=0)
        off = run_fixed_point(prob, _virt_cfg())
        on = run_fixed_point(prob, _virt_cfg(telemetry=True))
        assert off.x.tobytes() == on.x.tobytes()
        assert off.wall_time == on.wall_time
        assert off.worker_updates == on.worker_updates
        assert off.history == on.history
        assert on.telemetry is not None
        assert len(on.telemetry.events) > 0

    @pytest.mark.parametrize("executor", ["thread", "process"])
    def test_sync_parity_real_backends(self, executor):
        # Sync mode: the round plan is deterministic, so the final iterate
        # must be byte-identical with telemetry off vs on.  (Async real
        # backends race arrival order run-to-run, so there is no off-vs-on
        # comparison to make there — off-vs-off already differs.)
        if executor not in available_executors():
            pytest.skip(f"{executor} backend unavailable")
        prob = ToyContraction(n=32, seed=1)
        kw = dict(executor=executor, mode="sync", n_workers=2, seed=4,
                  max_updates=60, tol=1e-300, compute_time=None, faults=None)
        off = run_fixed_point(prob, RunConfig(**kw))
        on = run_fixed_point(prob, RunConfig(**kw, telemetry=True))
        assert off.x.tobytes() == on.x.tobytes()
        assert off.worker_updates == on.worker_updates
        assert on.telemetry_summary["span_counts"]["task"] > 0


# --------------------------------------------------------------------- #
class TestSummaryRoundTrip:
    def _result(self):
        return run_fixed_point(
            JacobiProblem(grid=12, sweeps=4, seed=0),
            _virt_cfg(telemetry=True, accel=AndersonConfig(m=4),
                      fire_every=4))

    def test_to_dict_from_dict(self):
        res = self._result()
        d = res.to_dict(include_history=False)
        assert d["telemetry_summary"] == res.telemetry_summary
        back = RunResult.from_dict(json.loads(json.dumps(d)))
        assert back.telemetry_summary == res.telemetry_summary
        assert back.telemetry["events"] == res.telemetry.to_dict()["events"]

    def test_unknown_keys_tolerated(self):
        d = self._result().to_dict(include_history=False)
        d["telemetry_summary"]["future_field"] = 123
        d["a_key_from_the_future"] = {"x": 1}
        back = RunResult.from_dict(d)
        assert back.telemetry_summary["future_field"] == 123

    def test_capture_round_trip_and_unknown_keys(self):
        cap = self._result().telemetry
        d = cap.to_dict()
        d["summary"]["new"] = 1
        back = TelemetryCapture.from_dict(d)
        assert back.events == cap.events
        assert back.summary["new"] == 1
        with pytest.raises(ValueError):
            TelemetryCapture.from_dict({"version": 999})

    def test_result_row_carries_staleness_digest(self):
        from benchmarks.common import result_row

        res = self._result()
        r = result_row("t", res)
        assert "st_p50=" in r["derived"] and "st_p95=" in r["derived"]
        # Telemetry-off rows stay unchanged.
        off = run_fixed_point(JacobiProblem(grid=12, sweeps=4, seed=0),
                              _virt_cfg())
        assert "st_p50" not in result_row("t", off)["derived"]


# --------------------------------------------------------------------- #
class TestInlineObservability:
    def test_inline_busy_frac_populated(self):
        prob = JacobiProblem(grid=12, sweeps=4, seed=0)
        cfg = dict(accel=AndersonConfig(m=4), fire_every=4,
                   accel_eval="coordinator", max_updates=600)
        off = run_fixed_point(prob, _virt_cfg(**cfg))
        on = run_fixed_point(prob, _virt_cfg(**cfg, telemetry=True))
        # Virtual inline runs meter no busy_s; the recorder's host-clock
        # fraction closes the gap — and only when telemetry is on.
        assert off.coordinator_busy_frac == 0.0
        assert on.coordinator_busy_frac > 0.0
        assert on.x.tobytes() == off.x.tobytes()

    def test_inline_fire_window_arrivals_populated(self):
        prob = JacobiProblem(grid=12, sweeps=4, seed=0)
        cfg = dict(accel=AndersonConfig(m=4), fire_every=4,
                   accel_eval="coordinator", max_updates=600)
        off = run_fixed_point(prob, _virt_cfg(**cfg))
        on = run_fixed_point(prob, _virt_cfg(**cfg, telemetry=True))
        assert off.fire_window_arrivals == 0  # inline, no instrumentation
        assert on.accel_fires > 0
        # With 4 async workers, some dispatch is in flight at every
        # inline fire — the open-task count stands in for the overlap.
        assert on.fire_window_arrivals > 0
        assert on.telemetry_summary["fires"]


# --------------------------------------------------------------------- #
class TestTaxonomyCoverage:
    def test_every_scenario_event_kind_maps(self):
        assert set(EVENT_KINDS) <= set(SCENARIO_SPAN_MAP)
        assert set(SCENARIO_SPAN_MAP.values()) <= set(SPAN_KINDS)

    def test_every_trace_event_kind_maps(self):
        assert set(TRACE_EVENT_KINDS) <= set(TRACE_SPAN_MAP)
        assert set(TRACE_SPAN_MAP.values()) <= set(SPAN_KINDS)

    def test_emitted_series_are_registered_metrics(self):
        res = run_fixed_point(
            JacobiProblem(grid=12, sweeps=4, seed=0),
            _virt_cfg(telemetry=True, accel=AndersonConfig(m=4),
                      fire_every=4))
        assert set(res.telemetry.series) <= set(METRICS)

    def test_emitted_span_kinds_are_registered(self):
        # 1500 updates ≈ 1.1 s virtual: comfortably past the scaled
        # script's last rejoin at 0.42 s; the crash channel makes
        # crash-restart rejoins (the "restart" instant) happen too.
        res = run_fixed_point(
            JacobiProblem(grid=12, sweeps=4, seed=0),
            _virt_cfg(telemetry=True, max_updates=1500,
                      faults=FaultProfile(delay_mean=2e-3, crash_prob=0.02,
                                          restart_after=0.01),
                      scenario=spot_wave(4).scaled(0.2)))
        kinds = {ev["k"] for ev in res.telemetry.events}
        assert kinds <= set(SPAN_KINDS)
        assert "scenario" in kinds and "restart" in kinds


# --------------------------------------------------------------------- #
class TestRecorderUnit:
    def test_config_validation(self):
        with pytest.raises(ValueError):
            TelemetryConfig(ring_size=0)
        with pytest.raises(ValueError):
            TelemetryConfig(series_every=0)
        with pytest.raises(TypeError):
            as_telemetry_config("yes")
        assert as_telemetry_config(True).ring_size == 65536
        cfg = TelemetryConfig(worker_batch=8)
        assert as_telemetry_config(cfg) is cfg

    def test_worker_lane_incarnations(self):
        assert worker_lane(3) == "w3"
        assert worker_lane(3, 2) == "w3#r2"

    def test_ring_drops_are_counted(self):
        rec = TelemetryRecorder(TelemetryConfig(ring_size=4))
        for i in range(10):
            rec.instant("restart", "w0", float(i))
        assert len(rec.events) == 4
        assert rec.dropped == 6
        assert rec.summary()["events_dropped"] == 6

    def test_task_spans_and_open_count(self):
        rec = TelemetryRecorder()
        rec.task_open(0, 1.0)
        rec.task_open(1, 1.5, gen=2, block=3)
        assert rec.open_tasks == 2
        rec.task_close(1, 2.0, disp="applied", staleness=4, gen=2)
        assert rec.open_tasks == 1
        (ev,) = list(rec.events)
        assert ev["lane"] == "w1#r2" and ev["b"] == 3 and ev["s"] == 4
        # Closing an unknown (worker, gen) is a silent no-op (truncation).
        rec.task_close(7, 3.0)
        assert len(rec.events) == 1

    def test_merge_worker_batch_anchors_on_parent_clock(self):
        rec = TelemetryRecorder()
        rec.merge_worker_batch(2, [(0.5, 0.2, "compute")], recv_t=3.0)
        (ev,) = list(rec.events)
        assert ev["src"] == "worker" and ev["lane"] == "w2"
        assert ev["t1"] == pytest.approx(2.5)
        assert ev["t0"] == pytest.approx(2.3)

    def test_staleness_percentiles(self):
        rec = TelemetryRecorder()
        for s in [1] * 60 + [5] * 35 + [9] * 5:
            rec.observe_staleness(s)
        # Nearest-rank over n=100: rank(q) = round(q * 99).
        assert rec.staleness_percentile(0.50) == 1.0
        assert rec.staleness_percentile(0.95) == 5.0
        assert rec.staleness_percentile(1.00) == 9.0


# --------------------------------------------------------------------- #
class TestExporters:
    def _capture(self):
        # Long enough (≈1.1 s virtual) for the scaled spot_wave rejoins
        # at 0.4-0.42 s to open incarnation lanes.
        return run_fixed_point(
            JacobiProblem(grid=12, sweeps=4, seed=0),
            _virt_cfg(telemetry=True, max_updates=1500,
                      scenario=spot_wave(4).scaled(0.2))).telemetry

    def test_chrome_trace_schema_and_lanes(self):
        cap = self._capture()
        doc = to_chrome_trace(cap)
        assert validate_chrome_trace(doc) == []
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "thread_name"}
        assert names == set(trace_lanes(cap))
        # Evicted workers rejoin on fresh incarnation lanes.
        assert any("#r1" in n for n in names)

    def test_validator_catches_violations(self):
        assert validate_chrome_trace({}) == ["traceEvents missing or empty"]
        bad = {"traceEvents": [
            {"ph": "X", "pid": 1, "tid": 0, "ts": 5.0, "dur": -1.0},
            {"ph": "i", "pid": 1, "tid": 0, "ts": 1.0},
        ]}
        errs = validate_chrome_trace(bad)
        assert any("dur" in e for e in errs)
        assert any("not monotone" in e for e in errs)
        assert any("no thread_name" in e for e in errs)

    def test_jsonl_stream(self):
        cap = self._capture()
        lines = to_jsonl(cap).splitlines()
        assert json.loads(lines[0])["meta"]["executor"] == "virtual"
        assert len(lines) == len(cap.events) + 2
        assert "series" in json.loads(lines[-1])

    def test_run_report_cli(self, tmp_path):
        cap = self._capture()
        p = tmp_path / "cap.json"
        cap.save(str(p))
        chrome = tmp_path / "out.trace.json"
        jsonl = tmp_path / "out.jsonl"
        rc = run_report_main([str(p), "--chrome", str(chrome),
                              "--jsonl", str(jsonl), "--validate"])
        assert rc == 0
        doc = json.loads(chrome.read_text())
        assert validate_chrome_trace(doc) == []
        assert jsonl.read_text().count("\n") == len(cap.events) + 2

    def test_run_report_cli_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text('{"no": "telemetry"}')
        assert run_report_main([str(p)]) == 2
        p2 = tmp_path / "runresult.json"
        res = run_fixed_point(ToyContraction(n=16),
                              _virt_cfg(telemetry=True))
        p2.write_text(json.dumps(res.to_dict(include_history=False)))
        assert run_report_main([str(p2)]) == 0  # RunResult shape loads too


# --------------------------------------------------------------------- #
class TestProbeAdapter:
    def test_probe_shares_recorder_staleness_window(self):
        cfg = _virt_cfg(telemetry=True,
                        controller=get_policy("target_staleness", target=4.0))
        coord = Coordinator(ToyContraction(n=16), cfg)
        assert coord.probe is not None and coord.telemetry is not None
        assert coord.probe.telemetry_source is coord.telemetry
        assert coord.probe.staleness is coord.telemetry.staleness_window
        coord.telemetry.observe_staleness(5)
        assert list(coord.probe.staleness) == [5]
        # observe() is a no-op on the probe side: one buffer, fed once.
        coord.probe.observe(7)
        assert list(coord.probe.staleness) == [5]

    def test_controller_run_with_telemetry_converges(self):
        res = run_fixed_point(
            JacobiProblem(grid=12, sweeps=4, seed=0),
            _virt_cfg(telemetry=True, tol=1e-6, max_updates=10**5,
                      n_workers=6,
                      scenario=spot_wave(6).scaled(0.1),
                      controller=get_policy("target_staleness",
                                            target=4.0)))
        assert res.converged
        assert res.telemetry_summary["staleness_n"] > 0


# --------------------------------------------------------------------- #
class TestDurabilitySpans:
    def test_checkpoint_spans_and_restore_instant(self, tmp_path):
        from repro.recover import (
            SolveCheckpoint,
            list_checkpoints,
            resume_fixed_point,
        )

        prob = JacobiProblem(grid=12, sweeps=4, seed=0)
        kw = dict(telemetry=True, max_updates=300,
                  checkpoint_every=100, checkpoint_dir=str(tmp_path))
        res = run_fixed_point(prob, _virt_cfg(**kw))
        counts = res.telemetry_summary["span_counts"]
        assert counts.get("checkpoint", 0) == res.checkpoints_written > 0
        ck = SolveCheckpoint.load(list_checkpoints(str(tmp_path))[0])
        resumed = resume_fixed_point(prob, _virt_cfg(**kw), ck)
        ev = [e for e in resumed.telemetry.events if e["k"] == "restore"]
        assert len(ev) == 1 and ev[0]["tag"] == ck.tag


# --------------------------------------------------------------------- #
@pytest.mark.skipif("process" not in available_executors(),
                    reason="process backend unavailable")
class TestProcessTelemetry:
    def test_worker_span_batches_and_pool_series(self):
        from repro.core import shutdown_pools

        prob = ToyContraction(n=48, seed=0)
        cfg = RunConfig(executor="process", mode="async", n_workers=2,
                        seed=6, max_updates=200, tol=1e-300,
                        telemetry=TelemetryConfig(worker_batch=8))
        try:
            res = run_fixed_point(prob, cfg)
        finally:
            shutdown_pools()
        cap = res.telemetry
        worker_spans = [e for e in cap.events if e.get("src") == "worker"]
        assert worker_spans, "no worker-shipped span batches arrived"
        assert {e["k"] for e in worker_spans} <= {"compute", "eval"}
        assert all(e["t1"] >= e["t0"] >= 0.0 for e in worker_spans)
        assert "pool_leases" in cap.series
        assert "pool_respawns" in cap.series
        # One warm pool, one lease: no respawns counted for this family.
        assert cap.series["pool_respawns"][-1][1] >= 0.0


# --------------------------------------------------------------------- #
class TestServeTelemetry:
    def test_prometheus_exposition(self):
        from repro.serve import ServiceConfig, SolverService

        cfg = RunConfig(executor="virtual", mode="async", n_workers=2,
                        tol=1e-6, max_updates=2000, compute_time=1e-3,
                        seed=0)
        with SolverService(ServiceConfig(max_active=2,
                                         telemetry=True)) as svc:
            tickets = [svc.submit(ToyContraction(n=16, seed=k), cfg,
                                  tenant=f"t{k % 2}")
                       for k in range(3)]
            for t in tickets:
                t.result(timeout=60.0)
            text = to_prometheus(svc)
        parsed = parse_prometheus(text)
        assert parsed['repro_serve_served_total{tenant="t0"}'] == 2.0
        assert parsed['repro_serve_served_total{tenant="t1"}'] == 1.0
        assert 'repro_serve_wait_seconds{quantile="0.5"}' in parsed
        assert 'repro_serve_request_seconds{quantile="0.95"}' in parsed
        assert parsed["repro_serve_queue_depth"] >= 0.0
        spans = [e for e in svc.telemetry.events if e["k"] == "serve"]
        assert len(spans) == 3
        assert {e["lane"] for e in spans} == {"tenant:t0", "tenant:t1"}

    def test_prometheus_without_recorder_still_renders(self):
        from repro.serve import ServiceConfig, SolverService

        with SolverService(ServiceConfig(max_active=1)) as svc:
            assert svc.telemetry is None
            parsed = parse_prometheus(to_prometheus(svc))
        assert parsed["repro_serve_pending"] == 0.0
        assert "repro_serve_queue_depth" not in parsed

    def test_parse_rejects_malformed(self):
        with pytest.raises(ValueError):
            parse_prometheus("this is { not exposition\n")


# --------------------------------------------------------------------- #
# Thread executor: leaf sections on the run's clock and the profiler's
# --------------------------------------------------------------------- #
_STRAGGLE_S = 0.02  # worker 0's delay per update
_SECTION_KINDS = ("lock_wait", "block_eval", "delay", "apply")


def _thread_cfg(mode: str, **kw) -> RunConfig:
    return RunConfig(executor="thread", mode=mode, n_workers=4, seed=5,
                     tol=1e-300, max_updates=24, device_plane="jnp",
                     faults={0: FaultProfile(delay_mean=_STRAGGLE_S)},
                     telemetry=True, **kw)


@pytest.fixture(scope="module")
def thread_runs():
    """One traced async solve on the device plane and one sync solve."""
    prob = JacobiProblem(grid=32, sweeps=4, seed=0)
    return {mode: run_fixed_point(prob, _thread_cfg(mode))
            for mode in ("async", "sync")}


def _spans(res, kind):
    return [e for e in res.telemetry.events if e["k"] == kind]


class TestThreadSections:
    @pytest.mark.parametrize("mode", ["async", "sync"])
    def test_every_record_is_timed(self, thread_runs, mode):
        res = thread_runs[mode]
        recs = _spans(res, "record")
        assert len(recs) == len(res.history)
        assert all(e["t1"] > e["t0"] for e in recs)
        assert [e["res"] for e in recs] == [h[2] for h in res.history]

    def test_async_block_evals_take_the_device_plane(self, thread_runs):
        res = thread_runs["async"]
        evals = _spans(res, "block_eval")
        assert {e["path"] for e in evals} == {"plane"}
        # A task evaluated when the run stopped is never applied.
        assert (res.device_dispatches <= len(evals)
                <= res.device_dispatches + 4)
        assert res.device_dispatches == res.worker_updates
        assert any(e["refresh"] for e in evals)

    def test_sync_block_evals_take_the_host_path(self, thread_runs):
        res = thread_runs["sync"]
        evals = _spans(res, "block_eval")
        assert len(evals) == res.worker_updates
        assert {e["path"] for e in evals} == {"host"}
        assert not _spans(res, "lock_wait")  # the sync loop has no lock

    @pytest.mark.parametrize("mode", ["async", "sync"])
    def test_straggler_delay_spans(self, thread_runs, mode):
        res = thread_runs[mode]
        delays = _spans(res, "delay")
        assert delays and {e["lane"] for e in delays} == {"w0"}
        assert all(e["t1"] - e["t0"] >= _STRAGGLE_S for e in delays)

    @pytest.mark.parametrize("mode", ["async", "sync"])
    def test_sections_carry_their_task_id(self, thread_runs, mode):
        res = thread_runs[mode]
        tasks = {e["task"]: e for e in _spans(res, "task")}
        assert len(tasks) == len(_spans(res, "task"))
        sections = [e for e in res.telemetry.events
                    if e["k"] in _SECTION_KINDS]
        assert {e["k"] for e in sections} >= {"block_eval", "delay"}
        for e in sections:
            assert e["task"] in tasks, e
            assert e["lane"] == tasks[e["task"]]["lane"]
        if mode == "async":
            phases = {e["phase"] for e in _spans(res, "lock_wait")}
            assert phases == {"dispatch", "arrival"}
            applies = _spans(res, "apply")
            assert sum(e["applied"] for e in applies) == res.worker_updates

    @pytest.mark.parametrize("mode", ["async", "sync"])
    def test_warm_up_ends_before_the_clock_starts(self, thread_runs, mode):
        warms = sorted(_spans(thread_runs[mode], "warm"),
                       key=lambda e: e["t0"])
        assert warms and warms[0]["lane"] == "coord"
        assert warms[0]["t0"] < warms[0]["t1"] <= 0.0
        # async: the device plan's warm-up runs once the clock has started
        assert len(warms) == (2 if mode == "async" else 1)

    @pytest.mark.parametrize("mode", ["async", "sync"])
    def test_spans_lie_on_one_clock(self, thread_runs, mode):
        res = thread_runs[mode]
        spans = [e for e in res.telemetry.events if "t0" in e]
        # async: the final record follows the stop at wall_time
        end = max([res.wall_time] + [e["t1"] for e in _spans(res, "record")])
        for e in spans:
            if e["k"] != "warm":
                assert 0.0 <= e["t0"] <= e["t1"] <= end, e
        # Each record starts where the history's clock read its time.
        for e, (t, _, _) in zip(_spans(res, "record"), res.history):
            assert 0.0 <= e["t0"] - t < 0.05

    def test_span_kinds_are_registered(self, thread_runs):
        for res in thread_runs.values():
            kinds = {e["k"] for e in res.telemetry.events}
            assert kinds <= set(SPAN_KINDS)

    def test_sections_are_profiler_annotations(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        prob = JacobiProblem(grid=32, sweeps=4, seed=0)
        run_fixed_point(prob, _thread_cfg("async"))  # compile outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("window"):
                run_fixed_point(prob, _thread_cfg("async"))
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        names = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    names.setdefault(ev.name, set()).add(
                        (plane.name, line.name))
        (window_line,) = names["window"]
        want = {ANNOTATION_PREFIX + k
                for k in ("record", "block_eval", "delay", "apply", "warm")}
        assert ANNOTATION_PREFIX == "solver."
        for name in want:
            assert window_line in names.get(name, ()), name
        assert ANNOTATION_PREFIX + "lock_wait" not in names
        assert not any(n.startswith(ANNOTATION_PREFIX + "task") for n in names)

    def test_export_starts_at_the_warm_up(self, thread_runs):
        cap = thread_runs["async"].telemetry
        doc = to_chrome_trace(cap)
        assert validate_chrome_trace(doc) == []
        origin = min(e["t0"] for e in cap.events if "t0" in e)
        assert origin < 0.0
        assert doc["otherData"]["t_origin_s"] == origin


class TestSectionUnit:
    def test_section_times_its_body_and_takes_args(self):
        rec = TelemetryRecorder()
        clock = iter([1.0, 3.5])
        rec.install_clock(lambda: next(clock))
        with rec.section("block_eval", "w2", task=7) as sec:
            sec.args["path"] = "host"
        (ev,) = list(rec.events)
        assert ev == {"k": "block_eval", "lane": "w2", "t0": 1.0, "t1": 3.5,
                      "task": 7, "path": "host"}

    def test_open_close_and_task_ids(self):
        rec = TelemetryRecorder()
        rec.install_clock(lambda: 2.0)
        a, b = rec.task_id(), rec.task_id()
        assert b == a + 1
        rec.task_open(0, 1.0, task=a)
        rec.section("apply", "w0", task=a).open().close(
            applied=True)
        rec.task_close(0, 2.5)
        app, task = list(rec.events)
        assert app["applied"] is True and app["task"] == task["task"] == a

    def test_sections_before_the_clock_move_onto_it(self):
        rec = TelemetryRecorder()
        with rec.section("warm", "coord"):
            pass
        rec.instant("restart", "w0", 0.25)  # caller's clock: left alone
        t0 = time.perf_counter()
        rec.install_clock(lambda: time.perf_counter() - t0)
        warm, restart = list(rec.events)
        assert warm["t0"] <= warm["t1"] <= 0.0
        assert warm["t1"] > -1.0
        assert restart["t"] == 0.25
        with rec.section("record", "coord"):
            pass
        assert list(rec.events)[-1]["t0"] >= 0.0

    @pytest.mark.parametrize("loop", [
        {},
        {"mode": "sync"},
        {"accel": AndersonConfig(m=4), "fire_every": 4},
        # the evaluation-cost loops: coordinator placement serializes
        # records and fires with arrivals; worker placement offloads them
        {"accel": AndersonConfig(m=4), "fire_every": 4,
         "accel_eval": "coordinator", "eval_time": 2e-3},
        {"accel": AndersonConfig(m=4), "fire_every": 4,
         "accel_eval": "worker", "eval_time": 2e-3},
        {"capture_trace": True},
    ])
    def test_virtual_records_take_no_virtual_time(self, loop):
        res = run_fixed_point(JacobiProblem(grid=12, sweeps=4, seed=0),
                              _virt_cfg(telemetry=True, max_updates=40,
                                        **loop))
        recs = [e for e in res.telemetry.events if e["k"] == "record"]
        assert len(recs) == len(res.history)
        # An offloaded record spans its modeled evaluation; every other
        # record sits at the history's time.
        assert [(e["t0"], e["t0"] if e.get("offloaded") else e["t1"])
                for e in recs] == [(t, t) for t, _, _ in res.history]
