"""The chip benchmark (``chipbench/``) on the CPU at tiny sizes.

The metrics' arithmetic, the trace reduction, the kernels' operation and
byte counts, finding cells by name, and each cell's set-up, window and
check; the check's control and planted faults must come out not correct.
Each cell runs at its family's ``TINY`` size (``problems/<family>.py``),
so that a cell of a new family needs no edit here.  The chip runs the
same code at full size (``BENCHMARK.json``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import anderson, faults, harness, peaks  # noqa: E402
from chipbench import trace as tr  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
JACOBI = "jacobi2d_g2800"
ANDERSON = "async_anderson_straggler"
DATA = Path(__file__).resolve().parent / "data"


def tiny(name: str, bench: dict = BENCH,
         base: Path = harness.HERE) -> harness.Cell:
    """The cell ``name`` at its family's ``TINY`` size."""
    family = harness.load_cell(name, bench, base=base).family
    return harness.load_cell(name, bench, base=base, overrides=family.TINY)


def with_cell(config: str, traffic: str, like: str = "") -> dict:
    """``BENCHMARK.json`` with the cell ``<config>.<traffic>`` in it, which
    reports the per-layer metrics of the cell ``like``, as the entries a
    later change extends would have it."""
    bench = json.loads(json.dumps(BENCH))
    name = f"{config}.{traffic}"
    if name not in CELLS:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        for m in bench["per_layer"]:
            if like in m["workloads"]:
                m["workloads"].append(name)
    return bench


def first_cell(config: str) -> harness.Cell:
    return harness.load_cell(next(w["name"] for w in BENCH["workloads"]
                                  if w["config"] == config))


@pytest.fixture
def device_plane_at_small_n(monkeypatch):
    """Let a tiny problem take the device plane, as a full-size one does."""
    import repro.core.engine.device_plane as dp

    monkeypatch.setattr(dp, "AUTO_THRESHOLD", 1, raising=False)


def solve(history, updates=0, **kw):
    return SimpleNamespace(history=history, worker_updates=updates,
                           telemetry=None, **kw)


# --------------------------------------------------------------------- #
# end-to-end metrics
# --------------------------------------------------------------------- #
def test_decades_per_s_counts_a_solve_the_window_cut_off():
    cell = first_cell(JACOBI)
    done = solve([(0.0, 0, 1.0), (1.0, 8, 1e-3), (2.0, 16, 1e-6)], 16)
    cut = solve([(0.0, 0, 2.0), (1.5, 4, 2e-2)], 4)
    w = harness.Window(cell=cell, seconds=4.0, setup_s=1.0,
                       solves=[done, cut])
    read = harness.load_module("metrics", "decades_per_s").read
    assert read(w) == pytest.approx((6 + 2) / 4.0)
    per_k = harness.load_module("metrics", "decades_per_kupdate").read(w)
    assert per_k == pytest.approx(1000 * 8 / 20)


@pytest.mark.parametrize("traffic", ["async_straggler", "sync_straggler"])
def test_point_updates_per_s_counts_every_sweep(traffic,
                                                device_plane_at_small_n):
    name = f"{JACOBI}.{traffic}"
    cell = tiny(name, with_cell(JACOBI, traffic))
    out = harness.run(cell, 3, 0.5, False, t_start=time.perf_counter())
    w = out.window
    # grid 32, 4 row blocks of 8 rows, 10 sweeps: 2560 points per update
    assert cell.family.points_per_update(cell.config) == 8 * 32 * 10
    got = out.line["metrics"]["point_updates_per_s"]["value"]
    assert got == pytest.approx(w["updates"] * 2560 / w["seconds"] / 1e6)
    if cell.mix["mode"] == "sync":
        assert w["updates"] % 4 == 0 and w["device_dispatches"] == 0
    else:
        assert w["device_dispatches"] == w["updates"] > 0


# --------------------------------------------------------------------- #
# trace reduction
# --------------------------------------------------------------------- #
def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_trace_reduction_on_synthetic_events():
    d0 = "/device:TPU:0"
    events = [
        ev("/host:CPU", "python", "window", 0, 100),
        ev("/host:CPU", "python", "solve", 15, 70),
        ev("/host:CPU", "python", "np.asarray(jax.Array)", 52, 10),
        ev(d0, tr.OPS_LINE, "fusion.1", 20, 20),
        ev(d0, tr.OPS_LINE, "fusion.2", 30, 20),  # overlaps the first
        ev(d0, tr.OPS_LINE, "fusion.1", 70, 10),
        ev(d0, tr.OPS_LINE, "copy", 95, 20),  # runs past the window
        ev(d0, tr.MODULES_LINE, "jit__halo_sweeps(3)", 20, 30),
        ev(d0, tr.MODULES_LINE, "jit__halo_sweeps(3)", 70, 10),
    ]
    s = tr.summarize(events)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((30 + 10 + 5) * 1e-9)
    assert s.idle_share == pytest.approx(0.55)
    assert s.kernels == {"_halo_sweeps": (pytest.approx(40e-9), 2)}
    assert s.top_ops[0] == ("_halo_sweeps/fusion.1", pytest.approx(30e-9))
    assert ("?/copy", pytest.approx(20e-9)) in s.top_ops  # outside programs
    # idle time by what the Python threads were doing, else the annotation
    assert s.idle_gaps == [("window", pytest.approx(35e-9)),
                           ("np.asarray(jax.Array)", pytest.approx(20e-9))]


def test_trace_reduction_without_a_device_reads_nothing():
    events = [ev("/host:CPU", "python", "window", 0, 100)]
    assert tr.summarize(events) is None


def test_trace_reduction_on_a_recorded_chip_trace():
    """0.6 s of a traced run of the async Jacobi cell at grid 4096 on one
    v5e: the device's ops and programs and the Python threads' line, the
    ops' HLO text cut to their names."""
    events = [tr.Event(**e) for e in json.loads(
        (DATA / "v5e_jacobi_trace.json").read_text())]
    s = tr.summarize(events)
    assert s.window_s == pytest.approx(0.6)
    assert s.busy_s == pytest.approx(0.027899883)
    assert s.kernels["_halo_sweeps"] == (pytest.approx(0.02331317), 7)
    assert s.top_ops[0] == ("_halo_sweeps/while.5",
                            pytest.approx(0.020959972))
    # the host is bringing float64 arrays back while the chip idles
    assert s.idle_gaps[0] == ("np.asarray(jax.Array)",
                              pytest.approx(0.5721000980000007))


def test_trace_is_read_from_a_profiler_dump(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load_events(str(tmp_path))
    assert any(e.name == "window" for e in events)
    assert tr.summarize(events) is None  # a CPU trace has no TPU plane


# --------------------------------------------------------------------- #
# operations and bytes, peaks
# --------------------------------------------------------------------- #
def test_kernel_costs():
    jac = first_cell(JACOBI)
    ops, nbytes = jac.family.KERNELS["_halo_sweeps"](jac.config)
    rows = 700  # 2800 rows over 4 workers
    assert ops == rows * 2800 * (5 * 10 + 3)
    assert nbytes == 8 * (3 * rows * 2800 + 2 * 2800)
    # bound by bytes on a v5e
    assert peaks.least_seconds("TPU v5 lite", ops, nbytes) == nbytes / 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# --------------------------------------------------------------------- #
# finding cells by name
# --------------------------------------------------------------------- #
def test_every_name_has_its_file():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert (harness.HERE / "mixes" / f"{w['traffic']}.json").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    for name in CELLS:
        cell = harness.load_cell(name)
        assert set(cell.family.TINY) <= set(cell.config)  # its CPU size
        assert {"point_updates_per_s", "setup_s"} <= set(cell.end_to_end)
        assert "idle_share" in cell.per_layer
        # a cell reports the end-to-end metric each of its layers moves
        assert {moves[m] for m in cell.per_layer} <= set(cell.end_to_end)


def test_a_mix_file_and_a_workload_entry_add_a_cell(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(harness.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    (base / "mixes" / "async_host_straggler.json").write_text(json.dumps(
        {"mode": "async", "device_plane": "off",
         "delay_s": {"0": 0.1, "1": 0.1}}))
    bench = json.loads(json.dumps(BENCH))
    name = f"{JACOBI}.async_host_straggler"
    bench["workloads"].append({
        "name": name, "config": JACOBI,
        "traffic": "async_host_straggler", "chips": 1, "why": "test"})
    cell = harness.load_cell(name, bench, base=base,
                             overrides={"grid": 32})
    assert cell.mix["delay_s"] == {"0": 0.1, "1": 0.1}
    assert all(p.read_bytes() == b for p, b in before.items())
    out = harness.run(cell, 1, 0.3, False, t_start=time.perf_counter())
    assert out.line["correct"] and out.window["device_dispatches"] == 0


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell(f"{JACOBI}.no_such_mix")


def test_a_per_layer_metric_must_name_its_cells():
    bench = json.loads(json.dumps(BENCH))
    del bench["per_layer"][0]["workloads"]
    with pytest.raises(KeyError, match="names no workloads"):
        harness.load_cell(CELLS[0], bench)


# --------------------------------------------------------------------- #
# each cell at a tiny size
# --------------------------------------------------------------------- #
DEVICE_METRICS = {m["name"] for m in BENCH["per_layer"]
                  if m["source"] == "device_trace"}


def sets_up_measures_and_checks(cell: harness.Cell) -> None:
    seed = 2 ** 33 + 5  # seeds may pass 32 bits
    out = harness.run(cell, seed, 0.6, False, t_start=time.perf_counter())
    line = out.line
    assert line["correct"] and line["failed"] == 0, line
    assert line["attempted"] == out.window["solves"] >= 1
    assert out.window["compiles"] == 0  # the warm-up covered every shape
    assert set(line["metrics"]) == set(cell.end_to_end)
    values = {k: m["value"] for k, m in line["metrics"].items()}
    # A straggling block's residual can grow before it falls, so a short
    # window may gain no decades; the rates and set-up are positive.
    assert values["point_updates_per_s"] > 0 and values["setup_s"] > 0
    assert math.isfinite(values.get("decades_per_s", 0.0))
    assert list(line)[-1] == "checks"
    accel = "accel" in cell.mix
    assert list(line["checks"]) == ["residual_gap", "block_step_gap"] + (
        ["anderson_gap", "acceptless_solves"] if accel else [])
    assert ("accel_fires" in out.window) == accel
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]
    assert line["device"]["platform"] == "cpu"

    traced = harness.run(cell, seed, 0.6, True,
                         t_start=time.perf_counter()).line
    assert traced["correct"]
    got = set(traced["metrics"])
    assert got <= set(cell.per_layer) and not got & DEVICE_METRICS
    assert "task_p95_ms" in got
    assert ("decades_per_kupdate" in got) == ("decades_per_s"
                                              in cell.end_to_end)
    assert "busy_s" not in traced["device"] and "breakdown" not in traced


@pytest.mark.parametrize("name", CELLS)
def test_cell_sets_up_measures_and_checks(name, device_plane_at_small_n):
    sets_up_measures_and_checks(tiny(name))


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "TPU" in out.stderr and '"correct"' not in out.stdout


# --------------------------------------------------------------------- #
# the control and the planted faults fail the check
# --------------------------------------------------------------------- #
def is_not_correct(cell: harness.Cell, kind: str) -> dict:
    """The run of ``cell`` with the fault ``kind``: not correct, with a
    number past its limit.  Returns the readings."""
    out = harness.run(cell, 7, 0.3, False, t_start=time.perf_counter(),
                      patch=faults.patch(kind, cell, 7))
    line = out.line
    assert not line["correct"] and line["failed"] >= 1
    readings = {k: c["value"] for k, c in line["checks"].items()}
    assert any(not v <= line["checks"][k]["limit"]
               for k, v in readings.items()), readings
    if kind == "float32":  # the control fails every gap
        assert all(v > line["checks"][k]["limit"] and math.isfinite(v)
                   for k, v in readings.items() if k.endswith("_gap"))
    return readings


@pytest.mark.parametrize("kind", faults.KINDS)
@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_are_not_correct(name, kind,
                                            device_plane_at_small_n):
    is_not_correct(tiny(name), kind)


# --------------------------------------------------------------------- #
# a new family as files only
# --------------------------------------------------------------------- #
TWIN = "jacobi2d_twin"


@pytest.fixture(scope="module")
def twin_family(tmp_path_factory):
    """A copy of ``chipbench/`` with one more family module and
    configuration (``jacobi2d`` renamed), and ``BENCHMARK.json`` with its
    cell: the files a later change would add, nothing edited.  Returns
    (bench, base, cell name, the copied files' bytes)."""
    base = tmp_path_factory.mktemp("bench") / "chipbench"
    shutil.copytree(harness.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    shutil.copy(base / "problems" / "jacobi2d.py",
                base / "problems" / f"{TWIN}.py")
    config = json.loads((base / "configs" / f"{JACOBI}.json").read_text())
    (base / "configs" / f"{TWIN}_g2800.json").write_text(
        json.dumps(dict(config, family=TWIN)))
    bench = with_cell(f"{TWIN}_g2800", "async_straggler",
                      like=f"{JACOBI}.async_straggler")
    bench["configs"].append({"name": f"{TWIN}_g2800", "source": "test",
                             "file": f"chipbench/configs/{TWIN}_g2800.json",
                             "reduced": [], "why": "test"})
    return bench, base, f"{TWIN}_g2800.async_straggler", before


def test_a_family_module_and_config_add_a_cell(twin_family,
                                               device_plane_at_small_n):
    bench, base, name, before = twin_family
    cell = tiny(name, bench, base)
    assert cell.family.__file__ == str(base / "problems" / f"{TWIN}.py")
    assert cell.config["family"] == TWIN
    assert cell.config["grid"] == cell.family.TINY["grid"] == 32
    assert all(p.read_bytes() == b for p, b in before.items())
    sets_up_measures_and_checks(cell)


@pytest.mark.parametrize("kind", faults.KINDS)
def test_a_new_familys_control_and_faults_are_not_correct(
        twin_family, kind, device_plane_at_small_n):
    bench, base, name, _ = twin_family
    is_not_correct(tiny(name, bench, base), kind)


# --------------------------------------------------------------------- #
# a mix that accelerates, and the check of the Anderson step
# --------------------------------------------------------------------- #
def anderson_cell(**mix) -> harness.Cell:
    """The tiny Jacobi cell of the Anderson mix, its mix updated."""
    cell = tiny(f"{JACOBI}.{ANDERSON}", with_cell(
        JACOBI, ANDERSON, like=f"{JACOBI}.async_straggler"))
    return dataclasses.replace(cell, mix=dict(cell.mix, **mix))


def test_a_mix_sets_the_run_configs_acceleration():
    from repro.core import AndersonConfig

    rc = harness.run_config(anderson_cell(), 1)
    assert rc.accel == AndersonConfig(m=5)
    assert (rc.accel_mode, rc.fire_every) == ("coordinator", 1)
    rc = harness.run_config(anderson_cell(accel={"m": 3, "beta": 0.5}), 1)
    assert rc.accel == AndersonConfig(m=3, beta=0.5)
    assert harness.run_config(tiny(f"{JACOBI}.async_straggler"), 1).accel \
        is None


@pytest.mark.parametrize("key", ("anderson_m", "accel_mode", "fire_every"))
def test_an_unknown_mix_key_is_an_error(key):
    with pytest.raises(KeyError, match=f"{key}.*known"):
        harness.run_config(anderson_cell(**{key: 5}), 1)


def test_every_mix_file_makes_a_run_config():
    cell = first_cell(JACOBI)
    for path in sorted((harness.HERE / "mixes").glob("*.json")):
        mix = json.loads(path.read_text())
        rc = harness.run_config(dataclasses.replace(cell, mix=mix), 1)
        assert rc.mode == mix["mode"]
        assert (rc.accel is None) == ("accel" not in mix)


def test_a_limit_the_check_does_not_read_is_an_error():
    cell = tiny(f"{JACOBI}.async_straggler")
    cell = dataclasses.replace(cell, mix=dict(
        cell.mix, limits={"anderson_gap": 1e-10}))
    with pytest.raises(KeyError, match="limits name"):
        harness.run(cell, 1, 0.1, False, t_start=time.perf_counter())


@pytest.mark.parametrize("key", ("residual_gap", "block_step_gap"))
def test_a_mix_cannot_set_a_configurations_limit(key):
    cell = anderson_cell(limits={"anderson_gap": 1e-9, key: 1.0})
    with pytest.raises(KeyError, match=f"{key}.*configuration sets"):
        cell.limits
    with pytest.raises(KeyError, match="configuration sets"):
        harness.run(cell, 1, 0.1, False, t_start=time.perf_counter())


@pytest.mark.parametrize("sample", (None, 256))
def test_the_window_keeps_the_state_its_coordinator_held(
        sample, device_plane_at_small_n, monkeypatch):
    from repro.core import AndersonState, run_fixed_point
    from repro.core.engine import coordinator

    if sample is not None:  # as at full size, a sample of the points
        monkeypatch.setattr(harness, "ANDERSON_SAMPLE", sample)
    cell = anderson_cell()
    problem = cell.family.build(cell.config, 4)
    with harness.anderson_kept(cell, 4, problem) as newest:
        res = run_fixed_point(problem, harness.run_config(
            cell, 4, max_updates=12))
        rec = newest[0]
    assert coordinator.AndersonState is AndersonState  # put back
    assert isinstance(rec, harness.Recorded)
    assert isinstance(rec.state, AndersonState)
    assert res.accel_fires == rec.n_fire > 0
    assert res.accel_accepts == rec.n_accept
    assert len(rec.pushed) == min(rec.n_fire, cell.mix["accel"]["m"] + 1)
    assert rec.step is not None
    assert len(rec.at) == problem.n if sample is None else \
        0.8 * sample < len(rec.at) <= sample
    assert harness.anderson_gap(cell.mix["accel"], rec) <= 1e-12
    with harness.anderson_kept(cell, 4, problem) as newest:  # window of 2
        res = run_fixed_point(problem, harness.run_config(
            cell, 4, max_updates=2))
    assert len(newest[0].pushed) == res.accel_fires == 2
    assert harness.anderson_gap(cell.mix["accel"], newest[0]) <= 1e-12
    with harness.anderson_kept(tiny(f"{JACOBI}.async_straggler"), 4,
                               problem) as newest:
        assert coordinator.AndersonState is AndersonState  # plain mix
    assert newest == [None]


def test_the_check_samples_a_large_iterate():
    at = harness.anderson_sample(10 ** 7, 2 ** 33 + 1)
    assert len(at) > 0.99 * harness.ANDERSON_SAMPLE
    assert np.all(np.diff(at) > 0) and 0 <= at[0] and at[-1] < 10 ** 7
    assert np.array_equal(at, harness.anderson_sample(10 ** 7, 2 ** 33 + 1))
    assert not np.array_equal(at[:100], harness.anderson_sample(
        10 ** 7, 2 ** 33 + 2)[:100])


def test_an_accelerated_cell_fires_and_is_correct(device_plane_at_small_n):
    cell = anderson_cell()
    assert cell.limits["anderson_gap"] == cell.mix["limits"]["anderson_gap"]
    sets_up_measures_and_checks(cell)
    out = harness.run(cell, 11, 0.6, False, t_start=time.perf_counter())
    w, checks = out.window, out.line["checks"]
    assert out.line["correct"]
    assert 0 < w["accel_accepts"] <= w["accel_fires"]
    assert checks["acceptless_solves"] == {"value": 0, "limit": 0}
    assert 0 <= checks["anderson_gap"]["value"] <= 1e-12  # rounding


@pytest.mark.parametrize("kind", ("float32",) + faults.COMBINE_KINDS)
def test_combine_control_and_fault_fail_anderson_gap(kind,
                                                     device_plane_at_small_n):
    cell = anderson_cell()
    readings = is_not_correct(cell, kind)
    assert readings["anderson_gap"] > cell.limits["anderson_gap"]


def test_a_combine_fault_needs_an_accelerated_mix():
    with pytest.raises(ValueError, match="needs a mix with accel"):
        faults.patch("dropped_newest", first_cell(JACOBI), 1)


class Rejected:
    """The program's Anderson state with every step moved far off, so that
    the safeguard rejects each one."""

    def __init__(self, state):
        self.state = state

    def propose(self):
        out = self.state.propose()
        return None if out is None else out + 1e3

    def __getattr__(self, name):
        return getattr(self.state, name)


def test_a_solve_without_an_accepted_step_fails(device_plane_at_small_n):
    from repro.core import AndersonState

    cell = anderson_cell()
    out = harness.run(cell, 3, 0.3, False, t_start=time.perf_counter(),
                      patch=lambda problem: (
                          lambda config: Rejected(AndersonState(config))))
    line = out.line
    assert out.window["accel_fires"] > 0 == out.window["accel_accepts"]
    assert not line["correct"] and line["failed"] == line["attempted"] >= 1
    assert line["checks"]["acceptless_solves"]["value"] == line["attempted"]


def test_anderson_reference_solves_the_constrained_least_squares():
    from repro.core import AndersonConfig, AndersonState

    rng = np.random.default_rng(5)
    F = rng.standard_normal((6, 200))
    reg = 1e-3
    B = F @ F.T
    lam = reg * np.trace(B) / 6
    w = np.linalg.solve(B + lam * np.eye(6), np.ones(6))
    want = w / w.sum()  # the constrained minimiser in closed form
    got = anderson.alpha(F, reg)
    assert got == pytest.approx(want, rel=1e-10)
    assert anderson.objective(F, got, reg) == pytest.approx(
        float(want @ (B + lam * np.eye(6)) @ want), rel=1e-10)
    X = rng.standard_normal((6, 200))
    state = anderson.State({"m": 5, "reg": reg, "beta": 0.5})
    for x, f in zip(X, F):
        state.push(x, x + f, f)
    step = state.propose()
    assert step == pytest.approx(want @ (X + 0.5 * F), rel=1e-10)
    assert anderson.gap(X, X + F, F, step, state.last_alpha,
                        {"reg": reg, "beta": 0.5}) < 1e-12
    # the program's step on the same window reads as rounding
    prog = AndersonState(AndersonConfig(m=5, reg=reg, beta=0.5))
    for x, f in zip(X, F):
        prog.push(x, x + f, f)
    assert anderson.gap(X, X + F, F, prog.propose(), prog.last_alpha,
                        {"reg": reg, "beta": 0.5}) < 1e-12


# --------------------------------------------------------------------- #
# sets of runs and their spreads
# --------------------------------------------------------------------- #
def test_sets_report_gives_the_interquartile_spread():
    from chipbench import sets

    assert sets.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)
    rec = {"workload": CELLS[0], "label": "A", "trace": 0, "rc": 0,
           "wall_s": 90.0, "window": {"updates": 300, "seconds": 52.0,
                                      "compiles": 0}}
    records = [dict(rec, seed=s, line={
        "correct": True, "checks": {"residual_gap": {"value": 1e-17}},
        "metrics": {"point_updates_per_s": {"value": v},
                    "setup_s": {"value": 20.0}},
        "device": {"memory_peak_bytes": 1}}) for s, v in enumerate(
            [100.0, 101.0, 99.0, 100.5, 99.5, 100.0])]
    text = "\n".join(sets.report(records))
    assert f"## {CELLS[0]} set A trace 0: 6 runs" in text
    want = sets.spread([100.0, 101.0, 99.0, 100.5, 99.5, 100.0])
    assert f"point_updates_per_s: median 100.0 spread {want!r}" in text


def test_diagnose_report_gives_updates_per_10s_and_gaps():
    from chipbench import diagnose

    rec = {"workload": CELLS[0], "label": "A", "seed": 5, "rc": 0,
           "line": {"metrics": {"point_updates_per_s": {"value": 150.0}}},
           "diag": {"cpu_s": 90.0, "window_s": 45.0,
                    "gc": {"n": 1, "s": 0.001, "max": 0.001},
                    "solves": [{"records": [(0.0, 0), (4.0, 40), (9.5, 84),
                                            (12.0, 100), (21.0, 180)],
                                "shares": {}}]}}
    (text,) = diagnose.report([rec, dict(rec, line=None)])[:1]
    assert "rate 150.0 updates per 10 s [84, 16, 80]" in text
    assert "longest gaps [9.0, 5.5, 4.0] cores 2.00" in text
    assert "no result" in diagnose.report([dict(rec, line=None)])[0]


def test_diagnose_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/diagnose.py", "child", CELLS[0], "1",
         "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0 and "TPU" in out.stderr
    assert '"correct"' not in out.stdout
