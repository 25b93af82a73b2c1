"""The benchmark's readers of the program's own spans, on the CPU.

``record_share``, ``lock_wait_share`` and ``block_eval_ms_p50`` read the
telemetry capture of a traced run; each returns None where the program
wrote no such span.  The trace reduction labels an idle gap by the host
event that overlaps it most and breaks ties by the greater name: the
program's ``solver.*`` annotations rely on winning that tie against the
JAX host events they enclose.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from repro.telemetry import TelemetryCapture  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ASYNC = "jacobi2d_g2800.async_straggler"
DATA = Path(__file__).resolve().parent / "data"
NEW = ("record_share", "lock_wait_share", "block_eval_ms_p50")


def span(kind, t0, t1, lane="coord", **args):
    return dict(k=kind, lane=lane, t0=t0, t1=t1, **args)


def window(*captures, walls):
    """A window of solves, one per capture, with these wall times."""
    solves = [SimpleNamespace(telemetry=cap, wall_time=wall)
              for cap, wall in zip(captures, walls)]
    return harness.Window(cell=harness.load_cell(ASYNC), seconds=sum(walls),
                          setup_s=1.0, solves=solves)


def read(metric, w):
    return harness.load_module("metrics", metric).read(w)


def capture(*events):
    return TelemetryCapture(events=list(events))


# --------------------------------------------------------------------- #
# the readers
# --------------------------------------------------------------------- #
def test_record_share_sums_record_spans_over_the_solves_wall():
    a = capture(span("record", 0.0, 0.5, wu=0),
                span("task", 0.1, 0.9, lane="w0", task=0),
                span("record", 2.0, 2.25, wu=4))
    b = capture(span("record", 0.0, 0.25, wu=0))
    w = window(a, b, walls=[4.0, 1.0])
    assert read("record_share", w) == pytest.approx(100.0 * 1.0 / 5.0)


def test_record_share_counts_only_the_solves_wall():
    """The async loop's final record runs after the stop, past the wall
    time: only the part inside the solve's wall time counts."""
    cap = capture(span("record", 0.0, 0.5, wu=0),
                  span("record", 1.75, 2.25, wu=4),
                  span("record", 2.25, 2.75, wu=4))
    w = window(cap, walls=[2.0])
    assert read("record_share", w) == pytest.approx(100.0 * 0.75 / 2.0)


def test_lock_wait_share_is_per_worker():
    lane = dict(lane="w1", task=3)
    cap = capture(span("lock_wait", 1.0, 1.5, phase="dispatch", **lane),
                  span("lock_wait", 2.0, 2.5, phase="arrival", **lane),
                  span("block_eval", 1.5, 2.0, path="plane", **lane))
    w = window(cap, walls=[2.0])
    n = w.cell.config["n_workers"]
    assert n == 4
    assert read("lock_wait_share", w) == pytest.approx(100.0 * 1.0 / (n * 2))


def test_block_eval_ms_p50_is_the_median_span():
    evals = [span("block_eval", 1.0, 1.0 + d, lane="w0", task=i,
                  path="plane") for i, d in enumerate([0.1, 0.3, 0.2])]
    w = window(capture(*evals[:2]), capture(evals[2]), walls=[1.0, 1.0])
    assert read("block_eval_ms_p50", w) == pytest.approx(200.0)


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_without_their_spans(metric):
    only_tasks = capture(span("task", 0.0, 1.0, lane="w0"))
    assert read(metric, window(only_tasks, walls=[2.0])) is None
    assert read(metric, window(None, walls=[2.0])) is None  # untraced


def test_record_share_reads_nothing_from_instant_records():
    """A program that writes each record as a span with t0 == t1 has not
    measured the record's cost: the reader says nothing, not 0."""
    cap = capture(span("record", 0.5, 0.5), span("record", 1.0, 1.0))
    assert read("record_share", window(cap, walls=[2.0])) is None


@pytest.mark.parametrize("metric", NEW)
def test_each_reader_lists_the_cells_that_report_its_spans(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_span"
    assert entry["moves"] == "point_updates_per_s"
    want = {"lock_wait_share": [ASYNC]}.get(
        metric, [w["name"] for w in BENCH["workloads"]])
    assert entry["workloads"] == want


# --------------------------------------------------------------------- #
# the labeller's tie rule
# --------------------------------------------------------------------- #
def enclosing(events, name, around="np.asarray(jax.Array)"):
    """An annotation named ``name`` that exactly encloses each ``around``
    host event, on its line."""
    return [tr.Event(e.plane, e.line, name, e.start_ns, e.dur_ns)
            for e in events if e.name == around]


def test_program_annotations_win_the_tie_on_a_recorded_chip_trace():
    events = [tr.Event(**e) for e in json.loads(
        (DATA / "v5e_jacobi_trace.json").read_text())]
    before = tr.summarize(events)
    assert before.idle_gaps[0][0] == "np.asarray(jax.Array)"
    idle = sum(s for _, s in before.idle_gaps)

    name = "solver.block_eval"  # the program's name for the section
    after = tr.summarize(events + enclosing(events, name))
    assert after.idle_gaps[0] == (name, pytest.approx(idle))
    assert after.busy_s == before.busy_s

    # A name that sorts before JAX's own loses the tie: the prefix matters.
    other = tr.summarize(events + enclosing(events, "coord.block_eval"))
    assert other.idle_gaps == before.idle_gaps
