"""``device_apply_share``: the share of device-plane dispatches whose block
the coordinator wrote into its device-resident iterate, on the CPU."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
METRIC = "device_apply_share"
ASYNC = [w["name"] for w in BENCH["workloads"] if w["traffic"] == "async_straggler"]


def read(*solves):
    w = harness.Window(cell=harness.load_cell(ASYNC[0]), seconds=1.0,
                       setup_s=1.0, solves=list(solves))
    return harness.load_module("metrics", METRIC).read(w)


def test_applies_over_dispatches_across_the_solves():
    a = SimpleNamespace(device_dispatches=40, device_applies=40)
    b = SimpleNamespace(device_dispatches=10, device_applies=5)
    assert read(a, b) == pytest.approx(100.0 * 45 / 50)


@pytest.mark.parametrize("solves", [
    [],
    [SimpleNamespace(device_dispatches=0, device_applies=0)],  # host path
    [SimpleNamespace(device_dispatches=12)],  # a program without the count
], ids=["no_solve", "no_dispatch", "no_count"])
def test_reads_nothing_without_device_dispatches_or_the_count(solves):
    assert read(*solves) is None


def test_the_entry_lists_the_async_cells():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == METRIC)
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "device plane",
                                 "point_updates_per_s", "%", "higher")
    assert entry["workloads"] == ASYNC
    assert len(ASYNC) == 2


@pytest.mark.parametrize("name", ASYNC)
def test_a_traced_tiny_cell_applies_every_return_on_the_device(
        name, monkeypatch):
    import repro.core.engine.device_plane as dp

    monkeypatch.setattr(dp, "AUTO_THRESHOLD", 1)  # as at full size
    cell = harness.load_cell(name)
    cell = harness.load_cell(name, overrides=cell.family.TINY)
    line = harness.run(cell, 2 ** 33 + 7, 0.6, True,
                       t_start=time.perf_counter()).line
    assert line["correct"]
    assert line["metrics"][METRIC]["value"] == 100.0
