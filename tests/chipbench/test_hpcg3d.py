"""The ``hpcg3d`` family's files: HPCG's spec in the plain reference, and
the operations and bytes of its two kernels."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, peaks  # noqa: E402

CELL = "hpcg3d_n104.async_straggler"


def test_the_cell_is_hpcgs_default_grid_on_one_chip():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(CELL, bench)
    assert cell.chips == 1 and cell.config["grid"] == 104
    assert cell.family.points_per_update(cell.config) == 2_812_160
    assert {"slab27_sweeps_roofline", "residual27_roofline"} \
        <= set(cell.per_layer)
    assert not {"halo_sweeps_roofline"} & set(cell.per_layer)


def test_kernel_costs_at_104_cubed():
    cell = harness.load_cell(CELL)
    g, planes = 104, 26  # 4 z-slabs of 26 planes
    ops, nbytes = cell.family.KERNELS["_slab27_sweeps"](cell.config)
    assert ops == planes * g * g * (28 * 10 + 3)
    assert nbytes == 8 * (3 * planes * g * g + 2 * g * g)
    r_ops, r_bytes = cell.family.KERNELS["_residual_norm27"](cell.config)
    assert (r_ops, r_bytes) == (31 * g ** 3, 16 * g ** 3)
    for o, b in ((ops, nbytes), (r_ops, r_bytes)):  # bound by bytes
        assert peaks.least_seconds("TPU v5 lite", o, b) == b / 819e9


@pytest.mark.parametrize("grid", [4, 9])
def test_reference_follows_generate_problem(grid):
    ref = harness.load_module("problems", "hpcg3d").Reference(
        {"grid": grid, "sweeps": 3}, seed=1)
    # b = 26 - (neighbours inside the grid): interior 0, face 9, edge 15,
    # corner 19
    assert ref.b[0, 0, 0] == 19 and ref.b[0, 0, 1] == 15
    assert ref.b[0, 1, 1] == 9 and ref.b[1, 1, 1] == (0 if grid > 2 else 9)
    ones = np.ones(grid ** 3)
    assert ref.residual_norm(ones) == 0.0
    assert ref.residual_norm(np.zeros(grid ** 3)) == pytest.approx(
        np.linalg.norm(ref.b))
    # the solution is a fixed point of every slab's sweeps
    idx = np.arange(grid * grid, 2 * grid * grid)
    np.testing.assert_allclose(ref.block_step(ones, idx), 1.0, rtol=1e-15)
