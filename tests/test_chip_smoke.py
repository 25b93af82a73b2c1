"""``chip_smoke.py``: refuses to run off the TPU, and its phases' checks
hold on the CPU at small sizes (the chip runs them at full size)."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def device_plane_at_small_n(monkeypatch):
    """Let a small problem take the device plane, as a full-size one does."""
    import repro.core.engine.device_plane as dp

    monkeypatch.setattr(dp, "AUTO_THRESHOLD", 1)


def test_exits_nonzero_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok": true' not in out.stdout


def _passes(smoke, fn, **kw):
    line = smoke.run_phase(fn, **kw)
    json.dumps(line)  # one JSON line per phase
    assert line["ok"], line
    for c in line["checks"].values():
        assert c["err"] <= c["limit"]
    return line


def test_jacobi_device_phase(smoke, device_plane_at_small_n):
    line = _passes(smoke, smoke.phase_jacobi_device, grid=32, max_updates=80)
    assert line["device_dispatches"] > 0 and line["updates"] == 80


def test_vi_anderson_device_phase(smoke, device_plane_at_small_n):
    line = _passes(smoke, smoke.phase_vi_anderson_device, S=2 ** 10,
                   max_wall=60.0)
    assert line["accel_accepts"] > 0 and line["device_dispatches"] > 0


def test_scf_straggler_phase(smoke):
    line = _passes(smoke, smoke.phase_scf_straggler, delay=0.02)
    assert line["async_over_sync_wall"] > 0
    assert line["device_dispatches"] == 0  # numpy SCF: a host-path check


def test_pallas_f32_phase(smoke):
    # 1100 x 256 float32: two row tiles, the last one padded.
    line = _passes(smoke, smoke.phase_pallas_f32, rows=(32, 1100), g=256,
                   h=6, n=1 << 10)
    assert line["setup_s"] > 0 and line["wall_s"] > 0
    assert line["device_dispatches"] == 3


def test_failed_phase_is_reported(smoke):
    def phase_broken():
        raise smoke.PhaseFailed("no dispatch reached the device")

    line = smoke.run_phase(phase_broken)
    assert line == {"phase": "broken", "ok": False,
                    "error": "PhaseFailed: no dispatch reached the device"}
