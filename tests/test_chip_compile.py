"""The main path's kernels compile for a TPU v5e chip, with no chip attached.

``jax.experimental.topologies`` describes a v5e host and the installed TPU
compiler compiles for its first chip: what it refuses here, the chip's
compiler refuses too.  Nothing runs, so these tests say nothing about
results or times.  The topology is described inside a module fixture only
(never at import), because one process at a time may load the TPU library;
every test of that kind lives in this one file.

The last tests need no topology: the float64 paths that Mosaic cannot lower
must raise with the reason instead of reaching the compiler or quietly
falling back to interpret mode or to jnp.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro.problems  # noqa: F401  (float64: enables jax x64)
from repro.core import AndersonConfig, RunConfig, run_fixed_point
from repro.core.anderson import AndersonState, _mix_kernel_auto
from repro.kernels import ops
from repro.problems import (GarnetMDP, HPCGProblem, JacobiProblem,
                            ValueIterationProblem)


@pytest.fixture(scope="module")
def one_chip():
    """First chip of a described v5e:2x2 host (skips where none can be)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # Executables for a described chip cannot be read back from the
        # persistent cache, so keep them out of it.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


ROWS, G = 1024, 4096  # one worker's block of the 4096 x 4096 grid


def test_halo_sweeps_float64(one_chip):
    """The jnp Jacobi block step the device plane runs, in float64."""
    from repro.problems.jacobi import _halo_sweeps

    c = _compile(lambda b, t, o, bg: _halo_sweeps(b, t, o, bg, 10), one_chip,
                 ((ROWS, G), jnp.float64), ((G,), jnp.float64),
                 ((G,), jnp.float64), ((ROWS, G), jnp.float64))
    assert c.memory_analysis().output_size_in_bytes >= ROWS * G * 8


def test_jacobi_residual_norm_float64(one_chip):
    """The coordinator's record at the 2800 x 2800 grid, in float64: one
    fused program whose only output is the 0-d norm."""
    from repro.problems.jacobi import _residual_norm

    g = 2800
    c = _compile(lambda x, b: _residual_norm(x, b, g), one_chip,
                 ((g * g,), jnp.float64), ((g * g,), jnp.float64))
    # The scalar's buffer is padded to a tile (1 KiB), far below one row.
    assert c.memory_analysis().output_size_in_bytes < g * 8


def test_slab27_sweeps_float64(one_chip):
    """HPCG's jnp z-slab step at 104^3 over 4 workers (26 planes of
    104 x 104), in float64."""
    from repro.problems.hpcg import _slab27_sweeps

    planes, g = 26, 104
    c = _compile(lambda b, t, o, bg: _slab27_sweeps(b, t, o, bg, 10),
                 one_chip, ((planes, g, g), jnp.float64),
                 ((g, g), jnp.float64), ((g, g), jnp.float64),
                 ((planes, g, g), jnp.float64))
    assert c.memory_analysis().output_size_in_bytes >= planes * g * g * 8


def test_hpcg_residual_norm_float64(one_chip):
    """The coordinator's record of HPCG at 104^3, in float64: one fused
    program whose only output is the 0-d norm."""
    from repro.problems.hpcg import _residual_norm27

    g = 104
    c = _compile(lambda x, b: _residual_norm27(x, b, g), one_chip,
                 ((g ** 3,), jnp.float64), ((g ** 3,), jnp.float64))
    assert c.memory_analysis().output_size_in_bytes < g * g * 8


def test_vi_block_step_float64(one_chip):
    """The jnp value-iteration block step at 2**18 states of S = 2**20."""
    from repro.problems.value_iteration import _vi_block_step

    rows, S, A, b = 1 << 18, 1 << 20, 4, 5
    c = _compile(_vi_block_step, one_chip,
                 ((S,), jnp.float64), ((rows,), jnp.float64),
                 ((rows, A, b), jnp.int32), ((rows, A, b), jnp.float64),
                 ((rows, A), jnp.float64), ((), jnp.float64))
    assert c.memory_analysis().output_size_in_bytes >= rows * 8


def test_anderson_mix_float32(one_chip):
    c = _compile(lambda X, Gm, a: ops.anderson_mix(X, Gm, a, interpret=False),
                 one_chip, ((6, 1 << 22), jnp.float32),
                 ((6, 1 << 22), jnp.float32), ((6,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows", [ROWS,
                                  1365])  # 3 workers' block: last tile padded
def test_jacobi_halo_sweeps_float32(one_chip, rows):
    c = _compile(
        lambda b, t, o, bg: ops.jacobi_halo_sweeps(b, t, o, bg, sweeps=10,
                                                   interpret=False),
        one_chip, ((rows, G), jnp.float32), ((G,), jnp.float32),
        ((G,), jnp.float32), ((rows, G), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_jacobi_sweep_float32(one_chip):
    c = _compile(lambda x, b: ops.jacobi_sweep(x, b, G, interpret=False),
                 one_chip, ((G * G,), jnp.float32), ((G * G,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


# --------------------------------------------------------------------- #
# float64 never reaches Mosaic
# --------------------------------------------------------------------- #
def test_pallas_device_plane_float64_raises():
    p = JacobiProblem(grid=16, sweeps=2)
    with pytest.raises(ValueError, match="Mosaic lowers no 64-bit types"):
        run_fixed_point(p, RunConfig(mode="async", executor="thread",
                                     n_workers=2, max_updates=8,
                                     device_plane="pallas"))


def test_pallas_hpcg_plane_raises():
    """HPCG has no Pallas kernel: its float64 plane raises with the reason
    before any worker starts."""
    p = HPCGProblem(grid=8, sweeps=2)
    with pytest.raises(ValueError, match="Mosaic lowers no 64-bit types"):
        run_fixed_point(p, RunConfig(mode="async", executor="thread",
                                     n_workers=2, max_updates=8,
                                     device_plane="pallas"))


def test_pallas_value_iteration_not_implemented():
    p = ValueIterationProblem(GarnetMDP(S=32, A=2, b=2, seed=0))
    with pytest.raises(NotImplementedError, match="gather"):
        run_fixed_point(p, RunConfig(mode="async", executor="thread",
                                     n_workers=2, max_updates=8,
                                     device_plane="pallas"))


def test_mix_kernel_float64_window_raises_on_tpu(monkeypatch):
    """An explicit ``mix_kernel_n`` on a TPU backend: the float64 window
    raises; auto mode keeps the numpy GEMV for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not _mix_kernel_auto(1 << 20, np.float64)
    assert _mix_kernel_auto(1 << 20, np.float32)
    assert not _mix_kernel_auto(1 << 10, np.float32)
    st = AndersonState(AndersonConfig(m=2, mix_kernel_n=8))
    rng = np.random.default_rng(0)
    for _ in range(3):
        st.push(rng.standard_normal(16), rng.standard_normal(16))
    with pytest.raises(ValueError, match="Mosaic lowers no 64-bit types"):
        st.propose()
