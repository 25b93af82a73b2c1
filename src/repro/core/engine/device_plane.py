"""Device-resident data plane resolution (``RunConfig.device_plane``).

The device plane keeps each worker's block resident as a JAX array across
the dispatch loop: per dispatch the worker ships only the halo/dependency
slices its block update reads (two g-length rows for Jacobi, the unique
successor closure for VI) instead of re-materializing the O(n) iterate,
and runs the fused block-update(+local-residual) kernel on the resident
block.  :func:`resolve_device_plane` decides whether a run qualifies and
which kernel flavour to use; the *problems* decide per block whether they
can serve it (``FixedPointProblem.device_block_plan``).

Structural requirements (anything else returns None — host path):

* a real backend (``thread`` / ``process``); the virtual backend always
  ignores the knob so fixed-seed virtual runs stay bit-identical to the
  goldens,
* async mode with fixed selection and block returns (the resident block
  IS the worker's fixed block),
* identity projection (a coordinator-side projection rewrites the whole
  iterate after every arrival, so no block stays resident),
* no chaos scenario, controller, or trace capture (membership changes
  reassign blocks mid-run), and no offloaded eval service in the loop
  (``accel_eval="worker"`` keeps the host loop).

Where the plane runs: thread-backend workers share the process that holds
the accelerator, so their resident blocks live on it (a TPU when there is
one).  Process-backend workers are host workers: each child pins its JAX to
the CPU platform before it builds anything, because a chip belongs to one
process, so their resident blocks live in host memory.

``"pallas"`` compiles the Mosaic kernels and never falls back to jnp or to
interpret mode: a float64 iterate raises ``ValueError`` (Mosaic lowers no
64-bit types) and value iteration raises ``NotImplementedError`` (Mosaic
lowers no general gather); see :mod:`repro.kernels.ops`.

``"auto"`` (the default) additionally requires ``n >= AUTO_THRESHOLD``:
below it the halo savings don't pay for the host<->device hops, above it
the O(n) snapshot per dispatch is the dominant cost the plane removes.
"""

from __future__ import annotations

from typing import Optional

from ..fixedpoint import FixedPointProblem
from .types import RunConfig

__all__ = ["AUTO_THRESHOLD", "resolve_device_plane"]

#: "auto" flips the device plane on at this state size (n = 2**20: the
#: per-dispatch O(n) snapshot crosses ~8 MB, which is where BENCH_hotpath
#: shows the copy dominating the block compute on this container).
AUTO_THRESHOLD = 1 << 20

_MODES = ("off", "auto", "on", "jnp", "pallas", "interpret", "ref")


def resolve_device_plane(problem: FixedPointProblem, cfg: RunConfig,
                         backend: str) -> Optional[str]:
    """Kernel flavour (``"jnp"``/``"pallas"``/``"interpret"``/``"ref"``)
    for this run, or None for the host path."""
    mode = getattr(cfg, "device_plane", "off") or "off"
    if mode not in _MODES:
        raise ValueError(
            f"unknown device_plane {mode!r} (expected one of {_MODES})")
    if mode == "off":
        return None
    if backend not in ("thread", "process"):
        return None
    if cfg.mode != "async":
        return None
    if cfg.selection != "fixed" or cfg.return_mode != "block":
        return None
    if (cfg.scenario is not None or cfg.controller is not None
            or cfg.capture_trace or cfg.accel_eval == "worker"):
        return None
    if cfg.checkpoint_every is not None or cfg.resume_from is not None:
        return None
    if not problem.is_projection_trivial():
        return None
    if mode == "auto":
        return "jnp" if problem.n >= AUTO_THRESHOLD else None
    return "jnp" if mode == "on" else mode
