"""The coordinator's iterate held on the device.

In a device-resident run (:func:`repro.core.engine.device_plane.
resident_iterate`) the whole flat iterate is one device array: each arrival
writes its device block into it, each dispatch slices its halos (and a
stale block) out of it, and the residual record reads it, so no block
crosses to the host between dispatches.  The host copy is made once, when
the solve ends (``Coordinator.host_sync``).

Imported only by such runs: it loads JAX, which the engine's host paths
(and the process backend's children) never need.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["DeviceIterate"]


@jax.jit
def _write(x, values, start):
    """``x`` with ``values`` at ``[start, start + values.size)``.

    Not donated: the old buffer may still feed a slice or a record that
    another worker queued."""
    return lax.dynamic_update_slice(x, values, (start,))


@functools.partial(jax.jit, static_argnames=("size",))
def _slice(x, start, size: int):
    return lax.dynamic_slice(x, (start,), (size,))


class DeviceIterate:
    """The flat iterate as one device array, ``x``.

    Callers serialise every method under the coordinator's lock."""

    def __init__(self, x: np.ndarray):
        self.x = jnp.asarray(x)

    def take(self, s):
        """``x[s]`` as a device array; ``s`` is a slice or an index array
        (a dependency closure)."""
        if isinstance(s, slice):
            return _slice(self.x, s.start, size=s.stop - s.start)
        return self.x[s]

    def write(self, ind: slice, values) -> None:
        """Write one block's ``values`` (device or host, flat) at the slice
        ``ind``: device-plane blocks are contiguous."""
        self.x = _write(self.x, values, ind.start)

    def to_host(self) -> np.ndarray:
        """A writable host copy: one read-back of the whole iterate."""
        return np.array(self.x)
