"""Jit'd public wrappers for the Pallas kernels.

This module is the one place that picks a kernel's mode when the caller
does not name it: interpret mode on the ``cpu`` backend (where the tests
run, the kernel bodies execute as jnp), compiled by Mosaic everywhere else.
A kernel that Mosaic cannot compile raises here with the reason; it never
falls back to interpret mode or to jnp.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from . import anderson_mix as _mix
from . import bellman as _bellman
from . import flash_attention as _flash
from . import jacobi_stencil as _jacobi


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _compiled_mode(interpret: Optional[bool], *arrays) -> bool:
    """The interpret flag for a call; raises for a 64-bit compiled call."""
    interp = _interpret(interpret)
    if not interp:
        wide = sorted({str(a.dtype) for a in arrays
                       if np.dtype(a.dtype).itemsize >= 8})
        if wide:
            raise ValueError(
                f"Mosaic lowers no 64-bit types; this {jax.default_backend()}"
                f" kernel call got {', '.join(wide)} operands")
    return interp


def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    softcap: Optional[float] = None, q_offset: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None):
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("expected (B, S, heads, head_dim) inputs")
    if k.shape != v.shape:
        raise ValueError(f"k/v mismatch: {k.shape} vs {v.shape}")
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"q heads {q.shape[2]} not a multiple of kv heads "
                         f"{k.shape[2]}")
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv,
        interpret=_compiled_mode(interpret, q, k, v))


def jacobi_sweep(x, b, g: int, *, interpret: Optional[bool] = None):
    if x.shape != (g * g,) or b.shape != (g * g,):
        raise ValueError(f"expected flat ({g*g},) arrays")
    return _jacobi.jacobi_sweep(x, b, g,
                                interpret=_compiled_mode(interpret, x, b))


def jacobi_halo_sweeps(xb, top, bot, b, *, sweeps: int,
                       interpret: Optional[bool] = None):
    """Fused frozen-halo row-block sweeps + block-local residual norm."""
    if xb.ndim != 2 or b.shape != xb.shape:
        raise ValueError(f"expected matching (rows, g) blocks, got "
                         f"{xb.shape} vs {b.shape}")
    g = xb.shape[1]
    if top.shape != (g,) or bot.shape != (g,):
        raise ValueError(f"expected ({g},) halo rows")
    if sweeps < 1:
        raise ValueError("sweeps must be >= 1")
    return _jacobi.jacobi_halo_sweeps(
        xb, top, bot, b, sweeps=sweeps,
        interpret=_compiled_mode(interpret, xb, top, bot, b))


def _bellman_mode(interpret: Optional[bool]) -> bool:
    if not _interpret(interpret):
        raise NotImplementedError(
            "the Pallas Bellman kernels run in interpret mode only: Mosaic "
            "lowers no general gather (a 1-D V[idx] is refused with 'Only "
            "2D gather is supported'; its 2-D gather needs indices of the "
            "operand's own shape).  Use device_plane='jnp' for value "
            "iteration on an accelerator.")
    return True


def bellman_block(idx, probs, rewards, v, v_old, *, gamma: float,
                  interpret: Optional[bool] = None):
    """Fused state-block Bellman backup + block-local residual norm."""
    rows, A, b = idx.shape
    if (probs.shape != (rows, A, b) or rewards.shape != (rows, A)
            or v.ndim != 1 or v_old.shape != (rows,)):
        raise ValueError("inconsistent MDP block shapes")
    return _bellman.bellman_block(idx, probs, rewards, v, v_old,
                                  gamma=gamma,
                                  interpret=_bellman_mode(interpret))


def bellman(idx, probs, rewards, v, *, gamma: float, block_s: int = 128,
            interpret: Optional[bool] = None):
    S, A, b = idx.shape
    if probs.shape != (S, A, b) or rewards.shape != (S, A) or v.shape != (S,):
        raise ValueError("inconsistent MDP shapes")
    return _bellman.bellman(idx, probs, rewards, v, gamma=gamma,
                            block_s=block_s,
                            interpret=_bellman_mode(interpret))


def anderson_mix(X, G, alpha, *, beta: float = 1.0, block_n: int = 4096,
                 interpret: Optional[bool] = None):
    if X.shape != G.shape or alpha.shape != (X.shape[0],):
        raise ValueError("inconsistent history shapes")
    return _mix.anderson_mix(X, G, alpha, beta=beta, block_n=block_n,
                             interpret=_compiled_mode(interpret, X, G, alpha))
