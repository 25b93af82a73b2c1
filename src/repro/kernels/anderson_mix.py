"""Pallas TPU fused Anderson/DIIS extrapolation (paper Eq. 2 application).

x_acc = sum_j alpha_j * ((1 - beta) * X_j + beta * G_j)

over a window of h iterate/map-value pairs of length-N states.  This is the
coordinator-side hot loop when the paper's technique drives large states
(the beyond-paper async-DP training case: N = parameter count).  Memory-
bound: one fused pass reads X and G once and writes x_acc once, instead of
2h+1 separate axpy passes.

The state axis is blocked (grid over N/bn, the last block padded); the
coefficients ride in VMEM as an (h, 1) column and the combine is a
broadcast-multiply and a sum over the h sublanes, written as a (1, bn) row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Block-index literal: a Python 0 is an i64 under x64, which Mosaic's
#: index maps cannot return.
_0 = np.int32(0)


def _mix_kernel(x_ref, g_ref, alpha_ref, o_ref, *, beta: float):
    combined = (1.0 - beta) * x_ref[...] + beta * g_ref[...]  # (h, bn)
    a = alpha_ref[...].astype(combined.dtype)  # (h, 1)
    o_ref[...] = jnp.sum(a * combined, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("beta", "block_n", "interpret"))
def anderson_mix(X: jax.Array, G: jax.Array, alpha: jax.Array, *,
                 beta: float, interpret: bool,
                 block_n: int = 4096) -> jax.Array:
    """X, G: (h, N) history (oldest first); alpha: (h,).  Returns (N,).

    ``block_n`` is rounded down to the 128-lane tile; a state shorter than
    one tile is a single block.
    """
    h, N = X.shape
    bn = max(128, block_n // 128 * 128)
    if bn >= N:
        bn = N
    out = pl.pallas_call(
        functools.partial(_mix_kernel, beta=beta),
        grid=(pl.cdiv(N, bn),),
        in_specs=[
            pl.BlockSpec((h, bn), lambda i: (_0, i)),
            pl.BlockSpec((h, bn), lambda i: (_0, i)),
            pl.BlockSpec((h, 1), lambda i: (_0, _0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (_0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), X.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(X, G, alpha.reshape(h, 1))
    return out.reshape(N)
