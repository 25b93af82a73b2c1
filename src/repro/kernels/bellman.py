"""Pallas Bellman operator (the paper's §3.3.2 hot loop).

(T V)(s) = max_a [ R(s,a) + gamma * sum_b P_b(s,a) * V(idx_b(s,a)) ]

The state axis is blocked (grid over S/bs); the value vector V is resident
in VMEM and each block gathers its (bs, A, b) successor values, then takes
the expectation and the max over actions.

These kernels run in interpret mode only.  Mosaic (JAX 0.9, libtpu 0.0.34)
lowers no general gather: ``V[idx]`` with a 1-D V is refused with "Only 2D
gather is supported", and its 2-D gather requires the indices to have the
operand's own shape, which a Garnet successor table (arbitrary states of
S = 2**20) does not.  ``repro.kernels.ops`` raises ``NotImplementedError``
instead of compiling them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _bellman_kernel(idx_ref, probs_ref, r_ref, v_ref, o_ref, *, gamma: float):
    idx = idx_ref[...]  # (bs, A, b) int32
    probs = probs_ref[...]  # (bs, A, b)
    r = r_ref[...]  # (bs, A)
    v = v_ref[...]  # (S,) resident
    succ = v[idx]  # VMEM gather
    ev = jnp.sum(probs * succ, axis=-1)  # (bs, A)
    o_ref[...] = jnp.max(r + gamma * ev, axis=-1)


def _bellman_block_kernel(idx_ref, probs_ref, r_ref, v_ref, vold_ref,
                          o_ref, n_ref, *, gamma: float):
    """Fused state-block Bellman backup + block-local inf-norm residual."""
    idx = idx_ref[...]  # (rows, A, b) int32 — positions into v_ref
    probs = probs_ref[...]  # (rows, A, b)
    r = r_ref[...]  # (rows, A)
    v = v_ref[...]  # (D,) resident successor values
    succ = v[idx]  # VMEM gather
    ev = jnp.sum(probs * succ, axis=-1)
    tv = jnp.max(r + gamma * ev, axis=-1)
    o_ref[...] = tv
    n_ref[0, 0] = jnp.max(jnp.abs(tv - vold_ref[...]))


@functools.partial(jax.jit, static_argnames=("gamma", "interpret"))
def bellman_block(idx: jax.Array, probs: jax.Array, rewards: jax.Array,
                  v: jax.Array, v_old: jax.Array, *, gamma: float,
                  interpret: bool):
    """One Bellman backup for a block of ``rows`` states, fused with its
    block-local residual.

    ``v`` is the successor-value vector the (possibly remapped) ``idx``
    gathers from — the full iterate, or just the block's dependency
    closure when the device plane ships dependency slices.  ``v_old`` is
    the block's previous values.  Returns ``(tv_block, local_inf_norm)``.
    """
    rows, A, b = idx.shape
    tv, norm = pl.pallas_call(
        functools.partial(_bellman_block_kernel, gamma=gamma),
        out_shape=(jax.ShapeDtypeStruct((rows,), v.dtype),
                   jax.ShapeDtypeStruct((1, 1), v.dtype)),
        interpret=interpret,
    )(idx, probs, rewards, v, v_old)
    return tv, norm[0, 0]


@functools.partial(jax.jit, static_argnames=("gamma", "block_s", "interpret"))
def bellman(idx: jax.Array, probs: jax.Array, rewards: jax.Array,
            v: jax.Array, *, gamma: float, interpret: bool,
            block_s: int = 128) -> jax.Array:
    S, A, b = idx.shape
    bs = min(block_s, S)
    while S % bs:
        bs -= 1
    grid = (S // bs,)
    return pl.pallas_call(
        functools.partial(_bellman_kernel, gamma=gamma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, A, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((bs, A, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((bs, A), lambda i: (i, 0)),
            pl.BlockSpec((S,), lambda i: (0,)),  # V resident across blocks
        ],
        out_specs=pl.BlockSpec((bs,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((S,), v.dtype),
        interpret=interpret,
    )(idx, probs, rewards, v)
