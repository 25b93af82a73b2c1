"""Pallas TPU flash attention (forward) with GQA / causal / window / softcap.

TPU-native design (not a CUDA port): the grid is (batch, q_head, Sq/bq,
Skv/bkv) executed sequentially with the KV-block axis innermost; the online-
softmax state (m, l) and the output accumulator live in VMEM scratch that
persists across the innermost grid dimension — the canonical TPU flash
pattern (MXU-aligned bq x bkv tiles, fp32 accumulation on the VPU).

GQA: the kv-head BlockSpec index map folds the query-head -> kv-head
mapping (h // group) so repeated KV heads are never materialized.

Validated against kernels/ref.py in interpret mode over shape/dtype sweeps
(tests/test_kernels.py); on real TPU hardware this kernel replaces the
chunked-jnp path in models/attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -2.0e38


def _flash_kernel(
    q_ref, k_ref, v_ref,  # (bq, hd), (bkv, hd), (bkv, hd)
    o_ref,  # (bq, hd)
    m_scr, l_scr, acc_scr,  # VMEM scratch
    *,
    scale: float,
    block_q: int,
    block_kv: int,
    seq_q: int,
    seq_kv: int,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(f32) * scale
    k = k_ref[...].astype(f32)
    v = v_ref[...].astype(f32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) + q_offset
    kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset", "block_q",
                     "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, nq, hd)
    k: jax.Array,  # (B, Skv, nkv, hd)
    v: jax.Array,  # (B, Skv, nkv, hd)
    *,
    interpret: bool,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    B, Sq, nq, hd = q.shape
    _, Skv, nkv, _ = k.shape
    assert nq % nkv == 0, (nq, nkv)
    group = nq // nkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    scale = float(1.0 / np.sqrt(hd))

    qt = q.transpose(0, 2, 1, 3)  # (B, nq, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)  # (B, nkv, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, nq, Sq // block_q, Skv // block_kv)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, block_q=block_q, block_kv=block_kv,
        seq_q=Sq, seq_kv=Skv, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_kv, hd),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((None, None, block_kv, hd),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), f32),
            pltpu.VMEM((block_q, 1), f32),
            pltpu.VMEM((block_q, hd), f32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # (B, Sq, nq, hd)
