"""Pallas TPU kernel: causal flash attention with triangular block skip.

Grid = (batch*kv_heads, q_blocks, kv_blocks); one step contracts a
(block_q, dh) x (block_k, dh) tile pair in VMEM with online softmax.
``pl.when`` skips every strictly-upper block (j > i) — on TPU the skipped
grid step costs only the (empty) control iteration, so causal attention
runs at the exact triangular FLOP count.  This is the hardware answer to
the 2x masked-FLOP overhead of the XLA-level blockwise path (§Perf), and
the reason kernels/ exists for this hot-spot.

Layout: q (BH, Sq, dh), k/v (BH, Skv, dh) with the GQA group folded into
BH by the ops.py wrapper (q heads of one kv head share its k/v tiles).
fp32 accumulators live in VMEM scratch; output is written on the last
unskipped kv step of each q row.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, sm_scale: float, causal: bool):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip blocks entirely above the causal diagonal: visible iff some
    # q_pos >= k_pos, i.e. the block's first k position <= last q position
    run = (j * block_k <= i * block_q + (block_q - 1)) if causal else (j >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0]                       # (block_q, dh)
        k = k_ref[0]                       # (block_k, dh)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:  # last visible kv block for this q row (uneven blocks ok)
        last = jnp.minimum(nk - 1, ((i + 1) * block_q - 1) // block_k)
    else:
        last = nk - 1

    @pl.when(j == last)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_pallas_call(bh: int, sq: int, skv: int, dh: int, *, block_q: int,
                      block_k: int, causal: bool, dtype, interpret: bool):
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    grid = (bh, sq // block_q, skv // block_k)
    kern = functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                             sm_scale=1.0 / math.sqrt(dh), causal=causal)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),    # l (running sum)
            pltpu.VMEM((block_q, dh), jnp.float32),   # acc
        ],
        interpret=interpret,
        name="repro_flash_attention",
    )
