"""Pallas TPU flash attention (causal, GQA) — the data-plane compute hot spot.

Grid = (batch·q_heads, q_blocks, kv_blocks); the kv axis is the innermost
("arbitrary") dimension so the online-softmax state (m, l, acc) lives in VMEM
scratch across kv steps.  BlockSpecs tile Q/K/V into VMEM: q [block_q, D],
k/v [block_k, D] — MXU-aligned multiples of 128.  Fully-masked causal blocks
are skipped with ``pl.when`` (the triangular schedule).  GQA is handled in the
K/V index maps: query head h reads kv head h // group_size, so no K/V
repetition ever materializes.

Validated in interpret mode against ``ref.naive_attention`` (CPU container;
TPU is the target, see DESIGN.md §6).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  nk: int, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # triangular schedule: skip blocks strictly above the causal diagonal
    run = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # [bq, D]
        k = k_ref[0].astype(jnp.float32)                      # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols < seq_len
        if causal:
            mask &= rows >= cols
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + p.sum(axis=1)
        v = v_ref[0].astype(jnp.float32)                      # [bk, Dv]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False):
    """q [BH, S, D]; k/v [BHkv, S, D] with BH = G·BHkv (same batch order).

    Returns [BH, S, Dv]."""
    BH, S, D = q.shape
    BHkv = k.shape[0]
    Dv = v.shape[-1]
    G = BH // BHkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = -(-S // block_q)
    nk = -(-S // block_k)
    Sp = nq * block_q
    Skp = nk * block_k
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0)))
    if Skp != S:
        k = jnp.pad(k, ((0, 0), (0, Skp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skp - S), (0, 0)))
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, nk=nk, seq_len=S)
    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :S]
