"""Fused Pallas TPU kernel for the Mamba2 SSD chunked scan.

Motivated directly by the §Perf cell-A hillclimb: every high-level lever
(remat policy, chunk size, decay dtype) failed to move the memory term
because the [Q,Q] decay tile, the [Q,N] B/C tiles and the [Q,P] gated-input
tiles each round-trip HBM per elementwise op in the XLA path.  Here one grid
step computes a whole chunk *in VMEM*: cumulative decays, the masked decay
tile, the G=C·Bᵀ tile and the running [N,P] state never leave the core.

Grid = (B·H, n_chunks); the chunk axis is sequential ("arbitrary") so the
inter-chunk state lives in VMEM scratch.  Per-head inputs:
    x  [BH, S, P]   gated inputs (already conv'd + silu'd)
    dt [BH, S]      softplus'd step sizes
    Bm [BH, S, N]   input projections  (per-head copies of the shared B)
    Cm [BH, S, N]   output projections
    a  [BH]         per-head decay rate (negative)
Outputs: y [BH, S, P], final state [BH, N, P].

Recurrence (identical discretization to ``repro.models.ssm``):
    h_t = exp(a·dt_t)·h_{t-1} + dt_t·B_t⊗x_t ;  y_t = C_t·h_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, state_ref,
                h_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)        # [Q]
    Bm = b_ref[0].astype(jnp.float32)         # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)         # [Q, N]
    a = a_ref[0].astype(jnp.float32)          # scalar

    la = dt * a                               # log decay per step
    L = jnp.cumsum(la)                        # [Q]
    # intra-chunk: y_i += Σ_{j<=i} (C_i·B_j)·exp(L_i-L_j)·dt_j·x_j
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q,Q]
    decay = jnp.exp(L[:, None] - L[None, :])
    Q = chunk
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    att = jnp.where(rows >= cols, G * decay * dt[None, :], 0.0)
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q,P]
    # inter-chunk: y_i += exp(L_i)·(C_i·h_in)
    h = h_scr[...]                            # [N,P]
    y = y + jnp.exp(L)[:, None] * jax.lax.dot_general(
        Cm, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    # state update: h_out = exp(L_last)·h_in + Σ_j exp(L_last-L_j)·dt_j·B_j⊗x_j
    w = jnp.exp(L[-1] - L) * dt               # [Q]
    h_new = jnp.exp(L[-1]) * h + jax.lax.dot_general(
        Bm * w[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # [N,P]
    h_scr[...] = h_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        state_ref[0] = h_new.astype(state_ref.dtype)


def ssd_scan(x, dt, Bm, Cm, a, *, chunk: int = 128, interpret: bool = False):
    """x [BH,S,P], dt [BH,S], Bm/Cm [BH,S,N], a [BH] →
    (y [BH,S,P], state [BH,N,P])."""
    BH, S, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = -(-S // Q)
    pad = nc * Q - S
    if pad:
        # neutral padding: dt=0 ⇒ decay 1, zero state contribution
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(_ssd_kernel, chunk=Q, n_chunks=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q), lambda b, c: (b, c)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc * Q, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, Bm, Cm, a)
    return y[:, :S], state
