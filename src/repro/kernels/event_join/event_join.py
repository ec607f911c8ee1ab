"""Pallas TPU kernel for the paper's hot loop: composite-event join
aggregation (Table 1 "Join": 100 triggers × 2000 events each).

TPU-native adaptation (DESIGN.md §2): instead of a per-event Python
interpreter, a *batch* of routed events is reduced to per-trigger activation
counts via a one-hot segmented sum on the VPU, then compared against each
trigger's threshold.  Grid tiles the event stream into VMEM blocks of
``block_events``; per-trigger counts accumulate in VMEM scratch across the
(sequential) grid and fire flags are emitted on the last step.

Inputs:  events   [N]  int32 trigger ids (−1 = padding)
         counts   [T]  int32 current per-trigger counts (context state)
         expected [T]  int32 per-trigger thresholds
Outputs: new_counts [T] int32, fired [T] int32 (0/1)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _join_kernel(events_ref, counts_ref, expected_ref, new_counts_ref,
                 fired_ref, acc_scr, *, n_blocks: int, block_events: int,
                 n_triggers: int):
    ib = pl.program_id(0)

    @pl.when(ib == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ev = events_ref[...]                                   # [block_events]
    # one-hot segmented count: [block, T] compare on the VPU, reduce rows
    tids = jax.lax.broadcasted_iota(jnp.int32, (block_events, n_triggers), 1)
    onehot = (ev[:, None] == tids).astype(jnp.int32)
    acc_scr[...] = acc_scr[...] + onehot.sum(axis=0)

    @pl.when(ib == n_blocks - 1)
    def _finish():
        total = counts_ref[...] + acc_scr[...]
        new_counts_ref[...] = total
        fired_ref[...] = (total >= expected_ref[...]).astype(jnp.int32)


def event_join_counts(events, counts, expected, *, block_events: int = 1024,
                      interpret: bool = False):
    (N,) = events.shape
    (T,) = counts.shape
    block = min(block_events, N)
    nb = -(-N // block)
    if nb * block != N:
        events = jnp.pad(events, (0, nb * block - N), constant_values=-1)
    kernel = functools.partial(_join_kernel, n_blocks=nb, block_events=block,
                               n_triggers=T)
    new_counts, fired = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((T,), lambda i: (0,)),
            pl.BlockSpec((T,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((T,), lambda i: (0,)),
            pl.BlockSpec((T,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((T,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(events, counts, expected)
    return new_counts, fired
