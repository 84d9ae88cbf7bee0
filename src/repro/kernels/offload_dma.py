"""Double-buffered async-copy (DMA) kernel for residual-stream staging.

XLA's ``save_and_offload_only_these_names`` policy leaves the residual
checkpoint's device->host copy on the main compute stream when it can't
prove overlap; this kernel is the manual path: the array is walked in
chunks through a two-slot VMEM scratch with explicit ``make_async_copy``
DMAs, so the fetch of chunk ``i+1`` is in flight while chunk ``i``
drains to its destination — the on-chip half of the double buffering
``repro.train.transfer.TransferLane`` does across the host link.

The kernel is a *copy* (source and destination live in compiler-chosen
``ANY`` memory space); its value is the DMA schedule, not the data
movement itself.  On TPU the two in-flight DMAs overlap in hardware; in
interpret mode (CPU tests) the same schedule executes with jnp
semantics, so correctness sweeps validate the real kernel logic.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 2 slots = double buffering: one DMA landing while the other drains
_SLOTS = 2


def _dma_copy_kernel(src_ref, dst_ref):
    n = src_ref.shape[0]                                # chunks

    def body(scratch, in_sems, out_sems):
        def copy_in(i, slot):
            return pltpu.make_async_copy(src_ref.at[i], scratch.at[slot],
                                         in_sems.at[slot])

        def copy_out(i, slot):
            return pltpu.make_async_copy(scratch.at[slot], dst_ref.at[i],
                                         out_sems.at[slot])

        # warm-up: start the first fetch before entering the loop
        copy_in(0, 0).start()

        def step(i, _):
            slot = jax.lax.rem(i, _SLOTS)
            nxt = 1 - slot

            # overlap: the next chunk's fetch rides behind this chunk's
            # drain — the whole point of the two-slot scratch
            @pl.when(i + 1 < n)
            def _():
                copy_in(i + 1, nxt).start()

            copy_in(i, slot).wait()
            copy_out(i, slot).start()
            copy_out(i, slot).wait()
            return 0

        jax.lax.fori_loop(0, n, step, 0)

    pl.run_scoped(body,
                  pltpu.VMEM((_SLOTS,) + src_ref.shape[1:], src_ref.dtype),
                  pltpu.SemaphoreType.DMA((_SLOTS,)),
                  pltpu.SemaphoreType.DMA((_SLOTS,)))


def dma_copy(x, *, chunk_elems: int = 1 << 15, interpret: bool = False):
    """Copy ``x`` through the double-buffered DMA pipeline.

    Flattens to chunks of ``chunk_elems`` (zero-padded tail), runs the
    kernel, and restores the original shape.  Returns an array equal to
    ``x``; on TPU the copy is a pipelined pair of DMA streams instead of
    one blocking transfer.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    chunk = int(min(chunk_elems, max(n, 1)))
    pad = (-n) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # each chunk a (rows, 128) tile stack, so that a chunk is an index on
    # an untiled leading dim (Mosaic slices tiled dims only at multiples
    # of 8 rows)
    lanes = 128 if chunk % 128 == 0 else chunk
    chunks = flat.reshape(-1, chunk // lanes, lanes)
    out = pl.pallas_call(
        _dma_copy_kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(chunks.shape, chunks.dtype),
        interpret=interpret,
    )(chunks)
    return out.reshape(-1)[:n].reshape(x.shape)
