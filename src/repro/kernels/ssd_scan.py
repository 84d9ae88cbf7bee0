"""Chunked Mamba2/SSD scan for TPU (Pallas, sequential-grid state carry).

TPU adaptation of the SSD algorithm [arXiv:2405.21060]: the chunk loop is
the *last* grid dimension with ``arbitrary`` semantics, so the recurrent
(P, N) state lives in a VMEM scratch buffer that persists across grid
steps — the TPU-idiomatic replacement for the CUDA warp-level scan.  The
intra-chunk work is two (Q, Q)-tile matmuls on the MXU; the inter-chunk
recurrence touches only the (P, N) state.

Layout: x (B, H, NC, Q, P); dt (B, H, NC, 1, Q); Bm/Cm (B, NC, Q, N);
A (H,) and the lengths in SMEM.  Grid: (B, H, NC) with NC sequential.

Ragged execution: a per-sequence ``kv_len`` operand marks the true
length of a bucket-padded batch.  Positions past the length contribute
nothing to the recurrent state (their dt is zeroed, so decay is exp(0)
and the update term vanishes), and chunks that lie entirely inside the
padding are never executed: each grid cell owns ``chunks_per_block``
chunks and walks them with a ``fori_loop`` whose trip count is the
number of *valid* chunks in the cell — shapes stay bucket-static, only
runtime trip counts depend on the lengths.  ``chunks_per_block > 1``
also amortises grid dispatch over several chunks (fewer, fatter cells),
at the price of a K*Q-position VMEM block per operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(kvl_ref, a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref,
                state_ref, *, chunk: int, chunks_per_block: int):
    g_idx = pl.program_id(2)
    Q = chunk
    K = chunks_per_block
    kvl = kvl_ref[pl.program_id(0)]                          # true length
    A = a_ref[pl.program_id(1)]                             # scalar decay rate
    base = g_idx * K                                        # first chunk here

    @pl.when(g_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    # chunks at or past the true length are skipped by trip count (their
    # outputs are padding); their y rows are pre-zeroed here
    valid = jnp.clip(pl.cdiv(kvl - base * Q, Q), 0, K)
    y_ref[...] = jnp.zeros_like(y_ref)

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

    def column(row):
        """(1, Q) row -> (Q, 1) column: the diagonal of its broadcast."""
        return jnp.sum(jnp.where(ii == jj, row, 0.0), axis=1, keepdims=True)

    def body(j, state):
        x = x_ref[0, 0, j].astype(jnp.float32)              # (Q, P)
        dt = dt_ref[0, 0, j].astype(jnp.float32)            # (1, Q)
        Bm = b_ref[0, j].astype(jnp.float32)                # (Q, N)
        Cm = c_ref[0, j].astype(jnp.float32)                # (Q, N)

        # zero the padded tail's dt: decay becomes exp(0)=1 and the state
        # update term dt*x*B vanishes, so padding never enters the state
        pos = (base + j) * Q + jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
        dt = jnp.where(pos < kvl, dt, 0.0)

        # cumulative log decay la_i = sum_{k<=i} dt_k A, as a column and
        # as a row (masked reductions: no cumsum or transpose in VMEM)
        dA = dt * A                                         # (1, Q)
        la_col = jnp.sum(jnp.where(jj <= ii, dA, 0.0), axis=1,
                         keepdims=True)                     # (Q, 1)
        la_row = jnp.sum(jnp.where(ii <= jj, column(dA), 0.0), axis=0,
                         keepdims=True)                     # (1, Q)
        la_end = jnp.sum(dA, axis=1, keepdims=True)         # (1, 1)

        # intra-chunk: L[i,j] = exp(la_i - la_j) * [i >= j]
        L = jnp.exp(jnp.where(ii >= jj, la_col - la_row, -jnp.inf))
        cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)
        y = jax.lax.dot_general(cb * L * dt, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (Q, P)

        # inter-chunk: contribution of the carried state
        y += jnp.exp(la_col) * jax.lax.dot_general(
            Cm, state, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (Q, P)
        y_ref[0, 0, j] = y.astype(y_ref.dtype)

        # state update: S' = exp(sum dA) S + sum_j exp(la_Q - la_j) dt_j x_j B_j^T
        w = jnp.exp(la_end - la_col) * column(dt)           # (Q, 1)
        xb = jax.lax.dot_general(x * w, Bm, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (P, N)
        return jnp.exp(la_end) * state + xb

    state_ref[...] = jax.lax.fori_loop(0, valid, body, state_ref[...])


def ssd_scan(x, dt, A, Bm, Cm, *, kv_len=None, chunk: int = 64,
             chunks_per_block: int = 1, interpret: bool = False):
    """x: (B, S, H, P); dt: (B, S, H); A: (H,); Bm, Cm: (B, S, N).

    Returns y: (B, S, H, P).  S must be a multiple of ``chunk *
    chunks_per_block`` (the ops wrapper pads to a chunk multiple and
    keeps ``chunks_per_block=1`` unless told otherwise).  ``kv_len``:
    optional (B,) int32 true lengths — state contributions past a
    sequence's length are zeroed and fully-padded chunks are never
    executed (dynamic trip counts).  ``A`` and the lengths ride in SMEM
    as scalar-prefetch operands; dt is laid out one (1, chunk) row per
    chunk so its block tiles on the chip.
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    K = int(chunks_per_block)
    assert S % (chunk * K) == 0, (S, chunk, K)
    NC = S // chunk
    if kv_len is None:
        kvl = jnp.full((B,), S, jnp.int32)
    else:
        kvl = jnp.clip(jnp.asarray(kv_len, jnp.int32), 0, S)

    xg = x.transpose(0, 2, 1, 3).reshape(B, H, NC, chunk, P)
    dtg = dt.transpose(0, 2, 1).reshape(B, H, NC, 1, chunk)
    bg = Bm.reshape(B, NC, chunk, N)
    cg = Cm.reshape(B, NC, chunk, N)

    x_blk = pl.BlockSpec((1, 1, K, chunk, P),
                         lambda b, h, c, *_: (b, h, c, 0, 0))
    bc_blk = pl.BlockSpec((1, K, chunk, N), lambda b, h, c, *_: (b, c, 0, 0))
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, chunks_per_block=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, NC // K),
            in_specs=[
                x_blk,
                pl.BlockSpec((1, 1, K, 1, chunk),
                             lambda b, h, c, *_: (b, h, c, 0, 0)),
                bc_blk, bc_blk,
            ],
            out_specs=x_blk,
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, NC, chunk, P), x.dtype),
        # the state carries across the chunk axis: it must run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kvl, jnp.asarray(A, jnp.float32), xg, dtg, bg, cg)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
