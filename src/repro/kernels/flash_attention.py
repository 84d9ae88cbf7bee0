"""Blockwise flash attention for TPU (Pallas, explicit VMEM BlockSpecs).

TPU adaptation of FlashAttention: rather than the CUDA shared-memory /
warp formulation, tiles are chosen for the MXU (128-aligned q/k blocks)
and staged HBM->VMEM by ``pl.pallas_call`` BlockSpecs.  The online
softmax runs in fp32 on the VPU; the (q_block, k_block) score tile never
leaves VMEM, so per-layer residual memory is O(S) — this is the kernel
whose effect the Mimose estimator observes as the quadratic coefficient
of its fitted memory curve collapsing to ~0.

Layout: q (B, H, S, hd); k, v (B, Hkv, S, hd) — GQA is expressed in the
kv index_map (query head h reads kv head h // group), so no repeat is
materialised.

Grid: (B, H, S // block_q); the k loop runs inside the kernel over
block_k-sized VMEM slices.  The whole-S k/v blocks of one head stay in
VMEM: at S=4096, hd=128 that is 1 MiB each in bf16 (double-buffered).

Ragged execution: every kernel takes a per-sequence ``kv_len`` operand
(true lengths of a bucket-padded batch).  Padded keys are masked out of
the online softmax, and the inner fori_loop trip counts are clamped so
k-blocks entirely past the true length — and q-blocks entirely inside
the padding — are never executed.  Shapes stay bucket-static (the
compile-once property is untouched); only runtime trip counts and masks
depend on the lengths, so one executable serves every raggedness.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _seq_tile(ref, idx, size):
    """The ``idx``-th (size, hd) tile of a (1, 1, S, hd) ref, as fp32."""
    start = pl.multiple_of(idx * size, size)
    return ref[0, 0, pl.ds(start, size), :].astype(jnp.float32)


def _keep(q_pos, k_pos, kvl, causal: bool, window: int):
    """Score-tile mask: keys inside the true length, plus the causal and
    sliding-window constraints."""
    mask = k_pos < kvl
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    return mask


def _q_block_trips(qi, bq, block_k, nkb, kvl, causal: bool):
    """Key blocks a query block visits: with causal masking, key blocks
    past this query block contribute nothing; key blocks entirely past
    the true length likewise, and a query block entirely inside the
    padding skips the loop outright."""
    upper = nkb if not causal else jnp.minimum(
        nkb, pl.cdiv((qi + 1) * bq, block_k))
    upper = jnp.minimum(upper, pl.cdiv(kvl, block_k))
    return jnp.where(qi * bq >= kvl, 0, upper)


_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_NN = (((1,), (0,)), ((), ()))       # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _flash_kernel(kvl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                  block_k: int, causal: bool, window: int, sm_scale: float):
    bq, hd = q_ref.shape[-2], q_ref.shape[-1]
    qi = pl.program_id(2)
    kvl = kvl_ref[pl.program_id(0)]                          # true length

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale           # (bq, hd)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k = _seq_tile(k_ref, j, block_k)                     # (bk, hd)
        v = _seq_tile(v_ref, j, block_k)
        s = _dot(q, k, _NT)                                  # (bq, bk)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        s = jnp.where(_keep(q_pos, k_pos, kvl, causal, window), s, NEG_INF)

        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)                 # (bq, 1)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * correction + _dot(p, v, _NN)
        return acc, m_cur, l_cur

    upper = _q_block_trips(qi, bq, block_k, pl.cdiv(k_ref.shape[-2], block_k),
                           kvl, causal)
    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)                           # (bq, 1)


def _resolve_kv_len(kv_len, B: int, S: int):
    """Normalise ``kv_len`` to a clamped (B,) int32 vector (None -> S)."""
    if kv_len is None:
        return jnp.full((B,), S, jnp.int32)
    return jnp.clip(jnp.asarray(kv_len, jnp.int32), 0, S)


def flash_attention_fwd(q, k, v, kv_len=None, *, causal: bool = True,
                        window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False, return_lse: bool = False):
    """q: (B, H, S, hd); k, v: (B, Hkv, S, hd) -> (B, H, S, hd) [, lse].

    ``kv_len``: optional (B,) int32 true sequence lengths — positions at
    or past a sequence's length are masked out and skipped blockwise.
    S must be a multiple of the blocks (``ops.flash_attention`` pads).
    The lengths ride in SMEM as a scalar-prefetch operand; the kernel
    writes lse as an (S, 1) column so its block tiles as (8, 128).
    """
    B, H, S, hd = q.shape
    group = H // k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(hd)
    q_blk = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, _: (b, h, i, 0))
    kv_all = pl.BlockSpec((1, 1, S, hd),
                          lambda b, h, i, _: (b, h // group, 0, 0))

    o, lse = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                          window=window, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, S // block_q),
            in_specs=[q_blk, kv_all, kv_all],
            out_specs=[q_blk,
                       pl.BlockSpec((1, 1, block_q, 1),
                                    lambda b, h, i, _: (b, h, i, 0))]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_resolve_kv_len(kv_len, B, S), q, k, v)
    return (o, lse[..., 0]) if return_lse else o


# ---------------------------------------------------------------------------
# backward kernels: blockwise dq and dk/dv with the score tile recomputed
# in VMEM from the saved (q, k, v, lse) — the FlashAttention-2 backward,
# adapted to TPU grid semantics.  The dq kernel works on (q, k) score
# tiles and reads lse/delta as (block_q, 1) columns; the dk/dv kernel
# works on transposed (k, q) tiles and reads them as (1, S) rows, so
# neither kernel transposes in VMEM.  GQA: dk/dv are produced per
# *query* head and reduced over the group outside the kernel.
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(kvl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, *, block_k: int, causal: bool,
                         window: int, sm_scale: float):
    bq, hd = q_ref.shape[-2], q_ref.shape[-1]
    qi = pl.program_id(2)
    kvl = kvl_ref[pl.program_id(0)]
    q = q_ref[0, 0].astype(jnp.float32)                       # (bq, hd)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                       # (bq, 1)
    delta = delta_ref[0, 0]                                   # (bq, 1)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, dq):
        k = _seq_tile(k_ref, j, block_k)
        v = _seq_tile(v_ref, j, block_k)
        s = sm_scale * _dot(q, k, _NT)                        # (bq, bk)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        p = jnp.where(_keep(q_pos, k_pos, kvl, causal, window),
                      jnp.exp(s - lse), 0.0)
        ds = p * (_dot(do, v, _NT) - delta) * sm_scale
        return dq + _dot(ds, k, _NN)

    upper = _q_block_trips(qi, bq, block_k, pl.cdiv(k_ref.shape[-2], block_k),
                           kvl, causal)
    dq = jax.lax.fori_loop(0, upper, body, jnp.zeros((bq, hd), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(kvl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, *, block_q: int,
                          causal: bool, window: int, sm_scale: float):
    bk, hd = k_ref.shape[-2], k_ref.shape[-1]
    ki = pl.program_id(2)
    kvl = kvl_ref[pl.program_id(0)]
    k = k_ref[0, 0].astype(jnp.float32)                       # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 0)
    nqb = pl.cdiv(q_ref.shape[-2], block_q)
    lower = 0 if not causal else ki * bk // block_q
    # query blocks past the true length contribute nothing to dk/dv; a
    # key block entirely inside the padding skips the loop outright
    upper = jnp.minimum(nqb, pl.cdiv(kvl, block_q))
    upper = jnp.where(ki * bk >= kvl, 0, upper)

    def body(i, carry):
        dk, dv = carry
        q = _seq_tile(q_ref, i, block_q)                      # (bq, hd)
        do = _seq_tile(do_ref, i, block_q)
        cols = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        lse = lse_ref[0, 0, :, cols]                          # (1, bq)
        delta = delta_ref[0, 0, :, cols]
        s_t = sm_scale * _dot(k, q, _NT)                      # (bk, bq)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bk, block_q), 1)
        keep = (q_pos < kvl) & _keep(q_pos, k_pos, kvl, causal, window)
        p_t = jnp.where(keep, jnp.exp(s_t - lse), 0.0)
        dv = dv + _dot(p_t, do, _NN)
        ds_t = p_t * (_dot(v, do, _NT) - delta) * sm_scale
        dk = dk + _dot(ds_t, q, _NN)
        return dk, dv

    dk0 = jnp.zeros((bk, hd), jnp.float32)
    dk, dv = jax.lax.fori_loop(lower, upper, body, (dk0, dk0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, kv_len=None, *, causal: bool,
                        window: int, block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Blockwise backward.  Returns (dq, dk, dv) with dk/dv group-reduced.
    ``lse``: the (B, H, S) log-sum-exp the forward returned."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    sm_scale = 1.0 / math.sqrt(hd)
    kvl = _resolve_kv_len(kv_len, B, S)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                   # (B, H, S)

    q_blk = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, _: (b, h, i, 0))
    col_blk = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, _: (b, h, i, 0))
    kv_all = pl.BlockSpec((1, 1, S, hd),
                          lambda b, h, i, _: (b, h // group, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          causal=causal, window=window, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, S // block_q),
            in_specs=[q_blk, kv_all, kv_all, q_blk, col_blk, col_blk],
            out_specs=q_blk),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
    )(kvl, q, k, v, do, lse[..., None], delta[..., None])

    # dk/dv per query head, reduced over the GQA group afterwards
    q_all = pl.BlockSpec((1, 1, S, hd), lambda b, h, i, _: (b, h, 0, 0))
    row_all = pl.BlockSpec((1, 1, 1, S), lambda b, h, i, _: (b, h, 0, 0))
    kv_blk = pl.BlockSpec((1, 1, block_k, hd),
                          lambda b, h, i, _: (b, h // group, i, 0))
    out_blk = pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, _: (b, h, i, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          causal=causal, window=window, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, S // block_k),
            in_specs=[q_all, kv_blk, kv_blk, q_all, row_all, row_all],
            out_specs=[out_blk, out_blk]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
        ],
        interpret=interpret,
    )(kvl, q, k, v, do, lse[:, :, None, :], delta[:, :, None, :])
    dk = dk_h.reshape(B, Hkv, group, S, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, group, S, hd).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP: residuals are O(S) (q, k, v, o, lse) — the flash memory
# signature.  Backward recomputes the score tiles blockwise in VMEM
# (FlashAttention-2 backward, Pallas kernels above).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, kv_len=None, causal: bool = True,
                    window: int = 0, interpret: bool = False):
    return flash_attention_fwd(q, k, v, kv_len, causal=causal, window=window,
                               interpret=interpret)


def _fwd(q, k, v, kv_len, causal, window, interpret):
    o, lse = flash_attention_fwd(q, k, v, kv_len, causal=causal,
                                 window=window, interpret=interpret,
                                 return_lse=True)
    return o, (q, k, v, o, lse, kv_len)


def _bwd(causal, window, interpret, res, do):
    q, k, v, o, lse, kv_len = res
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, kv_len,
                                     causal=causal, window=window,
                                     interpret=interpret)
    # int32 lengths are non-differentiable: their cotangent type is float0
    dlen = (None if kv_len is None
            else np.zeros(np.shape(kv_len), jax.dtypes.float0))
    return dq, dk, dv, dlen


flash_attention.defvjp(_fwd, _bwd)
