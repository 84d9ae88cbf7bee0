"""jit'd public wrappers around the Pallas kernels.

On TPU the kernels run compiled; everywhere else (this CPU container,
unit tests) they run in ``interpret=True`` mode, which executes the
kernel body with jnp semantics — bit-identical control flow, so the
allclose sweeps against ``ref.py`` validate the real kernel logic.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import offload_dma as _dma
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, kv_len=None, *, causal: bool = True,
                    window: int = 0):
    """q: (B, S, H, hd); k, v: (B, S, Hkv, hd) -> (B, S, H, hd).

    (Model layout; transposed to the kernel's (B, H, S, hd) internally.)
    ``kv_len``: optional (B,) int32 true lengths of a bucket-padded batch
    — padded keys are masked and fully-padded blocks skipped, so the
    kernel does work proportional to the *effective* tokens while the
    compiled shape stays the bucket shape.  A sequence longer than one
    128-block is padded to a block multiple (the pad is masked as
    padding and sliced off).
    """
    B, S = q.shape[:2]
    pad = (-S) % 128 if S > 128 else 0
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        if kv_len is None:
            kv_len = jnp.full((B,), S, jnp.int32)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _fa.flash_attention(qt, kt, vt, kv_len, causal, window, not _on_tpu())
    return o.transpose(0, 2, 1, 3)[:, :S]


@partial(jax.jit, static_argnames=("chunk_elems",))
def residual_dma_copy(x, *, chunk_elems: int = 1 << 15):
    """Stage a residual checkpoint through the double-buffered DMA
    pipeline (``offload_dma``): chunk ``i+1``'s fetch overlaps chunk
    ``i``'s drain.  Value-identical to ``x`` — the schedule, not the
    data, is the product."""
    return _dma.dma_copy(x, chunk_elems=chunk_elems,
                         interpret=not _on_tpu())


@partial(jax.jit, static_argnames=("chunk", "chunks_per_block"))
def ssd_scan(x, dt, A, Bm, Cm, kv_len=None, *, chunk: int = 64,
             chunks_per_block: int = 1):
    """Pads S to a ``chunk * chunks_per_block`` multiple and runs the
    Pallas SSD scan.

    ``kv_len``: optional (B,) int32 true lengths — contributions past a
    sequence's length never enter the recurrent state, and chunks fully
    inside the padding are never executed.  ``chunks_per_block``
    amortises grid dispatch over several chunks per cell.
    """
    B, S, H, P = x.shape
    span = chunk * chunks_per_block
    pad = (-S) % span
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    if kv_len is None and pad:
        kv_len = jnp.full((B,), S, jnp.int32)
    y = _ssd.ssd_scan(x, dt, A, Bm, Cm, kv_len=kv_len, chunk=chunk,
                      chunks_per_block=chunks_per_block,
                      interpret=not _on_tpu())
    return y[:, :S]
