"""Summing a training reference's gradient over blocks of rows, so that
a reference at published widths fits one chip.

A reference module gives a jitted ``fn(acc, params, block) -> (acc +
grad, nll sum)`` that donates ``acc``; ``sum_grads`` drives it over the
batch.  The mathematics is the reference's own: blocking only reorders
float32 sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = 4


def bytes_limit() -> float | None:
    """The default device's ``bytes_limit``, or None where the backend
    reports no memory statistics (the CPU)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return float(stats["bytes_limit"])


@jax.jit
def _zeros(params):
    return jax.tree_util.tree_map(jnp.zeros_like, params)


@functools.partial(jax.jit, donate_argnums=0)
def _divide(tree, w):
    return jax.tree_util.tree_map(lambda a: a / w, tree)


def sum_grads(fn, params, batch: dict, *, rows: int,
              keep_rows: int | None = None):
    """Token-weighted mean loss of ``batch`` and its gradient, summed in
    blocks of ``rows`` rows into one donated buffer.  The last block is
    filled up with rows of weight 0, which add exact zeros, so every
    block has one shape.  ``keep_rows`` keeps only the first rows (the
    half-batch fault)."""
    host = {k: np.asarray(v) for k, v in batch.items()}
    n = int(host["tokens"].shape[0]) if keep_rows is None else keep_rows
    host = {k: v[:n] for k, v in host.items()}
    total_w = float(np.maximum(np.sum(host["weights"]), 1.0))
    rows = max(min(int(rows), n), 1)
    pad = -n % rows
    if pad:
        host = {k: np.concatenate([v, np.zeros((pad,) + v.shape[1:],
                                               v.dtype)])
                for k, v in host.items()}
    acc = _zeros(params)
    val = 0.0
    for r in range(0, n + pad, rows):
        acc, s = fn(acc, params, {k: jnp.asarray(v[r:r + rows])
                                  for k, v in host.items()})
        val += float(s)
    return val / total_w, _divide(acc, total_w)
