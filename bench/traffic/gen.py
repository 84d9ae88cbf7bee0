"""The one generator that reads every traffic file.

A traffic file (``bench/traffic/<name>.json``) is data: a length
distribution, and for serving an arrival rate and output lengths.  The
sizes of a mix are drawn once from the file's own ``pool_seed``; a run's
``--seed`` only reorders them and draws the token ids.  So every seed
does the same work in another order, and two runs of one seed are the
same.

Training (``"kind": "train"``): ``pool_batches`` batches of per-row
lengths, fed in a fresh seeded order on every pass.  Each row is the
bigram language of ``repro/data/pipeline.py`` (``t+1 = 31 t + 7 mod
V-1, +1``) from a random start token, padded with 0 to the batch's
longest row rounded up to the quantum; labels are the next token.

Serving (``"kind": "serve"``): ``round(rate_rps * seconds)`` requests
whose prompt lengths, output lengths and exponential inter-arrival gaps
come from the pool; the gaps are scaled so that the last request is due
when the window closes.  Prompt tokens are uniform in [1, V).

Length distributions (``"dist"``): ``normal`` (mean, std) rounded and
clipped to [lo, hi], ``uniform`` integers in [lo, hi], ``powerlaw``
(alpha) from lo, clipped to hi -- the shapes of ``DISTRIBUTIONS`` in
``repro/data/pipeline.py`` and ``gen_trace`` in ``repro/data/trace.py``.
"""
from __future__ import annotations

import functools
from typing import Iterator

import numpy as np

def sample_lengths(spec: dict, rng: np.random.Generator, n) -> np.ndarray:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    kind = spec["dist"]
    if kind == "normal":
        x = rng.normal(spec["mean"], spec["std"], n)
    elif kind == "powerlaw":
        u = rng.random(n)
        x = lo * (1 - u) ** (-1.0 / (spec["alpha"] - 1.0))
    elif kind == "uniform":
        return rng.integers(lo, hi + 1, n).astype(np.int32)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int32)


def bucket(n: int, quantum: int) -> int:
    q = max(int(quantum), 1)
    return (int(n) + q - 1) // q * q


def _seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, stream])


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_pool(traffic: dict, batch_size: int) -> np.ndarray:
    """(pool_batches, batch_size) per-row lengths, fixed by the file."""
    rng = np.random.default_rng(int(traffic["pool_seed"]))
    return sample_lengths(traffic["lengths"], rng,
                          (int(traffic["pool_batches"]), int(batch_size)))


def train_buckets(traffic: dict, batch_size: int, quantum: int) -> list:
    """Every bucket sequence length the feed produces, ascending."""
    pool = train_pool(traffic, batch_size)
    return sorted({bucket(int(r.max()), quantum) for r in pool})


@functools.lru_cache(maxsize=None)
def _affine_powers(S: int, a: int, c: int, M: int):
    """(A_t, C_t) with y_t = (A_t y_0 + C_t) mod M for y_t = a y_{t-1} + c."""
    A, C = np.empty(S, np.int64), np.empty(S, np.int64)
    A[0], C[0] = 1, 0
    for t in range(1, S):
        A[t] = A[t - 1] * a % M
        C[t] = (C[t - 1] * a + c) % M
    return A, C


def bigram_rows(lengths: np.ndarray, S: int, vocab_size: int,
                rng: np.random.Generator) -> dict:
    """Rows of ``t+1 = (31 t + 7) mod (V-1) + 1`` from random starts,
    computed in closed form: with y = t - 1 the step is affine mod V-1."""
    B, M = len(lengths), vocab_size - 1
    start = rng.integers(1, vocab_size, (B,), dtype=np.int64)
    mult = 31 % M or 1
    A, C = _affine_powers(S, mult, (mult + 7) % M, M)
    tokens = ((start[:, None] - 1) * A[None, :] + C[None, :]) % M + 1
    weights = (np.arange(S)[None, :] < lengths[:, None]).astype(np.float32)
    tokens = tokens.astype(np.int32) * weights.astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    labels[:, -1] = 0
    return {"tokens": tokens, "labels": labels, "weights": weights,
            "lengths": lengths.astype(np.int32)}


def train_feed(traffic: dict, *, batch_size: int, vocab_size: int,
               quantum: int, seed: int) -> Iterator[dict]:
    """Endless seeded batches: each pass over the pool in a new order."""
    pool = train_pool(traffic, batch_size)
    order_rng, tok_rng = _seed_rng(seed, 1), _seed_rng(seed, 2)
    while True:
        for i in order_rng.permutation(len(pool)):
            lens = pool[i]
            yield bigram_rows(lens, bucket(int(lens.max()), quantum),
                              vocab_size, tok_rng)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_requests(traffic: dict, *, vocab_size: int, seed: int,
                   seconds: float) -> list:
    """[(rid, arrival_s, prompt int32 array, max_new_tokens)], by arrival."""
    n = max(int(round(float(traffic["rate_rps"]) * float(seconds))), 1)
    pool = np.random.default_rng(int(traffic["pool_seed"]))
    prompts = sample_lengths(traffic["prompt"], pool, n)
    outs = sample_lengths(traffic["new_tokens"], pool, n)
    gaps = pool.exponential(1.0, n)
    gaps *= float(seconds) / gaps.sum()
    rng = _seed_rng(seed, 3)
    prompts, outs = prompts[rng.permutation(n)], outs[rng.permutation(n)]
    arrivals = np.cumsum(gaps[rng.permutation(n)])
    tok = _seed_rng(seed, 4)
    return [(i, float(arrivals[i]),
             tok.integers(1, vocab_size, int(prompts[i]),
                          dtype=np.int64).astype(np.int32),
             int(outs[i])) for i in range(n)]
