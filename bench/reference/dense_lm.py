"""Plain reference of the dense decoder LM the benchmark's models share.

Written from the architecture's description and imports nothing of the
program under test.  One pre-norm block:

    h = x + Wo . attn(rope(qk_norm(Wq . n1(x))), rope(qk_norm(Wk . n1(x))),
                      Wv . n1(x))            causal, GQA, keys < length
    y = h + W2 . act(n2(h))                  act: gelu (tanh form) . Wi,
                                             or silu(Wg .) * (Wi .)

with RMSNorm n(x) = x / sqrt(mean(x^2) + eps) * scale computed in float32,
rotary embeddings on the two halves of each head (theta from the config),
tied embeddings as the output head, and a token-weighted mean
cross-entropy.  Weights come in the canonical layout of
``bench/lib/weights.py``, which also gives ``make_weights`` and
``from_program``; the optimizer is the shared ``adamw.AdamW``.

``precision`` picks the arithmetic:

* ``"fp32"``: every value float32, every matrix product at
  ``Precision.HIGHEST`` -- the reference;
* ``"int8"``: the fp32 path with every matrix input quantised to int8
  (weights per output channel, activations per row) -- the control of a
  bfloat16 configuration that has no int8 path of its own.  Its gradient
  passes each quantiser straight through.

The gradient is computed in blocks that fit the device (``block_sizes``):
blocks of rows summed into one donated buffer, each layer of the scan
under ``jax.checkpoint``, and the output head and loss over blocks of
tokens, each under ``jax.checkpoint``, so no (rows, S, V) logits tensor
exists.  Blocking only reorders float32 sums.

The GELU is the tanh form: the configuration's ``mlp_act: gelu`` names
the model this repository defines, whose activation is that form.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.lib.weights import from_program, layer_shapes, make_weights
from bench.reference import blocks
from bench.reference.adamw import AdamW

__all__ = ["AdamW", "from_program", "make_weights", "loss_and_grad",
           "logits", "block_sizes"]

HIGHEST = lax.Precision.HIGHEST


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _q8(x, axis):
    """Symmetric int8 quantise-dequantise along ``axis`` (the reduced
    axis of the product), in float32."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


@_q8.defjvp
def _q8_jvp(axis, primals, tangents):
    (x,), (t,) = primals, tangents
    return _q8(x, axis), t.astype(jnp.float32)


def _mm(a, w, precision: str):
    """a (..., k) @ w (k, n)."""
    if precision == "fp32":
        return jnp.matmul(a.astype(jnp.float32), w.astype(jnp.float32),
                          precision=HIGHEST)
    return jnp.matmul(_q8(a, -1), _q8(w, 0), precision=HIGHEST)


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """x (B, S, H, hd); positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def block(x, p, lengths, m: dict, precision: str):
    B, S, d = x.shape
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    h = rmsnorm(x, p["norm1"], eps)
    q = _mm(h, p["wq"], precision).reshape(B, S, H, hd)
    k = _mm(h, p["wk"], precision).reshape(B, S, Hkv, hd)
    v = _mm(h, p["wv"], precision).reshape(B, S, Hkv, hd)
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], eps)
        k = rmsnorm(k, p["k_norm"], eps)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    # grouped-query attention: query head j reads key/value head j // g
    g = H // Hkv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=HIGHEST) / math.sqrt(hd)
    pos = jnp.arange(S)
    mask = (pos[None, :, None] >= pos[None, None, :]) \
        & (pos[None, None, :] < lengths[:, None, None])
    s = jnp.where(mask[:, None], s, jnp.finfo(jnp.float32).min)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v.astype(jnp.float32),
                   precision=HIGHEST).reshape(B, S, H * hd)
    x = x + _mm(o, p["wo"], precision).astype(x.dtype)
    h = rmsnorm(x, p["norm2"], eps)
    if m["mlp_act"] == "swiglu":
        u = jax.nn.silu(_mm(h, p["wg"], precision)) * _mm(h, p["wi"],
                                                          precision)
    elif m["mlp_act"] == "gelu":
        u = jax.nn.gelu(_mm(h, p["wi"], precision), approximate=True)
    else:
        u = jax.nn.relu(_mm(h, p["wi"], precision))
    return x + _mm(u, p["w2"], precision).astype(x.dtype)


def hidden(params, tokens, lengths, m: dict, precision: str = "fp32"):
    """Final-norm hidden states (B, S, d)."""
    x = params["embed"][tokens].astype(jnp.float32)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(x, p):
        return block(x, p, lengths, m, precision), None
    x, _ = lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"], m["norm_eps"])


def logits(params, tokens, lengths, m: dict, precision: str = "fp32"):
    x = hidden(params, tokens, lengths, m, precision)
    return _mm(x, params["embed"].T, precision).astype(jnp.float32)


def nll_sum(params, batch, m: dict, precision: str = "fp32",
            tokens: int | None = None):
    """Sum of token-weighted negative log-likelihoods of a batch, the
    output head over blocks of ``tokens`` tokens (default: one block)."""
    x = hidden(params, batch["tokens"], batch["lengths"], m, precision)
    d = x.shape[-1]
    x = x.reshape(-1, d)
    lab, w = batch["labels"].reshape(-1), batch["weights"].reshape(-1)
    n = x.shape[0]
    t = n if tokens is None else min(int(tokens), n)
    pad = -n % t
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
        lab = jnp.concatenate([lab, jnp.zeros((pad,), lab.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def head(total, blk):
        xb, lb, wb = blk
        lg = _mm(xb, params["embed"].T, precision).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, lb[:, None], -1)[:, 0]
        return total + jnp.sum((lse - ll) * wb), None
    total, _ = lax.scan(head, jnp.zeros((), jnp.float32),
                        (x.reshape(-1, t, d), lab.reshape(-1, t),
                         w.reshape(-1, t)))
    return total


def block_sizes(m: dict, n_rows: int, S: int,
                limit: float | None) -> tuple:
    """(rows, tokens): the rows of one gradient block and the tokens of
    one head block, from the configuration's shapes and the device's
    ``bytes_limit`` (``limit``; None: one block of each).  Resident: the
    parameters, AdamW's m and v, the summed gradient and one block's
    gradient; per row: every layer's input kept for the backward, and
    one layer's recomputed activations with their cotangents; per token
    of the head: logits, probabilities and their cotangent.  The
    estimate lies above what the compiler allots: for a described v5e,
    qwen3_1p7b cut to 7 layers at 1 row of 2048 and 256 head tokens
    needs 14.19 GB with m and v (estimate 15.15 GB), bert_base_paper at
    48 rows of 512 and 8192 head tokens 6.13 GB (estimate 10.16 GB)."""
    if limit is None:
        return n_rows, n_rows * S
    d, L, V, ff = m["d_model"], m["num_layers"], m["vocab_size"], m["d_ff"]
    H, hd = m["num_heads"], m["head_dim"]
    n_params = sum(math.prod(s) for s in layer_shapes(m).values()) \
        + V * d + d
    resident = blocks.F32 * 5 * n_params
    mlp = (5 if m["mlp_act"] == "swiglu" else 4) * ff
    per_row = blocks.F32 * S * (L * d + 3 * H * S + mlp + 6 * H * hd
                                + 8 * d)
    per_token = blocks.F32 * 3 * V
    free = USABLE * limit - resident
    if free < per_row + per_token:
        raise MemoryError(
            f"the reference needs {resident / 2**30:.2f} GiB resident and "
            f"{per_row / 2**30:.2f} GiB a row; {USABLE} of the device's "
            f"{limit / 2**30:.2f} GiB does not hold them")
    tokens = min(n_rows * S, 1 << int(math.log2(
        max(HEAD_SHARE * free / per_token, 1))))
    rows = int((free - tokens * per_token) // per_row)
    return max(min(rows, n_rows), 1), tokens


# the share of ``bytes_limit`` the estimate may fill, and of what is left
# after the resident trees, the share a head block may take
USABLE = 0.9
HEAD_SHARE = 0.25


@functools.lru_cache(maxsize=None)
def _grad_fn(model_items: tuple, precision: str, tokens: int):
    m = dict(model_items)

    def f(acc, params, batch):
        s, g = jax.value_and_grad(nll_sum)(params, batch, m, precision,
                                           tokens)
        return jax.tree_util.tree_map(jnp.add, acc, g), s
    return jax.jit(f, donate_argnums=0)


def _items(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def loss_and_grad(params, batch, m: dict, *, keep_rows: int | None = None,
                  precision: str = "fp32"):
    """Token-weighted mean loss of ``batch`` and its gradient, in the
    blocks of rows and of head tokens that ``block_sizes`` sets from the
    device's ``bytes_limit``.  ``keep_rows`` keeps only the first rows
    (the half-batch fault)."""
    B, S = np.shape(batch["tokens"])
    n = B if keep_rows is None else keep_rows
    rows, tokens = block_sizes(m, n, S, blocks.bytes_limit())
    return blocks.sum_grads(_grad_fn(_items(m), precision, int(tokens)),
                            params, batch, rows=rows, keep_rows=keep_rows)
