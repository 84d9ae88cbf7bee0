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
``bench/lib/weights.py``.

``precision`` picks the arithmetic:

* ``"fp32"``: every value float32, every matrix product at
  ``Precision.HIGHEST`` -- the reference;
* ``"int8"``: the fp32 path with every matrix input quantised to int8
  (weights per output channel, activations per row) -- the control of a
  bfloat16 configuration that has no int8 path of its own.

The GELU is the tanh form: the configuration's ``mlp_act: gelu`` names
the model this repository defines, whose activation is that form.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _q8(x, axis):
    """Symmetric int8 quantise-dequantise along ``axis`` (the reduced
    axis of the product), in float32."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


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

    def body(x, p):
        return block(x, p, lengths, m, precision), None
    x, _ = lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"], m["norm_eps"])


def logits(params, tokens, lengths, m: dict, precision: str = "fp32"):
    x = hidden(params, tokens, lengths, m, precision)
    return _mm(x, params["embed"].T, precision).astype(jnp.float32)


def nll_sum(params, batch, m: dict, precision: str = "fp32"):
    """Sum of token-weighted negative log-likelihoods of a batch."""
    lg = logits(params, batch["tokens"], batch["lengths"], m, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    lab = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    return jnp.sum((lse - lab) * batch["weights"])


@functools.lru_cache(maxsize=None)
def _grad_fn(model_items: tuple):
    m = dict(model_items)

    def f(params, batch):
        return jax.value_and_grad(nll_sum)(params, batch, m)
    return jax.jit(f)


def _items(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def loss_and_grad(params, batch, m: dict, *, rows: int = 8,
                  keep_rows: int | None = None):
    """Token-weighted mean loss of ``batch`` and its gradient, summed in
    blocks of ``rows`` rows so that a batch larger than the device holds
    at once still fits.  ``keep_rows`` keeps only the first rows (the
    half-batch fault)."""
    import numpy as np
    n = int(np.shape(batch["tokens"])[0])
    if keep_rows is not None:
        n = keep_rows
    total_w = float(np.maximum(np.sum(np.asarray(batch["weights"])[:n]), 1.0))
    fn = _grad_fn(_items(m))
    val, grad = 0.0, None
    for r in range(0, n, rows):
        sub = {k: jnp.asarray(np.asarray(v)[r:min(r + rows, n)])
               for k, v in batch.items()}
        s, g = fn(params, sub)
        val += float(s)
        grad = g if grad is None else jax.tree_util.tree_map(jnp.add, grad, g)
    return val / total_w, jax.tree_util.tree_map(lambda a: a / total_w, grad)


class AdamW:
    """AdamW as published (Loshchilov & Hutter), with global-norm
    clipping first and a linear-warmup cosine schedule."""

    def __init__(self, *, lr, warmup, total, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.01, clip_norm=1.0):
        self.lr, self.warmup, self.total = lr, warmup, total
        self.b1, self.b2, self.eps = b1, b2, eps
        self.wd, self.clip = weight_decay, clip_norm

    def rate(self, step: int) -> float:
        if step < self.warmup:
            return self.lr * step / max(self.warmup, 1)
        prog = min(max((step - self.warmup)
                       / max(self.total - self.warmup, 1), 0.0), 1.0)
        return self.lr * 0.5 * (1 + math.cos(math.pi * prog))

    def init(self, params):
        z = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"step": 0, "m": z, "v": z}

    def clip_grads(self, grads):
        norm = jnp.sqrt(sum(jnp.sum(g * g)
                            for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, self.clip / (norm + 1e-9))
        return jax.tree_util.tree_map(lambda g: g * scale, grads)

    def update(self, grads, state, params):
        t = state["step"] + 1
        g = self.clip_grads(grads)
        m = jax.tree_util.tree_map(lambda a, b: self.b1 * a + (1 - self.b1)
                                   * b, state["m"], g)
        v = jax.tree_util.tree_map(lambda a, b: self.b2 * a + (1 - self.b2)
                                   * b * b, state["v"], g)
        bc1, bc2, lr = 1 - self.b1 ** t, 1 - self.b2 ** t, self.rate(t)
        new = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2)
                                                   + self.eps)
                                      + self.wd * p), params, m, v)
        return new, {"step": t, "m": m, "v": v}
