"""AdamW as published (Loshchilov & Hutter), with global-norm clipping
first and a linear-warmup cosine schedule: the training reference's
optimizer, shared by every reference module.

One jitted call per leaf computes the element-wise formula in float32,
with the step's scalars passed as weakly typed numbers, as an eager
evaluation would take them.  It donates the leaf and its moments, so an
update needs no second copy of the parameters or of AdamW's state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 2, 3))
def _leaf_update(p, g, m, v, scale, lr, bc1, bc2, *, b1, b2, eps, wd):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p)
    return p, m, v


class AdamW:
    """The optimizer's settings, and its update of a whole tree."""

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
        def zeros():
            return jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"step": 0, "m": zeros(), "v": zeros()}

    def clip_scale(self, grads):
        norm = jnp.sqrt(sum(jnp.sum(g * g)
                            for g in jax.tree_util.tree_leaves(grads)))
        return jnp.minimum(1.0, self.clip / (norm + 1e-9))

    def clip_grads(self, grads):
        scale = self.clip_scale(grads)
        return jax.tree_util.tree_map(lambda g: g * scale, grads)

    def update(self, grads, state, params):
        """The new parameters and state; ``params`` and ``state`` are
        donated."""
        t = state["step"] + 1
        scale = self.clip_scale(grads)
        bc1, bc2, lr = 1 - self.b1 ** t, 1 - self.b2 ** t, self.rate(t)
        flat, tree = jax.tree_util.tree_flatten(params)
        out = [_leaf_update(p, g, m, v, scale, lr, bc1, bc2, b1=self.b1,
                            b2=self.b2, eps=self.eps, wd=self.wd)
               for p, g, m, v in zip(
                   flat, tree.flatten_up_to(grads),
                   tree.flatten_up_to(state["m"]),
                   tree.flatten_up_to(state["v"]))]
        new, m, v = (tree.unflatten(x) for x in zip(*out))
        return new, {"step": t, "m": m, "v": v}
