"""Adaptive microbatching: gradient accumulation as a planner action.

The third axis of the memory/step-time trade space.  REMAT trades bytes
for recompute FLOPs and OFFLOAD trades bytes for PCIe traffic, but both
must keep *something* per unit on device — when a large bucket exceeds
the budget under even the most aggressive action plan, the only lever
left is the batch itself.  Splitting a mini-batch into ``k``
microbatches with gradient accumulation scales the batch-linear
activation terms by ~1/k while keeping the optimizer semantics of the
full mini-batch, so the planner can treat ``k`` as one more knob chosen
*per bucket*, jointly with the per-unit action plan
(``scheduler.greedy_plan_adaptive``).

This module is the execution half:

* ``split_batch`` — split (and, when ``B % k != 0``, zero-pad) a batch
  dict into ``k`` equal microbatches along the batch axis, the ragged
  ``lengths`` operand included.  Padded rows carry zero loss weight and
  zero length, so they contribute nothing to the loss, the gradients,
  or the length-aware kernels' executed work.
* ``accumulated_grads`` — one forward+backward per microbatch under a
  ``lax.scan``, accumulating *token-weighted* loss and gradients so the
  result matches the full-batch step exactly (the full-batch loss is
  ``sum(nll * w) / sum(w)``; weighting each microbatch's mean by its
  token count recovers the same global mean even when raggedness makes
  the microbatch weights unequal).  Activation liveness is bounded by
  ONE microbatch: each scan iteration completes its own backward before
  the next begins.
* ``accumulated_step_fn`` / ``build_accumulated_step`` — the trainer's
  train-step counterpart: grads -> optimizer update, one XLA compile
  per ``(actions, k, bucket)`` key (the trainer's jit cache adds ``k``
  to the step key).

Numerical contract (locked by ``tests/test_microbatch.py``): for
families without an auxiliary loss (dense / SSM / hybrid / enc-dec —
``aux == 0``), loss and grads from the ``k``-microbatch scan match the
full-batch step to fp32 allclose for any ``k``, including ragged
batches — exactness is why the planner may substitute a ``k``-split
step for the full step freely.  For MoE families the cross-entropy
term keeps that exactness, but the load-balance auxiliary loss is a
*nonlinear* statistic of router probabilities: the accumulated step
uses the token-weighted mean of the per-microbatch aux — the standard
gradient-accumulation semantics — which regularises balance per
microbatch rather than per mini-batch (an all-pad microbatch from
batch-axis padding contributes zero, see ``body``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def split_batch(batch: dict, k: int) -> dict:
    """Split a batch dict into ``k`` equal microbatches along axis 0.

    Every entry with the batch leading dimension (tokens, labels,
    weights, ``lengths``, frames, vision_embeds, positions...) gains a
    leading microbatch axis: ``(B, ...) -> (k, ceil(B/k), ...)``.  When
    ``k`` does not divide ``B`` the batch axis is zero-padded first —
    pad rows get token 0, weight 0.0 and length 0, so they are inert in
    the loss and in the length-aware kernels.  ``weights`` is
    materialised (all-ones over the original rows) when absent, because
    ``lm.loss`` would otherwise give the pad rows weight 1.
    """
    k = max(int(k), 1)
    B = int(np.shape(batch["tokens"])[0])
    out = dict(batch)
    if "weights" not in out:
        out["weights"] = jnp.ones(jnp.shape(batch["tokens"]), jnp.float32)
    Bp = -(-B // k) * k
    split = {}
    for key, v in out.items():
        a = jnp.asarray(v)
        assert a.ndim >= 1 and a.shape[0] == B, (
            f"batch entry {key!r} has no batch axis to split: "
            f"shape {a.shape}, batch {B}")
        if Bp != B:
            a = jnp.pad(a, [(0, Bp - B)] + [(0, 0)] * (a.ndim - 1))
        split[key] = a.reshape((k, Bp // k) + a.shape[1:])
    return split


def accumulated_grads(lm, params, batch, k: int, actions=None,
                      remat_policy=None) -> Tuple[jax.Array, dict, dict]:
    """Loss, metrics and gradients of ``lm.loss`` over ``k`` microbatches.

    Returns ``(loss, metrics, grads)`` matching
    ``jax.value_and_grad(lm.loss, has_aux=True)`` on the full batch to
    fp32 allclose (aux-free families; the MoE auxiliary loss follows
    per-microbatch semantics — module docstring).  Each scan iteration
    accumulates the *unnormalised*
    quantities (``loss_i * tokens_i`` recovers the microbatch's nll sum
    regardless of the loss's internal weight clamp; ``grads_i *
    tokens_i`` likewise) and the final division by the true global
    token count restores the full-batch mean.  Accumulators are fp32;
    grads are cast back to the parameter dtypes at the end.
    """
    k = max(int(k), 1)
    mbs = split_batch(batch, k)

    def loss_fn(p, mb):
        return lm.loss(p, mb, remat_mask=actions, remat_policy=remat_policy)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def body(carry, mb):
        g_acc, l_acc, a_acc, w_acc = carry
        (loss, metrics), grads = grad_fn(params, mb)
        w_raw = jnp.sum(mb["weights"]).astype(jnp.float32)
        # weight by the loss's (clamped) token count so loss * t
        # recovers the microbatch's nll sum exactly — but zero it for
        # an all-pad microbatch (w_raw == 0, t clamped to 1), which
        # must contribute nothing: its ce grads vanish on their own,
        # but a family's aux loss (MoE load balance) would not
        t = jnp.where(w_raw > 0, metrics["tokens"].astype(jnp.float32),
                      0.0)
        g_acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32) * t, g_acc, grads)
        l_acc = l_acc + loss.astype(jnp.float32) * t
        a_acc = a_acc + metrics["aux"].astype(jnp.float32) * t
        w_acc = w_acc + w_raw
        return (g_acc, l_acc, a_acc, w_acc), None

    init = (jax.tree_util.tree_map(
                lambda a: jnp.zeros(jnp.shape(a), jnp.float32), params),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32))
    (g_acc, l_acc, a_acc, w_acc), _ = jax.lax.scan(body, init, mbs)

    denom = jnp.maximum(w_acc, 1.0)
    grads = jax.tree_util.tree_map(
        lambda g, p: (g / denom).astype(jnp.asarray(p).dtype), g_acc, params)
    loss = l_acc / denom
    aux = a_acc / denom
    metrics = {"ce": loss - aux, "aux": aux, "tokens": denom}
    return loss, metrics, grads


def accumulated_step_fn(lm, optimizer, actions, k: int, remat_policy=None):
    """Raw (un-jitted) ``k``-way accumulated train step.

    Same contract as the trainer's inner ``train_step``:
    ``(params, opt_state, batch) -> (params, opt_state, loss, metrics)``
    — the split happens *inside* the step, so callers pass the ordinary
    bucket-shaped batch and shard it as usual (``launch/steps.py`` jits
    this with its own NamedShardings for the dry-run).
    """
    def train_step(params, opt_state, batch):
        loss, metrics, grads = accumulated_grads(
            lm, params, batch, k, actions=actions, remat_policy=remat_policy)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, loss, metrics

    return train_step


def build_accumulated_step(lm, optimizer, actions, k: int,
                           remat_policy=None):
    """Jitted ``accumulated_step_fn`` (params/opt_state donated) — what
    the trainer caches under its ``(bucket, actions, k, mesh)`` key."""
    return jax.jit(accumulated_step_fn(lm, optimizer, actions, k,
                                       remat_policy=remat_policy),
                   donate_argnums=(0, 1))
