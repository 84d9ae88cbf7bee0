"""The comparisons that decide ``correct``, and how they are printed.

Training: each checked step's loss, the first gradient as the optimizer
got it, and the parameters' change after the checked steps, each as
the widest relative gap of a per-leaf norm (a leaf is one layer's
matrix or vector in the reference's canonical layout).  The gap of
one leaf is ``|norm_program - norm_reference|`` over the larger of the
reference leaf's norm and the median leaf's.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.
"""
from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# a leaf whose reference gradient is below this share of the median
# leaf's moves under Adam by round-off alone: left out of the change
STILL_LEAF_SHARE = 1e-3


def leaf_norms(canon: dict) -> dict:
    """``{name: norm}`` of a tree in a reference's canonical layout: one
    entry per leaf, named by its dotted path, and one per layer
    (``layers.<i>.<path>``) for each leaf under ``"layers"``, which is
    stacked on a leading layer axis."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(canon)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        a = a.astype(jnp.float32)
        if keys[0] != "layers":
            out[".".join(keys)] = float(jnp.linalg.norm(a))
            continue
        n = np.asarray(jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim)))))
        rest = ".".join(keys[1:])
        for i, v in enumerate(n):
            out[f"layers.{i}.{rest}"] = float(v)
    return out


def diff_norms(a: dict, b: dict) -> dict:
    return leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """(widest gap, leaf) over the leaves in ``keep`` (default: all)."""
    names = sorted(ref if keep is None else keep)
    med = float(np.median([ref[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        g = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not np.isfinite(g):
            g = float("inf")
        if g >= worst:
            worst, where = g, n
    return worst, where


def moving_leaves(ref_grad: dict) -> list:
    med = float(np.median(list(ref_grad.values())))
    return [n for n, v in ref_grad.items() if v >= STILL_LEAF_SHARE * med]


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [...], "grad": leaf norms of the first
    clipped gradient, "change": leaf norms of the change after the
    checked steps}."""
    lp, lr = np.asarray(prog["losses"], float), np.asarray(ref["losses"],
                                                           float)
    with np.errstate(invalid="ignore", divide="ignore"):
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad"], ref["grad"])
    keep = moving_leaves(ref["grad"])
    change_gap, change_leaf = worst_leaf_gap(prog["change"], ref["change"],
                                             keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "_where": {"grad_gap": grad_leaf, "change_gap": change_leaf,
                       "still_leaves": len(ref["grad"]) - len(keep)}}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every compared number beside its limit."""
    checks = {}
    ok = True
    for name, lim in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= lim
        ok = ok and good
        checks[name] = {"value": v if v is None or np.isfinite(v)
                        else str(v), "limit": lim}
    return ok, checks


def print_checks(checks: dict, where: dict | None = None) -> None:
    """The compared numbers as the last lines on standard error."""
    if where:
        print(f"checks: where {json.dumps(where)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
