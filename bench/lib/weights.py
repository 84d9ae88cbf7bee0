"""Seeded weights for a dense decoder LM, made on the device in one call.

The benchmark makes the weights, not the program: both the system under
test and the plain reference are fed the same tree, made from the run's
seed.  The canonical layout stacks every layer's leaves on a leading
layer axis:

    {"embed": (V, d), "final_norm": (d,),
     "layers": {"norm1": (L, d), "wq": (L, d, H*hd), "wk": (L, d, Hkv*hd),
                "wv": (L, d, Hkv*hd), "wo": (L, H*hd, d),
                ["q_norm": (L, hd), "k_norm": (L, hd)],
                "norm2": (L, d), "wi": (L, d, ff), ["wg": (L, d, ff)],
                "w2": (L, ff, d)}}

``to_program`` rearranges it into the tree ``repro.models.lm.LM`` takes
(a list of per-layer dicts in ``unrolled`` mode, stacked dicts in
``scan`` mode); ``from_program`` is its inverse.  Scales follow the
usual convention: dense N(0, 1/d_in), embedding N(0, 0.02^2), norm
scales 1.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (larger than 32 bits too)."""
    rng = np.random.default_rng([int(seed) % 2**63, stream])
    word = rng.integers(0, 2**31)
    return jax.random.PRNGKey(int(word))


def layer_shapes(m: dict) -> dict:
    d, L, ff = m["d_model"], m["num_layers"], m["d_ff"]
    hd = m["head_dim"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    s = {"norm1": (L, d), "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
         "wo": (L, q, d), "norm2": (L, d), "wi": (L, d, ff), "w2": (L, ff, d)}
    if m.get("qk_norm"):
        s["q_norm"] = (L, hd)
        s["k_norm"] = (L, hd)
    if m["mlp_act"] == "swiglu":
        s["wg"] = (L, d, ff)
    return s


def _canonical(key, m: dict, dtype) -> dict:
    shapes = layer_shapes(m)
    names = sorted(shapes)
    keys = jax.random.split(key, len(names) + 1)
    layers = {}
    for k, name in zip(keys[1:], names):
        shape = shapes[name]
        if "norm" in name:
            layers[name] = jnp.ones(shape, dtype)
        else:
            scale = 1.0 / math.sqrt(shape[1])
            layers[name] = (jax.random.normal(k, shape, jnp.float32)
                            * scale).astype(dtype)
    embed = (jax.random.normal(keys[0], (m["vocab_size"], m["d_model"]),
                               jnp.float32) * 0.02).astype(dtype)
    return {"embed": embed, "final_norm": jnp.ones((m["d_model"],), dtype),
            "layers": layers}


def to_program(canon: dict, m: dict) -> dict:
    lay = canon["layers"]

    def block(get):
        attn = {"wq": get("wq"), "wk": get("wk"), "wv": get("wv"),
                "wo": get("wo")}
        if "q_norm" in lay:
            attn["q_norm"] = {"scale": get("q_norm")}
            attn["k_norm"] = {"scale": get("k_norm")}
        mlp = {"wi": get("wi"), "wo": get("w2")}
        if "wg" in lay:
            mlp["wg"] = get("wg")
        return {"norm1": {"scale": get("norm1")}, "attn": attn,
                "norm2": {"scale": get("norm2")}, "mlp": mlp}

    if m["remat_mode"] == "scan":
        blocks = block(lambda n: lay[n])
    else:
        blocks = [block(lambda n, i=i: lay[n][i])
                  for i in range(m["num_layers"])]
    return {"embed": canon["embed"],
            "final_norm": {"scale": canon["final_norm"]}, "blocks": blocks}


def from_program(tree: dict, m: dict) -> dict:
    """The canonical layout of a program-layout tree (params, or an
    optimizer moment of the same structure)."""
    blocks = tree["blocks"]
    if isinstance(blocks, (list, tuple)):
        blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    a, p = blocks["attn"], blocks["mlp"]
    lay = {"norm1": blocks["norm1"]["scale"], "wq": a["wq"], "wk": a["wk"],
           "wv": a["wv"], "wo": a["wo"], "norm2": blocks["norm2"]["scale"],
           "wi": p["wi"], "w2": p["wo"]}
    if "q_norm" in a:
        lay["q_norm"] = a["q_norm"]["scale"]
        lay["k_norm"] = a["k_norm"]["scale"]
    if "wg" in p:
        lay["wg"] = p["wg"]
    return {"embed": tree["embed"], "final_norm": tree["final_norm"]["scale"],
            "layers": lay}


@functools.lru_cache(maxsize=None)
def _maker(model_items: tuple, dtype: str, program: bool):
    m = dict(model_items)

    def make(key):
        canon = _canonical(key, m, jnp.dtype(dtype))
        return to_program(canon, m) if program else canon
    return jax.jit(make)


def make_weights(seed: int, m: dict, *, dtype: str | None = None,
                 program: bool = True) -> dict:
    """Weights from ``seed`` in one jitted call on the default device:
    the program's layout (``program=True``) or the canonical one."""
    items = tuple(sorted((k, v) for k, v in m.items()
                         if isinstance(v, (int, float, str, bool))))
    return _maker(items, dtype or m["dtype"], program)(jax_key(seed))
