"""``dense_lm`` on the weights as the configuration stores them.

A configuration whose weights are stored below float32 (``"dtype":
"bfloat16"``) trains the seed's weights rounded to that dtype.  This
module gives the reference those same weights: asked for float32, it
makes them in the configuration's dtype and casts them up, so the fp32
reference computes the model the program holds and a gap measures the
arithmetic alone.  Everything else is ``dense_lm``'s.
"""
from __future__ import annotations

import jax

from bench.reference.dense_lm import (AdamW, block_sizes, from_program,
                                      logits, loss_and_grad)
from bench.reference.dense_lm import make_weights as _make_weights

__all__ = ["AdamW", "from_program", "make_weights", "loss_and_grad",
           "logits", "block_sizes"]


def make_weights(seed: int, m: dict, *, dtype: str | None = None,
                 program: bool = True) -> dict:
    """The seed's weights in ``m["dtype"]``, cast to ``dtype`` if given."""
    w = _make_weights(seed, m, program=program)
    if dtype is None or dtype == m["dtype"]:
        return w
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), w)
