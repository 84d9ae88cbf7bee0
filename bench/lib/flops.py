"""Operations a dense decoder LM requires, computed from shapes.

The benchmark's copy of the arithmetic in ``repro/launch/roofline.py``
(``_attention_flops``, ``_mlp_flops``, ``unit_fwd_flops`` for dense
blocks), taking the configuration file's ``model`` dict.  A multiply-add
counts 2.  Causal attention counts S^2/2 query-key pairs for the score
and value products.
"""
from __future__ import annotations


def attention_flops(m: dict, B: int, S: int) -> float:
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    proj = 2.0 * B * S * d * q + 2.0 * 2.0 * B * S * d * kv \
        + 2.0 * B * S * q * d
    pairs = float(S) * S / 2.0
    return proj + 4.0 * B * m["num_heads"] * hd * pairs


def mlp_flops(m: dict, B: int, S: int) -> float:
    mult = 3.0 if m["mlp_act"] == "swiglu" else 2.0
    return 2.0 * B * S * m["d_model"] * m["d_ff"] * mult


def layer_fwd_flops(m: dict, B: int, S: int) -> float:
    """One block's forward at (B, S): the recompute cost of remat."""
    return attention_flops(m, B, S) + mlp_flops(m, B, S)


def head_flops(m: dict, tokens: int) -> float:
    return 2.0 * tokens * m["d_model"] * m["vocab_size"]


def seq_fwd_flops(m: dict, length: int) -> float:
    """Forward of one sequence at its true length, output head included."""
    return (m["num_layers"] * layer_fwd_flops(m, 1, int(length))
            + head_flops(m, int(length)))


def train_model_flops(m: dict, lengths) -> float:
    """Forward and backward (3x forward) the model requires for a batch
    of sequences at their true lengths; recomputation not counted."""
    return 3.0 * sum(seq_fwd_flops(m, int(L)) for L in lengths)


def decode_token_flops(m: dict, context: int) -> float:
    """Forward of one new token attending to ``context`` earlier ones."""
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    proj = 2.0 * d * q + 4.0 * d * kv + 2.0 * q * d
    score = 4.0 * m["num_heads"] * hd * (context + 1)
    return (m["num_layers"] * (proj + score + mlp_flops(m, 1, 1))
            + head_flops(m, 1))
