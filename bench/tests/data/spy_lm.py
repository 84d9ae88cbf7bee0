"""A test-only reference module: the dense reference, recording which
of its functions the harness calls, in ``CALLS``."""
from __future__ import annotations

from bench.reference import dense_lm
from bench.reference.dense_lm import AdamW, logits  # noqa: F401

CALLS = []


def _recorded(fn):
    def f(*args, **kwargs):
        CALLS.append(fn.__name__)
        return fn(*args, **kwargs)
    return f


make_weights = _recorded(dense_lm.make_weights)
from_program = _recorded(dense_lm.from_program)
loss_and_grad = _recorded(dense_lm.loss_and_grad)
