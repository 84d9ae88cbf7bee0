"""A training run with the timed path broken underneath reads
``correct`` false; the sound run and the control bracket the limits.

Each run skips the harness's look for a chip and drives everything else
at the tiny sizes of ``conftest.tiny_bench``.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import pytest

from bench import run


def _args(workload="train-bert-squad"):
    return types.SimpleNamespace(workload=workload, seed=2**31 + 17,
                                 seconds=0.5, trace=0)


def _frozen(step):
    """A step that returns its state unchanged."""
    def f(params, state, batch):
        keep = jax.tree_util.tree_map(jnp.copy, (params, state))
        _, _, loss = step(params, state, batch)
        return keep[0], keep[1], loss
    return f


def _half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(params, state, batch):
        half = len(batch["tokens"]) // 2
        return step(params, state, {k: v[:half] for k, v in batch.items()})
    return f


def _run(tiny_bench, **kw):
    return run.run_cell(_args(), require_tpu=False, bench_dir=tiny_bench,
                        **kw)


def test_sound_run_is_correct(tiny_bench):
    res = _run(tiny_bench)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert sum(res["_compiles_in_window"].values()) == 0
    assert list(res)[list(res).index("checks") + 1:] == [
        "_where", "_compiles_in_window"]


@pytest.mark.parametrize("fault", [_frozen, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(tiny_bench, fault):
    res = _run(tiny_bench, fault=fault)
    assert not res["correct"], res["checks"]


def test_control_on_the_bf16_path_is_not_correct(tiny_bench):
    res = _run(tiny_bench, control=True)
    assert not res["correct"], res["checks"]
