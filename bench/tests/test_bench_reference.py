"""The plain reference against the program at a small size on the CPU,
and the benchmark's own arithmetic against hand-worked values."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import check, flops
from bench.lib.device import PEAKS
from bench.lib.weights import from_program, make_weights, to_program
from bench.reference import dense_lm
from bench.traffic import gen

BERT_SMALL = {"num_layers": 3, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
              "mlp_act": "gelu", "vocab_size": 128, "tie_embeddings": True,
              "qk_norm": False, "rope_theta": 10000.0, "norm_eps": 1e-6,
              "remat_mode": "unrolled", "dtype": "float32"}
QWEN_SMALL = dict(BERT_SMALL, num_kv_heads=2, mlp_act="swiglu",
                  qk_norm=True, rope_theta=1e6, remat_mode="scan",
                  scan_chunks=3)
ADAMW = {"lr": 3e-3, "warmup": 2, "total": 10, "b1": 0.9, "b2": 0.999,
         "eps": 1e-8, "weight_decay": 0.01, "clip_norm": 1.0}


def _lm(arch, m):
    from repro.models.lm import build_model
    from repro.models.registry import get_config
    return build_model(dataclasses.replace(get_config(arch), **m))


def _batch(seed, B=4, S=32, vocab=128):
    rng = np.random.default_rng(seed)
    lens = rng.integers(S // 2, S + 1, B).astype(np.int32)
    return gen.bigram_rows(lens, S, vocab, rng)


def test_weights_round_trip_the_program_layout():
    for arch, m in (("bert_base_paper", BERT_SMALL),
                    ("qwen3-1.7b", QWEN_SMALL)):
        lm = _lm(arch, m)
        params = make_weights(7, m)
        assert jax.tree_util.tree_structure(params) == \
            jax.tree_util.tree_structure(jax.eval_shape(
                lm.init, jax.random.PRNGKey(0)))
        canon = make_weights(7, m, program=False)
        back = to_program(from_program(params, m), m)
        for a, b in zip(jax.tree_util.tree_leaves(back),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(from_program(params, m)["layers"]["wq"],
                                      canon["layers"]["wq"])


@pytest.mark.parametrize("arch,m", [("bert_base_paper", BERT_SMALL),
                                    ("qwen3-1.7b", QWEN_SMALL)])
def test_forward_loss_and_grad_match_the_program(arch, m, monkeypatch):
    # the reference in blocks of 3 rows
    monkeypatch.setattr(dense_lm, "block_sizes",
                        lambda m, n, S, limit: (3, n * S))
    lm = _lm(arch, m)
    params = make_weights(3, m)
    batch = {k: jnp.asarray(v) for k, v in _batch(1).items()}
    n_units = lm.num_plan_units()
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: lm.loss(p, batch, remat_mask=(True,) * n_units),
            has_aux=True)(params)
        rloss, rg = dense_lm.loss_and_grad(make_weights(3, m, program=False),
                                           _batch(1), m)
    assert abs(float(loss) - rloss) <= 1e-5 * abs(rloss)
    gap, where = check.worst_leaf_gap(
        check.leaf_norms(from_program(g, m)), check.leaf_norms(rg))
    assert gap <= 1e-4, where


def test_trainer_step_under_a_remat_plan_matches_the_reference():
    from repro.core import MimosePlanner
    from repro.optim.adamw import AdamW, cosine_schedule
    from repro.train.trainer import Trainer
    m = BERT_SMALL
    lm = _lm("bert_base_paper", m)
    # a budget just above the fixed bytes: every bucket must remat
    planner = MimosePlanner(lm, 1.0, quantum=32, warmup_samples=3)
    opt = AdamW(lr=cosine_schedule(ADAMW["lr"], ADAMW["warmup"],
                                   ADAMW["total"]))
    trainer = Trainer(lm, planner, opt)
    params = make_weights(5, m)
    state = opt.init(params)
    batches = [_batch(s) for s in (11, 12, 13)]
    with jax.default_matmul_precision("highest"):
        prog = {"losses": []}
        for i, b in enumerate(batches):
            params, state, loss = trainer.step(params, state, b)
            prog["losses"].append(loss)
            if i == 0:
                prog["grad"] = check.leaf_norms(jax.tree_util.tree_map(
                    lambda a: a / 0.1, from_program(state.m, m)))
        assert all(s.remat_units > 0 for s in trainer.history)
        prog["change"] = check.diff_norms(from_program(params, m),
                                          make_weights(5, m, program=False))
        from bench.lib.train_cell import reference_readings
        ref = reference_readings(dense_lm,
                                 {"model": m, "train": {"adamw": ADAMW}},
                                 5, batches)
    nums = check.train_numbers(prog, ref)
    assert nums["loss_gap"] <= 1e-5
    assert nums["grad_gap"] <= 1e-4
    assert nums["change_gap"] <= 1e-3


def test_serve_engine_prefill_and_decode_match_the_reference():
    from repro.data.trace import TraceRequest
    from repro.train.engine import ServeEngine
    m = QWEN_SMALL
    lm = _lm("qwen3-1.7b", m)
    params = make_weights(9, m)
    rng = np.random.default_rng(0)
    reqs = [TraceRequest(rid=i, arrival_s=0.01 * i,
                         prompt=rng.integers(1, 128, n).astype(np.int32),
                         max_new_tokens=k)
            for i, (n, k) in enumerate([(13, 5), (29, 7), (40, 4)])]
    with jax.default_matmul_precision("highest"):
        eng = ServeEngine(lm, params, hbm_bytes=1e10, quantum=16,
                          max_slots=2, prefill_chunk=8, decode_steps=2)
        eng.run(reqs)
    from bench.lib.serve_cell import reference_gaps
    samples = [(lv.req.prompt, lv.tokens) for lv in eng.done]
    assert len(samples) == 3
    gaps = reference_gaps(dense_lm, {"model": m}, 9, samples)
    assert max(float(g.max()) for g in gaps) <= 1e-4


def test_flops_arithmetic_for_bert_base():
    import json
    from bench.tests.conftest import BENCH
    m = json.loads((BENCH / "configs/bert_base_paper.json").read_text())[
        "model"]
    # one layer, B=1, S=512: q,k,v,o projections 2*512*768*768*4,
    # causal scores and values 4*12*64*512*512/2, MLP 2*512*768*3072*2
    assert flops.layer_fwd_flops(m, 1, 512) == (2415919104 + 402653184
                                                + 4831838208)
    assert flops.head_flops(m, 512) == 2 * 512 * 768 * 30522
    # the copy agrees with the program's own arithmetic
    from repro.launch.roofline import unit_fwd_flops
    from repro.models.registry import get_config
    cfg = get_config("bert_base_paper")
    assert flops.layer_fwd_flops(m, 48, 480) == unit_fwd_flops(
        cfg, "dense", batch=48, seq=480)
    assert flops.train_model_flops(m, [512]) == 3 * (
        12 * 7650410496 + 2 * 512 * 768 * 30522)
    assert PEAKS["TPU v5 lite"].flops == 197e12
    assert PEAKS["TPU v5 lite"].hbm_bw == 819e9
