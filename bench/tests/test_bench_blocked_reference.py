"""The training reference in blocks that fit one chip, and the harness
reaching it only through the module its configuration names."""
from __future__ import annotations

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.lib import check, reference
from bench.reference import dense_lm
from bench.reference.adamw import AdamW
from bench.tests.conftest import BENCH
from bench.traffic import gen

DATA = BENCH / "tests" / "data"
GELU = {"num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 16, "d_ff": 128, "mlp_act": "gelu", "vocab_size": 128,
        "tie_embeddings": True, "qk_norm": False, "rope_theta": 10000.0,
        "norm_eps": 1e-6, "remat_mode": "unrolled", "dtype": "float32"}
SWIGLU = dict(GELU, num_kv_heads=2, mlp_act="swiglu", qk_norm=True,
              rope_theta=1e6, remat_mode="scan", scan_chunks=3)


def _batch(seed, B=5, S=32, vocab=128):
    rng = np.random.default_rng(seed)
    lens = rng.integers(S // 2, S + 1, B).astype(np.int32)
    return gen.bigram_rows(lens, S, vocab, rng)


def _unblocked(params, batch, m, precision="fp32"):
    """The loss and gradient in one piece, from the full logits."""
    b = {k: jnp.asarray(v) for k, v in batch.items()}
    total_w = float(np.maximum(np.sum(batch["weights"]), 1.0))

    def f(p):
        lg = dense_lm.logits(p, b["tokens"], b["lengths"], m, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        lab = jnp.take_along_axis(lg, b["labels"][..., None], -1)[..., 0]
        return jnp.sum((lse - lab) * b["weights"]) / total_w
    return jax.value_and_grad(f)(params)


def _rel(a, b):
    return abs(a - b) / abs(b)


def _blocks(monkeypatch, rows, tokens):
    """Fix the block sizes, as a device too small for one block would."""
    monkeypatch.setattr(dense_lm, "block_sizes",
                        lambda m, n, S, limit: (rows, tokens))


@pytest.mark.parametrize("m", [GELU, SWIGLU], ids=["gelu", "swiglu_qk_norm"])
def test_blocked_reference_matches_the_unblocked_one(m, monkeypatch):
    """Rows in blocks of 2 of 5 (the last filled up), head blocks of 24
    of 32 tokens: the loss and every leaf's gradient norm to 1e-6."""
    params = dense_lm.make_weights(3, m, program=False)
    batch = _batch(1)
    _blocks(monkeypatch, 2, 24)
    with jax.default_matmul_precision("highest"):
        loss, g = dense_lm.loss_and_grad(params, batch, m)
        uloss, ug = _unblocked(params, batch, m)
    assert _rel(loss, float(uloss)) <= 1e-6
    got, want = check.leaf_norms(g), check.leaf_norms(ug)
    assert set(got) == set(want)
    worst = max(want, key=lambda n: _rel(got[n], want[n]))
    assert _rel(got[worst], want[worst]) <= 1e-6, worst


def test_int8_path_passes_its_gradient_straight_through(monkeypatch):
    """The int8 control of a training cell has a gradient near, and not
    equal to, the float32 one."""
    m = SWIGLU
    params = dense_lm.make_weights(4, m, program=False)
    batch = _batch(2)
    with jax.default_matmul_precision("highest"):
        _, g = dense_lm.loss_and_grad(params, batch, m)
        _blocks(monkeypatch, 3, 32)
        loss8, g8 = dense_lm.loss_and_grad(params, batch, m,
                                           precision="int8")
        uloss8, _ = _unblocked(params, batch, m, "int8")
    assert _rel(loss8, float(uloss8)) <= 1e-6
    want, got = check.leaf_norms(g), check.leaf_norms(g8)
    gaps = [_rel(got[n], want[n]) for n in want]
    assert 0 < max(gaps) < 0.5


def test_block_sizes_follow_the_shapes_and_the_limit():
    cfg = json.loads((BENCH / "configs/qwen3_1p7b.json").read_text())
    m = dict(cfg["model"], num_layers=7)
    # no memory statistics (the CPU): one block of each
    assert dense_lm.block_sizes(m, 8, 2048, None) == (8, 8 * 2048)
    # a v5e chip's bytes_limit holds the parameters, m, v, two gradients
    # and one row
    v5e = 16909336064.0
    rows, tokens = dense_lm.block_sizes(m, 8, 2048, v5e)
    assert 1 <= rows < 8 and 1 <= tokens < 2048
    more, _ = dense_lm.block_sizes(m, 8, 2048, 2 * v5e)
    assert more > rows
    # bert_base_paper's largest bucket fits whole
    b = json.loads((BENCH / "configs/bert_base_paper.json").read_text())
    assert dense_lm.block_sizes(b["model"], 48, 512, v5e)[0] == 48
    with pytest.raises(MemoryError):
        dense_lm.block_sizes(m, 8, 2048, 14e9)


def test_serving_names_no_program_control():
    """A serving cell reads its control as the gap of the token a lower
    precision of the reference puts first; the program's own path in
    another dtype is a training control only."""
    cfg = json.loads((BENCH / "configs/qwen3_1p7b.json").read_text())
    assert reference.control(cfg) == {"on": "reference",
                                      "precision": "int8"}
    with pytest.raises(ValueError):
        reference.control(dict(cfg, control={"on": "program",
                                              "dtype": "bfloat16"}))
    bert = json.loads((BENCH / "configs/bert_base_paper.json").read_text())
    assert reference.control(bert)["on"] == "program"


def test_leaf_norms_name_nested_layers():
    canon = {"embed": jnp.ones((4, 2)),
             "layers": {"moe": {"w": jnp.ones((3, 5, 2)) *
                                jnp.arange(1, 4.0)[:, None, None]},
                        "norm": jnp.ones((3, 2))}}
    n = check.leaf_norms(canon)
    assert n["embed"] == pytest.approx(np.sqrt(8))
    assert [n[f"layers.{i}.moe.w"] for i in range(3)] == pytest.approx(
        [np.sqrt(10) * i for i in (1, 2, 3)])
    assert len(n) == 7


def test_adamw_update_is_the_elementwise_formula():
    """One jitted call per leaf computes the formula evaluated op by op,
    to a few float32 roundings (the fused call may contract a multiply
    and an add)."""
    opt = AdamW(lr=3e-3, warmup=2, total=10, weight_decay=0.01,
                clip_norm=0.5)
    rng = np.random.default_rng(0)
    p = {"a": jnp.asarray(rng.normal(size=(7, 3)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    state = opt.init(p)
    for _ in range(3):
        g = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.normal(size=x.shape), jnp.float32), p)
        # the update donates the parameters and the state
        new, nstate = opt.update(g, *jax.tree_util.tree_map(jnp.copy,
                                                            (state, p)))
        t = state["step"] + 1
        gc = opt.clip_grads(g)
        bc1, bc2, lr = 1 - opt.b1 ** t, 1 - opt.b2 ** t, opt.rate(t)
        for k in p:
            m = opt.b1 * state["m"][k] + (1 - opt.b1) * gc[k]
            v = opt.b2 * state["v"][k] + (1 - opt.b2) * gc[k] * gc[k]
            want = p[k] - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + opt.eps)
                                + opt.wd * p[k])
            for got, ref in ((nstate["m"][k], m), (nstate["v"][k], v),
                             (new[k], want)):
                np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-8)
        p, state = new, nstate


def _bench_dir(tmp_path, tiny_bench, cfg: dict):
    """A benchmark directory holding one configuration, the tiny traffic,
    the metric readers and the reference modules, with the test-only
    one among them."""
    for sub in ("configs", "reference"):
        (tmp_path / sub).mkdir()
    (tmp_path / "traffic").symlink_to(tiny_bench / "traffic")
    (tmp_path / "metrics").symlink_to(BENCH / "metrics")
    for f in (BENCH / "reference").glob("*.py"):
        (tmp_path / "reference" / f.name).symlink_to(f)
    (tmp_path / "reference" / "spy_lm.py").symlink_to(DATA / "spy_lm.py")
    (tmp_path / "configs" / f"{cfg['name']}.json").write_text(
        json.dumps(cfg))
    spec = {"workloads": [{"name": "train-cell", "config": cfg["name"],
                           "traffic": "squad", "chips": 1, "why": "test"}],
            "end_to_end": [{"name": "train_tokens_per_s",
                            "unit": "tokens/s", "better": "higher",
                            "bound": 0.2, "source": "host_clock"},
                           {"name": "setup_s", "unit": "s",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock"}],
            "per_layer": []}
    return spec


def _args():
    return types.SimpleNamespace(workload="train-cell", seed=2**31 + 41,
                                 seconds=0.5, trace=0)


def test_harness_reaches_the_reference_through_the_config(tmp_path,
                                                          tiny_bench):
    cfg = json.loads((DATA / "spy_train.json").read_text())
    spec = _bench_dir(tmp_path, tiny_bench, cfg)
    spy = reference.load(cfg, tmp_path)
    spy.CALLS.clear()
    res = run.run_cell(_args(), require_tpu=False, bench_dir=tmp_path,
                       spec=spec)
    assert res["correct"], res["checks"]
    assert {"make_weights", "from_program", "loss_and_grad"} <= set(
        spy.CALLS)
    assert spy.CALLS.count("loss_and_grad") == 3
    with pytest.raises(KeyError):
        reference.load({k: v for k, v in cfg.items() if k != "reference"},
                       tmp_path)


def test_control_on_the_reference_int8_path_takes_the_programs_place(
        tmp_path, tiny_bench):
    """A configuration may name the reference's int8 path as its
    control: the reference at that precision then takes the program's
    place, reads far from the sound run, and comes out not correct
    through the cell's own comparison.  The limits are set as a
    configuration's are, between the readings at this size (sound loss
    gaps 1e-7 to 2e-7, the int8 control's 6e-5 to 9e-5); a cell's own
    come from the chip at its size."""
    cfg = json.loads((tiny_bench / "configs/bert_base_paper.json")
                     .read_text())
    cfg["control"] = {"on": "reference", "precision": "int8"}
    cfg["limits"] = {"loss_gap": 1e-5, "grad_gap": 3e-4,
                     "change_gap": 5e-4}
    spec = _bench_dir(tmp_path, tiny_bench, cfg)
    sound = run.run_cell(_args(), require_tpu=False, bench_dir=tmp_path,
                         spec=spec)
    ctrl = run.run_cell(_args(), require_tpu=False, control=True,
                        bench_dir=tmp_path, spec=spec)
    assert sound["correct"], sound["checks"]
    assert not ctrl["correct"], ctrl["checks"]
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert ctrl["checks"][name]["value"] > \
            100 * sound["checks"][name]["value"], name
