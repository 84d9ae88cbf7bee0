"""The blocked output head and loss (``repro.models.lm.blocked_xent``)
against the unblocked formula, the planner's count of the head, and the
absence of (B, S, V) logits from a compiled training step."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MimosePlanner
from repro.models import lm as lm_mod
from repro.models.lm import blocked_xent, build_model, head_block_tokens
from repro.models.registry import get_config
from repro.optim.adamw import AdamW
from repro.train.accumulate import accumulated_grads
from repro.train.trainer import Trainer

TOL = 1e-6


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _unblocked_xent(h, w, labels, scale, vocab_rows):
    """The formula in one piece: full logits, take_along_axis."""
    logits = (h @ (w.T if vocab_rows else w)).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum((lse - label) * scale)


def _batch(B, S, vocab, seed=0, pad_rows=0):
    """A ragged batch; the last ``pad_rows`` rows have weight 0 only."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(S // 3, S + 1, B).astype(np.int32)
    lens[0] = S
    lens[B - pad_rows:] = 0
    tokens = rng.integers(1, vocab, (B, S)).astype(np.int32)
    w = (np.arange(S)[None, :] < lens[:, None]).astype(np.float32)
    labels = np.roll(tokens * w.astype(np.int32), -1, axis=1)
    labels[:, -1] = 0
    return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
            "weights": jnp.asarray(w), "lengths": jnp.asarray(lens)}


@pytest.mark.parametrize("vocab_rows", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("n,block", [(96, 32), (100, 32), (7, 32)],
                         ids=["divides", "remainder", "one_short_block"])
def test_blocked_xent_matches_the_unblocked_formula(vocab_rows, n, block):
    """Loss and the gradients of the hidden state and of the head, with
    a third of the tokens at weight 0, to 1e-6 relative."""
    d, V = 24, 300
    k = jax.random.split(jax.random.PRNGKey(n), 4)
    h = jax.random.normal(k[0], (n, d))
    w = jax.random.normal(k[1], (V, d) if vocab_rows else (d, V)) * 0.2
    labels = jax.random.randint(k[2], (n,), 0, V)
    wts = (jax.random.uniform(k[3], (n,)) > 0.33).astype(jnp.float32)
    scale = wts / jnp.maximum(wts.sum(), 1.0)
    with jax.default_matmul_precision("highest"):
        got, (gh, gw) = jax.value_and_grad(
            lambda a, b: blocked_xent(a, b, labels, scale, block,
                                      vocab_rows), (0, 1))(h, w)
        want, (wh, ww) = jax.value_and_grad(
            lambda a, b: _unblocked_xent(a, b, labels, scale, vocab_rows),
            (0, 1))(h, w)
    assert _rel(got, want) <= TOL
    assert _rel(gh, wh) <= TOL
    assert _rel(gw, ww) <= TOL
    # zero-weight tokens get no gradient
    assert float(jnp.max(jnp.abs(gh[wts == 0]))) == 0.0


def test_blocked_xent_scales_by_the_incoming_cotangent():
    d, V, n = 8, 40, 20
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    h = jax.random.normal(k[0], (n, d))
    w = jax.random.normal(k[1], (V, d))
    labels = jax.random.randint(k[2], (n,), 0, V)
    scale = jnp.full((n,), 1.0 / n)
    g1 = jax.grad(lambda a: blocked_xent(a, w, labels, scale, 8, True))(h)
    g3 = jax.grad(lambda a: 3.0 * blocked_xent(a, w, labels, scale, 8,
                                               True))(h)
    np.testing.assert_allclose(np.asarray(g3), 3.0 * np.asarray(g1),
                               rtol=1e-6)


def test_block_rule():
    """At most 256 MiB of fp32 logits a block, but at least 1024
    tokens; the fewest blocks of that size, equal and rounded up to 8."""
    assert head_block_tokens(8 * 2048, 151936) == 1024
    assert head_block_tokens(8 * 1792, 151936) == 1024
    assert head_block_tokens(48 * 512, 30522) == 2048
    assert head_block_tokens(48 * 448, 30522) == 2152
    assert head_block_tokens(37, 151936) == 40
    for V in (512, 30522, 151936, 1 << 20):
        for n in (1000, 4099, 1 << 20):
            t = head_block_tokens(n, V)
            most = max(1024, (1 << 28) // (4 * V) // 128 * 128)
            blocks = -(-n // t)
            assert t % 8 == 0 and blocks == -(-n // most)
            assert blocks * t - n < 8 * blocks


def _tiny(tie, **over):
    cfg = get_config("bert_base_paper").reduced(**dict(
        dict(num_layers=2, d_model=32, d_ff=64, vocab_size=97,
             dtype="float32", tie_embeddings=tie), **over))
    lm = build_model(cfg)
    return lm, lm.init(jax.random.PRNGKey(1))


def _unblocked_loss(lm, params, batch):
    """``LM.loss`` as it was before blocking: full logits, one-hot."""
    logits, aux = lm.forward(params, batch)
    w = batch["weights"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(batch["labels"], logits.shape[-1])
    nll = lse - jnp.einsum("bsv,bsv->bs", logits, onehot)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0) + aux


def _small_blocks(monkeypatch, t=24):
    """Blocks of ``t`` tokens, as a large vocabulary would give."""
    monkeypatch.setattr(lm_mod, "head_block_tokens",
                        lambda n, V: min(t, -(-n // 8) * 8))


def _assert_grads_match(g, want):
    for path, a in jax.tree_util.tree_flatten_with_path(g)[0]:
        b = want
        for key in path:
            b = b[getattr(key, "key", getattr(key, "idx", None))]
        assert _rel(a, b) <= TOL, jax.tree_util.keystr(path)


@pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("pad_rows", [0, 1], ids=["ragged", "zero_rows"])
def test_lm_loss_matches_the_unblocked_loss(monkeypatch, tie, pad_rows):
    """Through ``LM.loss``: 3 x 23 tokens in blocks of 24 (the last one
    short), ragged rows, a row of weight 0; loss and every parameter's
    gradient (the head's or the embedding's among them) to 1e-6."""
    _small_blocks(monkeypatch)
    lm, params = _tiny(tie)
    batch = _batch(3, 23, 97, seed=pad_rows, pad_rows=pad_rows)
    assert lm.head_blocks(batch) == 3
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: lm.loss(p, batch), has_aux=True)(params)
        want, wg = jax.value_and_grad(
            lambda p: _unblocked_loss(lm, p, batch))(params)
    assert _rel(loss, want) <= TOL
    _assert_grads_match(g, wg)
    assert ("lm_head" in g) != tie


def test_microbatch_path_matches_the_unblocked_loss(monkeypatch):
    _small_blocks(monkeypatch, 16)
    lm, params = _tiny(True)
    batch = _batch(4, 19, 97, seed=5)
    with jax.default_matmul_precision("highest"):
        loss, _, g = accumulated_grads(lm, params, batch, 2)
        want, wg = jax.value_and_grad(
            lambda p: _unblocked_loss(lm, p, batch))(params)
    assert _rel(loss, want) <= TOL
    _assert_grads_match(g, wg)


def _shape_batch(B, S):
    i32 = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return {"tokens": i32, "labels": i32,
            "weights": jax.ShapeDtypeStruct((B, S), jnp.float32),
            "lengths": jax.ShapeDtypeStruct((B,), jnp.int32)}


def test_compiled_loss_holds_no_full_logits():
    """At vocabulary 131072 and 8 x 1024 tokens (8 blocks of 1024) the
    compiled loss and gradient allot fewer temporary bytes than half the
    full fp32 logits, and no (B, S, V) or (B*S, V) array exists."""
    B, S, V = 8, 1024, 131072
    lm, _ = _tiny(True, vocab_size=V)
    assert head_block_tokens(B * S, V) == 1024
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    grad = jax.jit(jax.value_and_grad(lambda p, b: lm.loss(p, b)[0]))
    compiled = grad.lower(params, _shape_batch(B, S)).compile()
    full_logits = B * S * V * 4
    assert compiled.memory_analysis().temp_size_in_bytes < full_logits / 2
    hlo = compiled.as_text()
    assert not re.search(rf"\[({B},{S}|{B * S}),{V}\]", hlo)
    assert re.search(rf"\[1024,{V}\]", hlo)


@pytest.fixture(scope="module")
def large_vocab():
    cfg = get_config("bert_base_paper").reduced(
        num_layers=2, d_model=64, d_ff=128, vocab_size=16384,
        dtype="float32")
    lm = build_model(cfg)
    return lm, lm.init(jax.random.PRNGKey(2))


def test_planner_counts_the_head(large_vocab):
    """The collector's head term is positive and in the step's planned
    peak, which is at least the CPU-compiled step's argument and
    temporary bytes; the head is not a plan unit."""
    lm, params = large_vocab
    units = lm.num_plan_units()
    planner = MimosePlanner(lm, 1e12, quantum=16, warmup_samples=1)
    opt = AdamW(lr=1e-3)
    tr = Trainer(lm, planner, opt)
    batch = _batch(4, 256, 16384, seed=7)
    state = opt.init(params)
    mask, info = planner.plan(params, batch)
    head = planner.collector.head_bytes(batch)
    blocks = lm.head_blocks(batch)
    assert head > 0 and blocks == 1
    assert info.head_bytes == head
    assert len(mask) == units == lm.cfg.num_layers
    ma = tr._build_step(mask).lower(params, state, batch).compile() \
        .memory_analysis()
    copy = jax.tree_util.tree_map(jnp.copy, (params, state))
    tr.step(*copy, dict(batch))
    tr.drain()
    st = tr.history[-1]
    assert st.head_bytes == head and st.head_blocks == blocks
    assert st.planned_peak_bytes >= planner.fixed_bytes + head
    assert st.planned_peak_bytes >= (ma.argument_size_in_bytes
                                     + ma.temp_size_in_bytes)
    assert lm.num_plan_units() == units
    gauge = tr.telemetry.metrics.get("train_head_bytes")
    assert gauge is not None
