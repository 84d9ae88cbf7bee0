"""The trainer reads each step's loss one step late.

``Trainer.step`` dispatches step n+1 before it waits on the loss of step
n, so the chip has work queued while the host waits and does its
bookkeeping.  The same executables run on the same inputs, so losses and
parameters are bitwise those of a loop that reads every loss at once.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MimosePlanner
from repro.models.lm import build_model
from repro.models.registry import get_config
from repro.obs import TRACK_STEP, SpanTracer, Telemetry
from repro.optim.adamw import AdamW
from repro.train.resilience import OOMWatchdog
from repro.train.trainer import DeferredStepError, Trainer

B = 4
QUANTUM = 32
# between the fixed bytes (8.4 MB) and fixed plus every activation
# (20.5 MB at S=64) of the model below: the planner remats 2 of 4
# units at the 64 bucket and 1 at the 32 bucket
BUDGET = 14.5e6


@pytest.fixture(scope="module")
def small():
    cfg = get_config("bert_base_paper").reduced(
        num_layers=4, d_model=128, d_ff=256, vocab_size=512)
    lm = build_model(cfg)
    return lm, lm.init(jax.random.PRNGKey(0))


def _batches(n, seed=0, vocab=512):
    """Ragged host batches in the 32 and 64 buckets, with weights and
    lengths, as the benchmark's feed makes them."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        S = (40, 64, 21, 56, 30, 64)[i % 6]
        lengths = rng.integers(S // 2, S + 1, B).astype(np.int32)
        w = (np.arange(S)[None, :] < lengths[:, None]).astype(np.float32)
        tokens = rng.integers(1, vocab, (B, S)).astype(np.int32)
        out.append({"tokens": tokens * w.astype(np.int32),
                    "labels": np.roll(tokens, -1, 1) * w.astype(np.int32),
                    "weights": w, "lengths": lengths})
    return out


def _trainer(lm, params, telemetry=None, watchdog=None):
    tr = Trainer(lm, MimosePlanner(lm, BUDGET, quantum=QUANTUM,
                                   warmup_samples=1),
                 AdamW(lr=1e-3), telemetry=telemetry, watchdog=watchdog)
    state = tr.optimizer.init(params)
    assert tr.prewarm(params, state, [32, 64], B) == 2
    return tr, state


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _plain_loop(tr, params, state, batches):
    """The trainer's own compiled steps, its loss read after each."""
    losses = []
    for raw in batches:
        batch = tr._prepare(raw)
        mask, info = tr.planner.plan(params, batch)
        fn = tr._step_cache.get(tr._step_key(mask, batch,
                                             max(info.plan.microbatch, 1)))
        assert fn is not None
        params, state, loss, _ = fn(params, state, batch)
        losses.append(float(loss))
    return params, losses


def _deferred_reads(tr) -> float:
    return tr.telemetry.metrics.get("train_loss_reads_deferred").value()


def test_deferred_loss_is_bitwise_the_plain_loop(small):
    lm, params0 = small
    batches = _batches(6)
    tr, state = _trainer(lm, params0)
    ref_params, ref_losses = _plain_loop(tr, _copy(params0), _copy(state),
                                         batches)

    params = _copy(params0)
    returned = []
    for i, raw in enumerate(batches):
        params, state, loss = tr.step(params, state, raw)
        returned.append(loss)
        st = tr.history[-1]
        prepared = tr._prepare(raw)
        plan = tr.planner.cache.get(tr.planner.plan_key(prepared))
        # booked at dispatch, with no read of the step's outputs
        assert st.tokens == int(raw["lengths"].sum())
        assert st.padded_tokens == int(np.prod(prepared["tokens"].shape))
        assert st.remat_units == plan.n_remat
        assert not st.compile
        assert _deferred_reads(tr) == i
    assert any(s.remat_units for s in tr.history)
    assert len(tr.history) == 6 and _deferred_reads(tr) == 5

    # the newest loss is read when first read; that read is not deferred
    assert [s.loss for s in tr.history] == ref_losses
    assert _deferred_reads(tr) == 5
    assert [float(x) for x in returned] == ref_losses
    assert all(s.step_time_s > 0 for s in tr.history)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref_params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert tr.summary()["final_loss"] == ref_losses[-1]


def test_dispatch_of_the_next_step_precedes_the_read_of_the_loss(small):
    lm, params = small
    tracer = SpanTracer()
    tr, state = _trainer(lm, _copy(params), Telemetry(tracer=tracer))
    params = _copy(params)
    for raw in _batches(5, seed=1):
        params, state, _ = tr.step(params, state, raw)
    xs = [e for e in tracer.events()
          if e["ph"] == "X" and e["tid"] == TRACK_STEP]
    steps = [e for e in xs if e["name"] == "step"]
    assert len(steps) == 5

    def inside(e, parent):
        return parent["ts"] <= e["ts"] <= parent["ts"] + parent["dur"]

    for n, st in enumerate(steps):
        inner = [e for e in xs if e is not st and inside(e, st)]
        syncs = [e for e in inner if e["name"] == "sync"]
        if n == 0:
            assert not syncs                 # nothing in flight yet
            continue
        (dispatch,) = [e for e in inner if e["name"] == "dispatch"]
        (sync,) = syncs
        assert dispatch["ts"] + dispatch["dur"] <= sync["ts"]
        assert sync["args"] == {"step": n - 1}


class _Watched:
    """A step's loss and metrics, counting the host's reads; ``fail``
    makes the loss read raise as a failure on the device would."""

    def __init__(self, loss, metrics, fail=False):
        self.loss, self.fail, self.reads = loss, fail, 0
        self.metrics = _CountingDict(metrics, self)

    def __float__(self):
        self.reads += 1
        if self.fail:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory while "
                               "running the step")
        return float(self.loss)


class _CountingDict(dict):
    def __init__(self, d, owner):
        super().__init__(d)
        self.owner = owner

    def __getitem__(self, k):
        self.owner.reads += 1
        return super().__getitem__(k)


def _watch(tr, fail_at=None):
    """Wrap the trainer's steps so each returns a ``_Watched`` loss."""
    watched = []
    get = tr._get_step_fn

    def get_step_fn(*a, **kw):
        fn, is_new = get(*a, **kw)

        def step(p, s, b):
            p, s, loss, metrics = fn(p, s, b)
            w = _Watched(loss, metrics, fail=len(watched) == fail_at)
            watched.append(w)
            return p, s, w, w.metrics
        return step, is_new

    tr._get_step_fn = get_step_fn
    return watched


def test_a_warm_step_reads_only_the_previous_loss(small):
    lm, params = small
    tr, state = _trainer(lm, _copy(params))
    watched = _watch(tr)
    params = _copy(params)
    for n, raw in enumerate(_batches(4, seed=2)):
        params, state, _ = tr.step(params, state, raw)
        assert watched[n].reads == 0
        if n:
            assert watched[n - 1].reads == 1
    tr.drain()
    assert [w.reads for w in watched] == [1, 1, 1, 1]


@pytest.mark.parametrize("read_by", ["next_step", "drain"])
def test_a_failure_seen_at_the_deferred_read_names_its_step(small, read_by):
    lm, params = small
    wd = OOMWatchdog(max_retries=3)
    tr, state = _trainer(lm, _copy(params), watchdog=wd)
    _watch(tr, fail_at=2)
    params = _copy(params)
    batches = _batches(4, seed=3)
    for raw in batches[:3]:
        params, state, _ = tr.step(params, state, raw)
    with pytest.raises(DeferredStepError, match="step 2 failed"):
        if read_by == "next_step":
            tr.step(params, state, batches[3])
        else:
            tr.drain()
    # not retried, not booked as an OOM of the step that read it
    assert wd.stats["oom_events"] == 0
    assert math.isnan(tr.history[2].loss)
    assert len(tr.history) == 3


def test_a_compiling_step_reads_the_step_in_flight_first(small):
    lm, params = small
    tracer = SpanTracer()
    tr, state = _trainer(lm, _copy(params), Telemetry(tracer=tracer))
    params = _copy(params)
    wide = _batches(1)[0]
    wide = {k: (np.pad(v, ((0, 0), (0, 96 - v.shape[1])))
                if v.ndim == 2 else v) for k, v in wide.items()}
    params, state, _ = tr.step(params, state, _batches(1)[0])
    n0 = len(tracer)
    params, state, _ = tr.step(params, state, wide)     # 96: compiles
    assert tr.history[-1].compile
    names = [e["name"] for e in tracer.events()[n0:]
             if e["ph"] == "X" and e["tid"] == TRACK_STEP]
    assert names.index("sync") < names.index("dispatch")
    assert names.count("sync") == 1
    assert _deferred_reads(tr) == 0
    assert tr.history[0].step_time_s > 0
    tr.drain()
    assert tr.history[-1].step_time_s > 0
