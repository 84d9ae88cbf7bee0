"""The serving cell's harness, as a later PR adds the cell: a run with a
served token altered where it is produced reads ``correct`` false, the
sound run reads true, and the int8 control's gap lies above the limit."""
from __future__ import annotations

import json
import types

import numpy as np

from bench import run
from bench.tests.conftest import BENCH, with_serve_cell

SPEC = with_serve_cell(run.load_spec())


def _args():
    return types.SimpleNamespace(workload="serve-qwen3-steady",
                                 seed=2**31 + 29, seconds=1.0, trace=0)


def _alter_tokens(engine):
    """Every decoded token is replaced by its successor id."""
    inner = engine._decode_jit
    vocab = engine.lm.cfg.vocab_size

    def f(*args):
        nxt, cache = inner(*args)
        return (nxt + 1) % vocab, cache
    engine._decode_jit = f


def test_sound_run_is_correct(tiny_bench):
    res = run.run_cell(_args(), require_tpu=False, bench_dir=tiny_bench,
                       spec=SPEC)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert sum(res["_compiles_in_window"].values()) == 0


def test_altered_token_is_not_correct(tiny_bench):
    res = run.run_cell(_args(), require_tpu=False, bench_dir=tiny_bench,
                       spec=SPEC, fault=_alter_tokens)
    assert not res["correct"], res["checks"]


def test_int8_control_reads_above_the_limit():
    """At the published widths, two layers and a 16384-row vocabulary."""
    from bench.lib import reference
    from bench.lib.serve_cell import reference_gaps
    cfg = json.loads((BENCH / "configs/qwen3_1p7b.json").read_text())
    cfg["model"].update(num_layers=2, vocab_size=16384)
    rng = np.random.default_rng(4)
    samples = [(rng.integers(1, 16384, n).astype(np.int32),
                list(rng.integers(1, 16384, 40))) for n in (20, 31, 9)]
    gaps = reference_gaps(reference.load(cfg), cfg, 4, samples,
                          control=reference.control(cfg)["precision"])
    assert max(float(g.max()) for g in gaps) > cfg["limits"]["token_gap"]
