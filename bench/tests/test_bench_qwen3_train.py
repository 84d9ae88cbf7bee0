"""The ``train-qwen3-squad`` cell end to end at a CPU size: a tiny copy
of ``qwen3_1p7b_train`` (bfloat16, scan planning in 2 chunks, the
reference's int8 path as control) under a tiny ``squad4x``, through
``run_cell``; the sound run is correct, the control and the half-batch
fault are not."""
from __future__ import annotations

import json
import types

import pytest

from bench import run
from bench.tests.conftest import BENCH, TINY_MODEL

WORKLOAD = "train-qwen3-squad"


@pytest.fixture(scope="module")
def qwen_bench(tmp_path_factory):
    d = tmp_path_factory.mktemp("qwen_bench")
    for sub in ("configs", "traffic"):
        (d / sub).mkdir()
    (d / "metrics").symlink_to(BENCH / "metrics")
    (d / "reference").symlink_to(BENCH / "reference")
    cfg = json.loads((BENCH / "configs/qwen3_1p7b_train.json").read_text())
    assert cfg["model"]["dtype"] == "bfloat16"
    assert cfg["model"]["remat_mode"] == "scan"
    cfg["model"].update(TINY_MODEL, num_kv_heads=2, scan_chunks=2)
    cfg["train"].update(batch_size=4, quantum=16, budget_bytes=1e18)
    # limits set from the readings at this size, as the cell's are at
    # its own (PERF.md).  On five seeds the sound run read loss_gap
    # 1.0e-4 to 6.6e-4, grad_gap 1.5e-3 to 2.0e-3 and change_gap 0.65
    # to 0.66 (bf16 storage drops sub-ulp updates); the int8 reference
    # read grad_gap 4.4e-3 to 7.2e-3 and less than the sound run on the
    # others; the half batch read grad_gap 0.077 to 0.13
    cfg["limits"] = {"loss_gap": 1e-3, "grad_gap": 3e-3,
                     "change_gap": 0.8}
    (d / "configs/qwen3_1p7b_train.json").write_text(json.dumps(cfg))
    t = json.loads((BENCH / "traffic/squad4x.json").read_text())
    t.update(pool_batches=4, lengths=dict(t["lengths"], mean=40, std=10,
                                          lo=18, hi=64))
    (d / "traffic/squad4x.json").write_text(json.dumps(t))
    return d


def _half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(params, state, batch):
        half = len(batch["tokens"]) // 2
        return step(params, state, {k: v[:half] for k, v in batch.items()})
    return f


def _run(bench_dir, **kw):
    args = types.SimpleNamespace(workload=WORKLOAD, seed=2**31 + 1320,
                                 seconds=0.5, trace=0)
    return run.run_cell(args, require_tpu=False, bench_dir=bench_dir, **kw)


def test_sound_run_is_correct(qwen_bench):
    res = _run(qwen_bench)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert sum(res["_compiles_in_window"].values()) == 0


@pytest.mark.parametrize("kw", [{"control": True},
                                {"fault": _half_batch}],
                         ids=["int8_reference_control", "half_batch"])
def test_control_and_fault_are_not_correct(qwen_bench, kw):
    res = _run(qwen_bench, **kw)
    assert not res["correct"], res["checks"]
