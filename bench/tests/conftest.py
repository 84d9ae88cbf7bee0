"""Shared fixtures of the benchmark's tests: tiny copies of the cells'
configurations, so a whole run fits a CPU test."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "head_dim": 16, "d_ff": 128, "vocab_size": 256}


# the serving cell as a later PR would add it to BENCHMARK.json
SERVE_CELL = {
    "workload": {"name": "serve-qwen3-steady", "config": "qwen3_1p7b",
                 "traffic": "steady", "chips": 1,
                 "why": "open-loop Poisson below the knee"},
    "end_to_end": [
        {"name": "serve_ttft_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["serve-qwen3-steady"]},
        {"name": "serve_itl_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["serve-qwen3-steady"]},
        {"name": "serve_tokens_per_s", "unit": "tokens/s",
         "better": "higher", "bound": 0.25, "source": "host_clock",
         "workloads": ["serve-qwen3-steady"]}],
    "per_layer": [
        {"name": n, "unit": u, "better": b, "source": s, "layer": lay,
         "moves": mv, "workloads": ["serve-qwen3-steady"]}
        for n, u, b, s, lay, mv in (
            ("queue_wait_p95_ms.serve", "ms", "lower", "program_span",
             "engine", "serve_ttft_p95_ms"),
            ("decode_step_ms.serve", "ms", "lower", "program_span",
             "engine", "serve_itl_p95_ms"),
            ("idle_share.serve", "%", "lower", "device_trace", "device",
             "serve_itl_p95_ms"),
            ("mfu.serve", "%", "higher", "host_clock", "device",
             "serve_tokens_per_s"))]}


def with_serve_cell(spec: dict) -> dict:
    out = json.loads(json.dumps(spec))
    out["workloads"].append(SERVE_CELL["workload"])
    out["end_to_end"].extend(SERVE_CELL["end_to_end"])
    out["per_layer"].extend(SERVE_CELL["per_layer"])
    return out


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory) -> pathlib.Path:
    """A benchmark directory whose configurations keep every setting of
    the real ones except the sizes, and whose traffic is CPU-sized."""
    d = tmp_path_factory.mktemp("tiny_bench")
    (d / "configs").mkdir()
    (d / "traffic").mkdir()
    (d / "metrics").symlink_to(BENCH / "metrics")
    (d / "reference").symlink_to(BENCH / "reference")
    b = json.loads((BENCH / "configs/bert_base_paper.json").read_text())
    b["model"].update(TINY_MODEL, num_kv_heads=4)
    b["train"].update(batch_size=4, budget_bytes=1e18)
    (d / "configs/bert_base_paper.json").write_text(json.dumps(b))
    q = json.loads((BENCH / "configs/qwen3_1p7b.json").read_text())
    q["model"].update(TINY_MODEL, num_kv_heads=2, scan_chunks=2)
    q["serve"].update(quantum=16, max_slots=4, prefill_chunk=8,
                      decode_steps=2, hbm_bytes=1e10)
    (d / "configs/qwen3_1p7b.json").write_text(json.dumps(q))
    for name, lo, hi in (("squad", 20, 64), ("swag", 8, 32)):
        (d / f"traffic/{name}.json").write_text(json.dumps(
            {"kind": "train", "pool_batches": 4, "pool_seed": 1,
             "lengths": {"dist": "normal", "mean": (lo + hi) / 2,
                         "std": (hi - lo) / 4, "lo": lo, "hi": hi}}))
    (d / "traffic/steady.json").write_text(json.dumps(
        {"kind": "serve", "rate_rps": 6.0, "pool_seed": 2,
         "prompt": {"dist": "normal", "mean": 20, "std": 5, "lo": 8,
                    "hi": 32},
         "new_tokens": {"dist": "uniform", "lo": 2, "hi": 6}}))
    return d
