"""Continuous-batching serve driver (ROADMAP 1).

CPU-runnable example (reduced scale):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --reduced --num-requests 16 --rate-rps 8 --hbm-gb 0.5

Builds the model, generates (or loads, ``--trace``) a deterministic
open-loop trace, runs it through ``repro.train.engine.ServeEngine``
under the ``--hbm-gb`` budget, and prints the serve report — tokens/s,
TTFT and inter-token latency percentiles, the admission ledger
(admitted / deferred / rejected, predicted vs actual peak HBM), and the
compile audit proving decode stayed at O(#buckets) geometries.

The budget is input-aware end to end: the engine's PolyEstimator (the
paper's §4.3 estimator re-aimed at cache bytes) predicts the footprint
of each admit and each prefill chunk before allocating, so an
over-subscribed trace *defers* instead of OOMing; a request that can
never fit is rejected with a reason, never a crash.

``main`` returns the run's summary (``ServeResult.summary()`` plus each
request's generated tokens), so a caller in the same process reads
results instead of parsing stdout.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.data.pipeline import DISTRIBUTIONS
from repro.data.trace import TraceRequest, gen_trace
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.report import serve_report
from repro.models.lm import build_model
from repro.obs import build_telemetry, flush_telemetry
from repro.models.registry import get_config
from repro.train.engine import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--dataset", default="swag", choices=list(DISTRIBUTIONS))
    ap.add_argument("--hbm-gb", type=float, default=0.5,
                    help="serve HBM budget (params + caches + workspace)")
    ap.add_argument("--quantum", type=int, default=64,
                    help="cache bucket granularity (padded total length)")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="per-bucket batch-slot ceiling")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="largest prefill chunk (power of two)")
    ap.add_argument("--decode-steps", type=int, default=4,
                    help="decode iterations per scheduler loop")
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--rate-rps", type=float, default=8.0,
                    help="Poisson arrival rate; <=0 = burst at t=0")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--prompt-scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None,
                    help="JSON trace from tools/gen_trace.py "
                         "(overrides the generator knobs)")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink the model for CPU runs")
    ap.add_argument("--save", default=None,
                    help="write the run summary as JSON")
    # unified telemetry (repro.obs) — same flags as launch/train.py
    ap.add_argument("--metrics", default=None,
                    help="write the final metrics snapshot here at exit "
                         "(.json = JSON doc, else Prometheus text)")
    ap.add_argument("--events-out", default=None,
                    help="JSONL event log: admit/defer/reject decisions "
                         "with predicted bytes, pool grows, completions")
    ap.add_argument("--trace-out", default=None,
                    help="Chrome trace_event JSON (Perfetto): per-request "
                         "queue-wait, prefill-chunk and decode-batch spans")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=2, d_model=128, d_ff=256,
                          vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name} (family={cfg.family}, "
          f"{cfg.num_layers}L d={cfg.d_model}) under "
          f"{args.hbm_gb:.3f} GB, quantum={args.quantum}, "
          f"max_slots={args.max_slots}")

    if args.trace:
        trace = [TraceRequest.from_json(r)
                 for r in json.load(open(args.trace))]
    else:
        trace = gen_trace(num_requests=args.num_requests,
                          vocab_size=cfg.vocab_size, dataset=args.dataset,
                          rate_rps=args.rate_rps,
                          max_new_tokens=args.max_new_tokens,
                          prompt_scale=args.prompt_scale, seed=args.seed)
    lens = [len(r.prompt) for r in trace]
    print(f"trace: {len(trace)} requests, prompt lens "
          f"{min(lens)}..{max(lens)}, "
          f"last arrival {trace[-1].arrival_s:.2f}s")

    telemetry = build_telemetry(metrics_path=args.metrics,
                                events_path=args.events_out,
                                trace_path=args.trace_out)
    engine = ServeEngine(lm, params, hbm_bytes=args.hbm_gb * 1e9,
                         quantum=args.quantum, max_slots=args.max_slots,
                         prefill_chunk=args.prefill_chunk,
                         decode_steps=args.decode_steps,
                         telemetry=telemetry)
    t0 = time.time()
    result = engine.run(trace)
    print(f"served in {time.time() - t0:.2f}s\n")
    print(serve_report(engine, result))
    summary = result.summary()
    if args.save:
        with open(args.save, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"\nsummary written to {args.save}")
    for kind, path in flush_telemetry(telemetry).items():
        print(f"{kind} written to {path}")
    return dict(summary, outputs=result.outputs)


if __name__ == "__main__":
    main()
