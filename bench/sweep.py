"""Find the knee of a serving cell: the highest rate the engine sustains.

    python bench/sweep.py --workload <name> --rates 2,3,4,6 [--seconds S]

In one process on the chip, serves the cell's traffic mix at each
offered rate for ``--seconds`` of arrivals and prints one JSON line per
rate: requests, failures, time to first token (median and 95th
percentile, and the 95th of the first and last third of arrivals),
inter-token latency p95, tokens completed per second of the window, and
how long the engine ran on past the window's close.  A rate is sustained
when the last third's tail is no worse than the first third's and the
run drains soon after the close; the cell's traffic file then offers 4/5
of the highest sustained rate.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import numpy as np

from run import ROOT, load_spec, resolve  # noqa: E402  (bench/ on path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=3_100_000_001)
    args = ap.parse_args(argv)
    w, cfg, traffic, _, _ = resolve(load_spec(), args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from bench.lib import device as dev, reference, serve_cell
    dev.enable_compile_cache()
    devices = dev.require_chips(int(w["chips"]))
    hbm = float(devices[0].memory_stats()["bytes_limit"])
    lm = serve_cell._build(cfg)
    params = reference.load(cfg).make_weights(args.seed, cfg["model"])
    q = cfg["serve"]["quantum"]
    warmed = set()
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(traffic, rate_rps=rate)
        cell = types.SimpleNamespace(cfg=cfg, traffic=tr, seed=args.seed,
                                     seconds=args.seconds)
        reqs = serve_cell._requests(cell)
        buckets = {serve_cell._bucket(len(r.prompt), r.max_new_tokens, q)
                   for r in reqs} - warmed
        serve_cell.warm(lm, params, cfg, hbm, sorted(buckets))
        warmed |= buckets
        engine = serve_cell._engine(lm, params, cfg, hbm)
        t0 = time.perf_counter()
        engine.run(reqs)
        wall = time.perf_counter() - t0
        done = sorted(engine.done, key=lambda lv: lv.arrival_s)
        ttft = np.array([lv.token_times[0] - lv.arrival_s for lv in done])
        third = max(len(done) // 3, 1)
        itl = [b - a for lv in done
               for a, b in zip(lv.token_times, lv.token_times[1:])]
        end = max(lv.token_times[-1] for lv in done)
        toks = sum(1 for lv in done for t in lv.token_times
                   if t <= args.seconds)
        print(json.dumps({
            "rate_rps": rate, "requests": len(reqs),
            "failed": len(reqs) - len(done), "wall_s": round(wall, 2),
            "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
            "ttft_p95_first_third_ms": float(
                np.percentile(ttft[:third], 95) * 1e3),
            "ttft_p95_last_third_ms": float(
                np.percentile(ttft[-third:], 95) * 1e3),
            "itl_p95_ms": float(np.percentile(itl, 95) * 1e3),
            "tokens_per_s": toks / args.seconds,
            "ran_past_close_s": end - args.seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
