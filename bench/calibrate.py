"""Read the numbers that set a cell's correctness limits, on the chip.

    python bench/calibrate.py --workload <name> --seeds 12 [--seconds S]

For each of ``--seeds`` fresh seeds, in one process, at the cell's own
sizes, prints one JSON line of readings of the compared numbers:

* training cells -- ``program``: the program's checked steps against the
  fp32 reference (the sound runs: the lower reading); ``control``: what
  the configuration's ``"control"`` names, the same steps on the
  program's own path in that dtype, or the reference at that precision
  in the program's place; ``half_batch``: the reference over half of
  each batch, the mean over the rest.  A step that returns its state
  unchanged reads 1 on ``change_gap`` by its definition and needs no
  run.
* serving cells -- ``program``: the widest gap of the served tokens of a
  short window at the cell's load (``--seconds``, the mix's longest
  requests finish in it); ``control``: the gap of the token that the
  reference at the control's precision puts first at the same
  positions; ``altered``: the same samples with one served token per
  request replaced by its successor.

The benchmark's own runs never run this; its readings and the limits set
from them are in ``PERF.md``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import types

from run import ROOT, load_spec, resolve  # noqa: E402  (bench/ on path)


def train_readings(cfg, ref, traffic, seeds, devices):
    from bench.lib import check, reference, train_cell
    from bench.traffic import gen
    m, t = cfg["model"], cfg["train"]
    B, q = int(t["batch_size"]), int(t["quantum"])
    budget = float(devices[0].memory_stats()["bytes_limit"])
    ctrl = reference.control(cfg)
    dtypes = {"program": m["dtype"]}
    if ctrl["on"] == "program":
        dtypes["control"] = ctrl["dtype"]
    steppers = {}
    for name, dtype in dtypes.items():
        c = dict(cfg, model=dict(m, dtype=dtype))
        st = train_cell.ProgramStepper(c, ref, budget)
        warm_params = ref.make_weights(seeds[0], c["model"])
        st.warm(warm_params, st.init_state(warm_params),
                gen.train_buckets(traffic, B, q), B)
        steppers[name] = (st, c["model"])
        del warm_params

    def checked(name, seed):
        st, mm = steppers[name]
        params = ref.make_weights(seed, mm)
        feed = gen.train_feed(traffic, batch_size=B,
                              vocab_size=m["vocab_size"], quantum=q,
                              seed=seed)
        params, state, batches, readings = train_cell.checked_steps(
            st, st.step, params, st.init_state(params), feed, seed, mm)
        del params, state
        gc.collect()
        return batches, readings

    for seed in seeds:
        t0 = time.perf_counter()
        batches, prog = checked("program", seed)
        remat = steppers["program"][0].last_stats()["remat_units"]
        if ctrl["on"] == "program":
            _, low = checked("control", seed)
        ref_r = train_cell.reference_readings(ref, cfg, seed, batches)
        if ctrl["on"] == "reference":
            low = train_cell.reference_readings(
                ref, cfg, seed, batches, precision=ctrl["precision"])
        half = train_cell.reference_readings(ref, cfg, seed, batches,
                                             keep_rows=B // 2)
        out = {"seed": seed, "buckets": [int(b["tokens"].shape[1])
                                         for b in batches],
               "remat_units_last": remat,
               "s": round(time.perf_counter() - t0, 1)}
        for name, r in (("program", prog), ("control", low),
                        ("half_batch", half)):
            out[name] = check.train_numbers(r, ref_r)
        print(json.dumps(out), flush=True)


def serve_readings(cfg, ref, traffic, seeds, devices, seconds):
    import numpy as np
    from bench.lib import reference, serve_cell
    e = cfg["serve"]
    ctrl = reference.control(cfg)
    hbm = float(devices[0].memory_stats()["bytes_limit"])
    lm = serve_cell._build(cfg)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        cell = types.SimpleNamespace(cfg=cfg, traffic=traffic, seed=seed,
                                     seconds=seconds)
        reqs = serve_cell._requests(cell)
        params = ref.make_weights(seed, cfg["model"])
        if i == 0:
            serve_cell.warm(lm, params, cfg, hbm, sorted(
                {serve_cell._bucket(len(r.prompt), r.max_new_tokens,
                                    e["quantum"]) for r in reqs}))
        engine = serve_cell._engine(lm, params, cfg, hbm)
        engine.run(reqs)
        samples = serve_cell.check_samples(engine.done, seed)
        del engine, params
        gc.collect()
        pad = serve_cell._pad_len(traffic, e["quantum"])
        prog = serve_cell.reference_gaps(ref, cfg, seed, samples,
                                         pad_to=pad)
        ctrl_gaps = serve_cell.reference_gaps(ref, cfg, seed, samples,
                                              pad_to=pad,
                                              control=ctrl["precision"])
        rng = np.random.default_rng([seed, 6])
        altered = []
        for prompt, toks in samples:
            j = int(rng.integers(len(toks)))
            toks = list(toks)
            toks[j] = (toks[j] + 1) % cfg["model"]["vocab_size"]
            altered.append((prompt, toks))
        alt = serve_cell.reference_gaps(ref, cfg, seed, altered, pad_to=pad)
        widest = lambda gs: float(max(g.max() for g in gs))
        print(json.dumps({
            "seed": seed, "requests": len(samples),
            "tokens": int(sum(len(g) for g in prog)),
            "program": {"token_gap": widest(prog)},
            "control": {"token_gap": widest(ctrl_gaps)},
            "altered": {"token_gap": widest(alt)},
            "s": round(time.perf_counter() - t0, 1)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    w, cfg, traffic, _, _ = resolve(load_spec(), args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from bench.lib import device as dev, reference
    dev.enable_compile_cache()
    devices = dev.require_chips(int(w["chips"]))
    ref = reference.load(cfg)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if cfg["kind"] == "train":
        train_readings(cfg, ref, traffic, seeds, devices)
    else:
        serve_readings(cfg, ref, traffic, seeds, devices, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
