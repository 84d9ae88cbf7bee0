"""A serving cell: ``repro.train.engine.ServeEngine`` on one chip.

Set-up makes the weights, warms every program the window's buckets can
use (each bucket's prefill chunks, pool tiers, inserts, evictions and
decode batches) by serving a few short requests per bucket through the
engine itself, then builds a fresh engine for the window.  The window is
one ``ServeEngine.run`` over an open-loop trace whose requests are all
due within ``seconds`` of the engine's clock; the run ends when the last
of them is served.  The engine's clock skips idle stretches, which costs
no request any latency: the window is measured on that clock.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import reference, spans
from bench.traffic import gen

TRACE_SECONDS = 3.0
CHECK_REQUESTS = 8
# prompt remainders (mod the largest chunk) whose chunk sequences cover
# every chunk size both inside and at the end of a prefill
_WARM_REMAINDERS = ((31,), (2, 4), (8, 16, 0, 1))


def _build(cfg):
    from repro.models.lm import build_model
    from repro.models.registry import get_config
    return build_model(dataclasses.replace(get_config(cfg["arch"]),
                                           **cfg["model"]))


def _engine(lm, params, cfg, hbm_bytes, telemetry=None):
    from repro.train.engine import ServeEngine
    e = cfg["serve"]
    return ServeEngine(lm, params, hbm_bytes=hbm_bytes, quantum=e["quantum"],
                       max_slots=e["max_slots"],
                       prefill_chunk=e["prefill_chunk"],
                       decode_steps=e["decode_steps"], telemetry=telemetry)


def _bucket(prompt_len, new, quantum):
    return gen.bucket(prompt_len + new, quantum)


def warm(lm, params, cfg, hbm_bytes, buckets) -> None:
    """Serve, per bucket, one request alone, two together and four
    together: every pool tier decodes, grows, inserts and evicts, and the
    prompts' lengths make every prefill chunk size occur."""
    from repro.data.trace import TraceRequest
    e = cfg["serve"]
    chunk, q = e["prefill_chunk"], e["quantum"]
    rid = 0
    for b in buckets:
        for group in _WARM_REMAINDERS:
            reqs = []
            for r in group:
                # the shortest prompt of this bucket that is r mod chunk
                lo = max(b - q - 1, 1)
                n = lo + (r - lo) % chunk
                if _bucket(n, 2, q) != b:
                    n = b - 2
                reqs.append(TraceRequest(rid=rid, arrival_s=0.0,
                                         prompt=np.ones((n,), np.int32),
                                         max_new_tokens=2))
                rid += 1
            _engine(lm, params, cfg, hbm_bytes).run(reqs)


def _requests(cell):
    from repro.data.trace import TraceRequest
    return [TraceRequest(rid=i, arrival_s=a, prompt=p, max_new_tokens=n)
            for i, a, p, n in gen.serve_requests(
                cell.traffic, vocab_size=cell.cfg["model"]["vocab_size"],
                seed=cell.seed, seconds=cell.seconds)]


def reference_gaps(ref, cfg, seed, samples, *, pad_to=None, control=None):
    """Per sampled request, the gap of each served token's reference
    logit below the reference's best, from the configuration's reference
    module ``ref``.  ``control`` (a precision of that module, such as
    "int8") instead reads the gap of the token that the lower precision
    puts first at each of those positions."""
    m = cfg["model"]
    params = ref.make_weights(seed, m, program=False)
    pad = pad_to or max(len(p) + len(t) for p, t in samples)

    def fn(params, tokens, length, served):
        lg = ref.logits(params, tokens, length, m, "fp32")[0]
        best = jnp.max(lg, axis=-1)
        if control is None:
            pick = served
        else:
            lo = ref.logits(params, tokens, length, m, control)[0]
            pick = jnp.argmax(lo, axis=-1)
        return best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]

    fn = jax.jit(fn)
    gaps = []
    for prompt, toks in samples:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        L = len(seq)
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :L] = seq
        served = np.zeros((pad,), np.int32)
        served[len(prompt) - 1:L] = toks
        g = np.asarray(fn(params, jnp.asarray(tokens),
                          jnp.asarray([L], jnp.int32), jnp.asarray(served)))
        gaps.append(g[len(prompt) - 1:L])
    return gaps


def check_samples(done, seed):
    """A seeded sample of finished requests, with the longest in it."""
    done = sorted(done, key=lambda lv: lv.req.rid)
    if not done:
        return []
    longest = max(done, key=lambda lv: (len(lv.tokens), -lv.req.rid))
    rng = np.random.default_rng([int(seed) % 2**63, 5])
    pick = rng.choice(len(done), size=min(CHECK_REQUESTS, len(done)),
                      replace=False)
    chosen = {done[i].req.rid: done[i] for i in pick}
    chosen[longest.req.rid] = longest
    return [(lv.req.prompt, list(lv.tokens)) for _, lv in
            sorted(chosen.items())]


def run(cell) -> dict:
    cfg, m = cell.cfg, cell.cfg["model"]
    ref, ctrl = cell.ref, reference.control(cell.cfg)
    device = cell.devices[0]
    hbm = float(device.memory_stats()["bytes_limit"]) \
        if device.platform == "tpu" else float(cfg["serve"]["hbm_bytes"])
    q = cfg["serve"]["quantum"]
    lm = _build(cfg)
    params = ref.make_weights(cell.seed, m)
    want = jax.tree_util.tree_structure(
        jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
    if jax.tree_util.tree_structure(params) != want:
        raise RuntimeError("weights layout is not the program's")
    reqs = _requests(cell)
    buckets = sorted({_bucket(len(r.prompt), r.max_new_tokens, q)
                      for r in reqs})
    warm(lm, params, cfg, hbm, buckets)

    telemetry = tracer = None
    state = {"profiling": False, "done": False, "log_dir": None}
    if cell.trace:
        from repro.obs import Telemetry
        tracer = spans.tracer_class()()
        telemetry = Telemetry(tracer=tracer)
    engine = _engine(lm, params, cfg, hbm, telemetry)
    if cell.fault is not None:
        cell.fault(engine)
    trace_from = max(cell.seconds - TRACE_SECONDS, 0.0)

    def start():
        state["log_dir"] = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(state["log_dir"],
                                 profiler_options=spans.profile_options())
        tracer.annotate = True
        state["span"] = spans.harness_span("traced_window")
        state["span"].__enter__()
        state["t"] = [time.perf_counter(), None]
        state["profiling"] = True

    def stop():
        state["t"][1] = time.perf_counter()
        state["span"].__exit__(None, None, None)
        tracer.annotate = False
        jax.profiler.stop_trace()
        state["profiling"], state["done"] = False, True

    def on_span(name):
        now = engine._now()
        if not state["profiling"] and not state["done"] \
                and now >= trace_from:
            start()
        elif state["profiling"] and now >= cell.seconds:
            stop()

    if tracer is not None:
        tracer.on_span = on_span
    cell.counter.active = True
    t0 = time.perf_counter()
    setup_s = t0 - cell.clock0
    engine.run(reqs)
    wall = time.perf_counter() - t0
    cell.counter.active = False
    if state["profiling"]:
        stop()
    peak = cell.memory_peak()

    done = list(engine.done)
    finished = {lv.req.rid for lv in done}
    records = [{"rid": lv.req.rid, "arrival_s": lv.arrival_s,
                "admit_s": lv.t_admit, "prompt": len(lv.req.prompt),
                "token_times": list(lv.token_times)} for lv in done]
    samples = check_samples(done, cell.seed)
    decode_spans = (spans.span_durations(tracer, "decode_batch")
                    if tracer is not None else [])
    del engine, params, lm, done
    gc.collect()
    trace_summary = None
    if state["log_dir"] is not None:
        from bench.metrics import devtrace
        trace_summary = devtrace.reduce_trace(state["log_dir"])
        shutil.rmtree(state["log_dir"], ignore_errors=True)
        if trace_summary is not None:
            trace_summary["host_window_s"] = state["t"][1] - state["t"][0]

    gaps = reference_gaps(
        ref, cell.cfg, cell.seed, samples, pad_to=_pad_len(cell.traffic, q),
        control=ctrl["precision"] if cell.control else None)
    numbers = {"token_gap": float(max((g.max() for g in gaps if len(g)),
                                      default=float("inf"))),
               "_where": {"requests": len(samples),
                          "tokens": int(sum(len(g) for g in gaps))}}

    S = float(cell.seconds)
    missing = wall + 1.0
    ttft = [r["token_times"][0] - r["arrival_s"] if r["token_times"]
            else missing for r in records]
    ttft += [missing] * (len(reqs) - len(records))
    itl = [b - a for r in records
           for a, b in zip(r["token_times"], r["token_times"][1:])]
    in_window = sum(1 for r in records for t in r["token_times"] if t <= S)
    return {
        "attempted": len(reqs), "failed": len(reqs) - len(finished),
        "end_to_end": {
            "serve_ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "serve_itl_p95_ms": float(np.percentile(itl, 95)) * 1e3
            if itl else missing * 1e3,
            "serve_tokens_per_s": in_window / S,
            "setup_s": setup_s},
        "run": {"kind": "serve", "model": m, "window_s": S, "wall_s": wall,
                "requests": records, "decode_spans": decode_spans,
                "memory_peak_bytes": peak, "trace": trace_summary},
        "numbers": numbers, "memory_peak_bytes": peak,
    }


def _pad_len(traffic, quantum):
    return gen.bucket(int(traffic["prompt"]["hi"])
                      + int(traffic["new_tokens"]["hi"]), quantum)
