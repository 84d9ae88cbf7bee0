"""A training cell: the Mimose planner and trainer on one chip.

Set-up builds one ``Trainer`` with its compiled steps and state, warms
every bucket the seeded feed can produce (plan and compile), then drives
that same trainer through the first ``CHECK_STEPS`` steps of the feed,
which the reference follows.  The window keeps calling the same
``Trainer.step`` on the same feed for ``seconds``; every step is taken
whole, so the window ends with the first step that ends past it.  The
weights, their layout and the reference come from the module the
configuration names (``bench/lib/reference.py``).
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import jax
import numpy as np

from bench.lib import check, reference, spans
from bench.traffic import gen

CHECK_STEPS = 3
TRACE_SECONDS = 3.0


class ProgramStepper:
    """The system under test: ``repro.train.trainer.Trainer``."""

    def __init__(self, cfg: dict, ref, budget_bytes: float,
                 telemetry=None):
        import dataclasses
        from repro.core import MimosePlanner
        from repro.models.lm import build_model
        from repro.models.registry import get_config
        from repro.optim.adamw import AdamW, cosine_schedule
        from repro.train.trainer import Trainer

        m, t = cfg["model"], cfg["train"]
        mc = dataclasses.replace(get_config(cfg["arch"]), **m)
        self.lm = build_model(mc)
        self.planner = MimosePlanner(
            self.lm, budget_bytes, quantum=t["quantum"],
            warmup_samples=t["warmup_samples"], cost_aware=t["cost_aware"],
            offload=t["offload"], max_microbatches=t["max_microbatches"])
        o = t["adamw"]
        self.b1 = o["b1"]
        self.opt = AdamW(lr=cosine_schedule(o["lr"], o["warmup"], o["total"]),
                         b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         clip_norm=o["clip_norm"])
        self.trainer = Trainer(self.lm, self.planner, self.opt,
                               telemetry=telemetry)
        self.model = m
        self.ref = ref

    def check_layout(self, params) -> None:
        want = jax.tree_util.tree_structure(
            jax.eval_shape(self.lm.init, jax.random.PRNGKey(0)))
        got = jax.tree_util.tree_structure(params)
        if want != got:
            raise RuntimeError(f"weights layout {got} is not the program's "
                               f"{want}")

    def init_state(self, params):
        return self.opt.init(params)

    def warm(self, params, state, seq_lens, batch_size) -> int:
        return self.trainer.prewarm(params, state, seq_lens, batch_size)

    def step(self, params, state, batch):
        return self.trainer.step(params, state, batch)

    def first_grad(self, state):
        """The clipped gradient of step 1 as AdamW holds it (m / (1-b1))."""
        return jax.tree_util.tree_map(lambda a: a / (1 - self.b1),
                                      self.ref.from_program(state.m,
                                                            self.model))

    def last_stats(self) -> dict:
        s = self.trainer.history[-1]
        return {"plan_time_s": s.plan_time_s, "remat_units": s.remat_units,
                "tokens": s.tokens, "padded_tokens": s.padded_tokens,
                "planned_peak_bytes": s.planned_peak_bytes,
                "step_time_s": s.step_time_s, "compile": s.compile}

    def units(self) -> int:
        return self.lm.num_plan_units()


def reference_readings(ref, cfg: dict, seed: int, batches: list, *,
                       keep_rows=None, precision: str = "fp32") -> dict:
    """Losses, first clipped gradient and change after the checked steps,
    of the plain reference from the seed's weights, as leaf norms.  The
    parameters, AdamW's state and the gradient summed over blocks stay on
    the device, which the module's block sizes count; the first weights
    are made again from the seed to read the change."""
    m, o = cfg["model"], cfg["train"]["adamw"]
    opt = ref.AdamW(lr=o["lr"], warmup=o["warmup"], total=o["total"],
                    b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])
    params = ref.make_weights(seed, m, dtype="float32", program=False)
    state = opt.init(params)
    losses, grad = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            loss, g = ref.loss_and_grad(params, b, m, keep_rows=keep_rows,
                                        precision=precision)
            if i == 0:
                grad = check.leaf_norms(opt.clip_grads(g))
            params, state = opt.update(g, state, params)
            del g
            losses.append(float(loss))
        del state
        change = check.diff_norms(params, ref.make_weights(
            seed, m, dtype="float32", program=False))
    return {"losses": losses, "grad": grad, "change": change}


def checked_steps(stepper, step, params, state, feed, seed, m):
    """The first ``CHECK_STEPS`` steps, through the window's own call and
    feed: the losses, the first gradient as the optimizer holds it and
    the change of every leaf after them, as leaf norms.  Returns the
    state to go on with, the batches (for the reference) and readings."""
    checked, prog = [], {"losses": []}
    for i in range(CHECK_STEPS):
        batch = next(feed)
        checked.append(batch)
        params, state, loss = step(params, state, batch)
        prog["losses"].append(float(loss))
        if i == 0:
            prog["grad"] = check.leaf_norms(stepper.first_grad(state))
    ref = stepper.ref
    p0 = ref.make_weights(seed, m)
    prog["change"] = check.diff_norms(ref.from_program(params, m),
                                      ref.from_program(p0, m))
    del p0
    return params, state, checked, prog


def run(cell) -> dict:
    cfg, m, t = cell.cfg, cell.cfg["model"], cell.cfg["train"]
    ref, ctrl = cell.ref, reference.control(cell.cfg)
    device = cell.devices[0]
    B, quantum = int(t["batch_size"]), int(t["quantum"])
    budget = float(device.memory_stats()["bytes_limit"]) \
        if device.platform == "tpu" else float(t.get("budget_bytes", 1e18))
    tracer = None
    telemetry = None
    if cell.trace:
        from repro.obs import Telemetry
        tracer = spans.tracer_class()()
        telemetry = Telemetry(tracer=tracer)
    if cell.control and ctrl["on"] == "program":
        cfg = dict(cfg, model=dict(m, dtype=ctrl["dtype"]))
        m = cfg["model"]
    stepper = ProgramStepper(cfg, ref, budget, telemetry)

    params = ref.make_weights(cell.seed, m)
    stepper.check_layout(params)
    state = stepper.init_state(params)
    buckets = gen.train_buckets(cell.traffic, B, quantum)
    stepper.warm(params, state, buckets, B)
    feed = gen.train_feed(cell.traffic, batch_size=B,
                          vocab_size=m["vocab_size"], quantum=quantum,
                          seed=cell.seed)
    step = stepper.step
    if cell.fault is not None:
        step = cell.fault(step)

    params, state, checked, prog = checked_steps(stepper, step, params,
                                                 state, feed, cell.seed, m)

    units = stepper.units()
    log_dir, traced = None, None
    records = []
    cell.counter.active = True
    t0 = time.perf_counter()
    setup_s = t0 - cell.clock0
    trace_from = max(cell.seconds - TRACE_SECONDS, 0.0)
    profiling, window_span = False, None
    while True:
        if cell.trace and not profiling and \
                time.perf_counter() - t0 >= trace_from:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(log_dir,
                                     profiler_options=spans.profile_options())
            tracer.annotate = True
            window_span = spans.harness_span("traced_window")
            window_span.__enter__()
            traced = [time.perf_counter(), None]
            profiling = True
        with spans.harness_span("feed"):
            batch = next(feed)
        with spans.harness_span("step"):
            params, state, loss = step(params, state, batch)
        st = stepper.last_stats()
        st.update(seq=int(np.shape(batch["tokens"])[1]),
                  lengths=np.asarray(batch["lengths"]).tolist(), loss=loss)
        records.append(st)
        now = time.perf_counter()
        if now - t0 >= cell.seconds:
            break
    t_end = now
    cell.counter.active = False
    if profiling:
        traced[1] = time.perf_counter()
        window_span.__exit__(None, None, None)
        tracer.annotate = False
        jax.profiler.stop_trace()
    peak = cell.memory_peak()

    # free the program's state before the reference runs
    del params, state, stepper, step
    gc.collect()
    trace_summary = None
    if log_dir is not None:
        from bench.metrics import devtrace
        trace_summary = devtrace.reduce_trace(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        if trace_summary is not None:
            trace_summary["host_window_s"] = traced[1] - traced[0]
    if cell.control and ctrl["on"] == "reference":
        prog = reference_readings(ref, cell.cfg, cell.seed, checked,
                                  precision=ctrl["precision"])
    numbers = check.train_numbers(
        prog, reference_readings(ref, cell.cfg, cell.seed, checked))

    window = t_end - t0
    eff = sum(r["tokens"] for r in records)
    good = [r for r in records if np.isfinite(r["loss"])]
    return {
        "attempted": len(records), "failed": len(records) - len(good),
        "end_to_end": {"train_tokens_per_s": eff / window,
                       "setup_s": setup_s},
        "run": {"kind": "train", "model": m, "window_s": window,
                "steps": records, "batch_size": B,
                "units": units, "memory_peak_bytes": peak,
                "trace": trace_summary},
        "numbers": numbers, "memory_peak_bytes": peak,
    }
