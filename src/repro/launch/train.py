"""End-to-end training driver with the Mimose planner on the critical path.

CPU-runnable example (reduced scale):
    PYTHONPATH=src python -m repro.launch.train --arch bert_base_paper \
        --dataset swag --planner mimose --budget-mb 600 --steps 50 --reduced

Sharding-aware planning: ``--mesh-shape 4x2 --hbm-gb 16`` plans against
the *per-device* budget of a (data=4, model=2) mesh — activations and
fixed bytes divided by their PartitionSpec divisors, ZeRO-1 aware with
``--zero1``.  The step compiles under the Mesh context (inputs stay
replicated — this driver passes no explicit shardings); a mesh with
more devices than are visible is an error.  End-to-end *sharded*
execution is validated by the dry-run path (launch/dryrun.py), which
lowers the step with full param/batch/optimizer NamedShardings.

``main`` returns the run's summary (``Trainer.summary()`` plus the
per-step history), so a caller in the same process reads results
instead of parsing stdout.

``--profile-dir DIR`` captures a ``jax.profiler`` trace of the steps
after prewarm, with the trainer's spans in it as ``program:<span>``
annotations (``repro.obs.SpanTracer(to_profiler=True)``), so the
device's idle gaps are named by what the host was doing.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (DTRSimPlanner, MeshBudget, MimosePlanner,
                        NonePlanner, SublinearPlanner)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, parse_mesh_shape
from repro.launch.report import engine_report
from repro.launch.roofline import device_peaks
from repro.obs import build_telemetry, flush_telemetry
from repro.data.pipeline import (DISTRIBUTIONS, bucket_length, make_batches,
                                 top_buckets)
from repro.models.lm import build_model
from repro.models.registry import get_config
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train import checkpoint as ckpt
from repro.train.resilience import (FaultInjector, OOMWatchdog,
                                    SnapshotManager)
from repro.train.trainer import Trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert_base_paper")
    ap.add_argument("--dataset", default="swag", choices=list(DISTRIBUTIONS))
    ap.add_argument("--planner", default="mimose",
                    choices=["mimose", "sublinear", "dtr", "none"])
    ap.add_argument("--budget-mb", type=float, default=0.0,
                    help="GPU/TPU memory budget; 0 = unlimited")
    ap.add_argument("--mesh-shape", default=None,
                    help="plan against a per-device mesh budget, e.g. 4x2 "
                         "(data x model) or 2x16x16 (pod x data x model)")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-device HBM for --mesh-shape planning")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1 optimizer-state sharding in the budget")
    ap.add_argument("--byte-only-remat", action="store_true",
                    help="paper's byte-only Algorithm 1 instead of "
                         "cost-aware (bytes per recompute-FLOP) selection")
    ap.add_argument("--offload", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="hybrid remat+offload plans: units may stream "
                         "residuals to pinned host memory when that beats "
                         "recompute (never worse at equal budget)")
    ap.add_argument("--pcie-gbps", type=float, default=None,
                    help="host<->device link bandwidth (GB/s) the planner "
                         "prices OFFLOAD actions at; default: this host's "
                         "measured calibration (tools/bench_offload_bw.py "
                         "writes it; $MIMOSE_PCIE_GBPS overrides), else 16")
    ap.add_argument("--opt-offload", action="store_true",
                    help="ZeRO-Offload-style fourth action: a plan may "
                         "park a unit's fp32 optimizer moments in host "
                         "memory for the whole step when the freed fixed "
                         "bytes beat the per-step link round trip "
                         "(needs --offload)")
    ap.add_argument("--max-microbatches", type=int, default=1,
                    help="adaptive microbatching: the planner may split "
                         "a bucket's step into up to K gradient-"
                         "accumulation microbatches when that wins on "
                         "simulated step time — or alone fits the "
                         "budget (k=1 always competes, so enabling "
                         "this never loses at equal budget)")
    ap.add_argument("--solver", default="off", choices=["off", "dp"],
                    help="optimal-plan tier: a background thread solves "
                         "each bucket's (k, action) assignment exactly "
                         "(DP over the layer chain, exhaustive on small "
                         "instances) and swaps the improved plan into the "
                         "cache — greedy still serves the first steps "
                         "instantly")
    ap.add_argument("--solver-budget-ms", type=float, default=50.0,
                    help="per-bucket wall-clock budget for the background "
                         "solve; on timeout the best plan found so far "
                         "still competes")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--quantum", type=int, default=32)
    ap.add_argument("--prewarm", type=int, default=0,
                    help="AOT-compile the top-K likeliest buckets before "
                         "step 0 (0 = off)")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced model variant (CPU demo)")
    ap.add_argument("--save", default=None)
    # elastic resilience (repro.train.resilience)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for periodic full-state snapshots "
                         "(params + optimizer + planner state + data "
                         "cursor); atomic, hash-manifested, last-k kept")
    ap.add_argument("--checkpoint-every-steps", type=int, default=25,
                    help="snapshot cadence in steps (0 = off)")
    ap.add_argument("--checkpoint-every-secs", type=float, default=0.0,
                    help="wall-clock snapshot cadence in seconds (0 = off; "
                         "fires on the first step boundary past the mark)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="retain the newest K snapshots")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid snapshot from "
                         "--checkpoint-dir (params, optimizer, planner "
                         "warmup state, data cursor) and continue — works "
                         "across a different --mesh-shape: estimator "
                         "samples replay abstractly under the new mesh")
    ap.add_argument("--max-oom-retries", type=int, default=3,
                    help="OOM watchdog: retries per step, each after a "
                         "DTR-style plan escalation (more remat -> "
                         "offload -> higher microbatch split)")
    ap.add_argument("--inject-oom", default=None,
                    help="deterministic fault injection for drills: an "
                         "int N (fail the first N step executions) or "
                         'JSON like {"bucket": {"1024": 2}} — also '
                         "readable from $MIMOSE_INJECT_OOM")
    # unified telemetry (repro.obs): all three sinks are opt-in and the
    # run is bitwise-identical with them off
    ap.add_argument("--metrics", default=None,
                    help="write the final metrics snapshot here at exit "
                         "(.json = JSON doc, anything else = Prometheus "
                         "text exposition)")
    ap.add_argument("--events-out", default=None,
                    help="structured JSONL event log: every planner "
                         "decision (plan/drift/refit/escalation), OOM, "
                         "snapshot and train step with provenance")
    ap.add_argument("--trace-out", default=None,
                    help="Chrome trace_event JSON (load in Perfetto / "
                         "chrome://tracing): per-step plan/compile/execute "
                         "spans, planner and transfer tracks")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the steps after "
                         "prewarm into this directory; the trainer's spans "
                         "appear in it as program:<span> annotations, so "
                         "the device's idle time is named by them")
    args = ap.parse_args(argv)
    enable_compile_cache()
    peaks = device_peaks()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=4, d_model=256, d_ff=512,
                          vocab_size=1024, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M "
          f"units={lm.num_plan_units()} "
          f"device={jax.devices()[0].device_kind} "
          f"peak={peaks.flops / 1e12:.0f}TFLOP/s")

    budget = args.budget_mb * 2**20 if args.budget_mb else 1e18
    mesh_budget = mesh = None
    if args.mesh_shape:
        shape = parse_mesh_shape(args.mesh_shape)
        mesh_budget = MeshBudget.from_shape(shape, args.hbm_gb * 2**30,
                                            zero1=args.zero1)
        # explicit --budget-mb overrides the per-device HBM
        budget = args.budget_mb * 2**20 if args.budget_mb else None
        # the Mesh context lets XLA honour any sharding constraints the
        # model emits; this driver does not device_put explicit
        # param/batch shardings, so data stays replicated — fully
        # sharded execution is the dry-run's job (launch/dryrun.py).
        # Too few visible devices raise here.
        mesh = make_production_mesh(shape=shape)
        print(f"mesh {shape}: planning per-device; compiling under the "
              f"{mesh.devices.size}-device mesh context (inputs "
              "replicated — see launch/dryrun.py for sharded execution)")
    dist = DISTRIBUTIONS[args.dataset]
    max_size = args.batch_size * bucket_length(dist.hi, args.quantum)
    if args.offload and args.byte_only_remat:
        ap.error("--offload needs the cost-aware selector "
                 "(drop --byte-only-remat)")
    if args.opt_offload and not args.offload:
        ap.error("--opt-offload needs --offload (moment parking rides "
                 "the same host link)")
    if args.opt_offload and args.planner != "mimose":
        ap.error("--opt-offload needs --planner mimose")
    if args.solver != "off" and args.planner != "mimose":
        ap.error("--solver needs --planner mimose (the solver tier swaps "
                 "plans into the Mimose bucket cache)")
    if args.pcie_gbps is None:
        # price the link at what THIS host measured, not the roofline
        # constant (tools/bench_offload_bw.py writes the calibration)
        from repro.launch.roofline import PCIE_BW, calibrated_pcie_gbps
        args.pcie_gbps = calibrated_pcie_gbps(PCIE_BW / 1e9)
    offload_degraded = False
    if args.offload:
        # probe-based: only degrade OFFLOAD execution to remat where a
        # minimal offloaded grad genuinely fails to compile under this
        # mesh (warn-once per mesh signature; the plan keeps its typed
        # actions either way)
        from repro.models.lm import configure_offload
        offload_degraded = configure_offload(lm, mesh)
    planner = {
        "mimose": lambda: MimosePlanner(lm, budget, quantum=args.quantum,
                                        mesh_budget=mesh_budget,
                                        warmup_samples=3,
                                        cost_aware=not args.byte_only_remat,
                                        offload=args.offload,
                                        opt_offload=args.opt_offload,
                                        pcie_gbps=args.pcie_gbps,
                                        max_microbatches=args.max_microbatches,
                                        solver=args.solver,
                                        solver_budget_ms=args.solver_budget_ms),
        "sublinear": lambda: SublinearPlanner(lm, budget,
                                              max_input_size=max_size,
                                              mesh_budget=mesh_budget,
                                              cost_aware=not args.byte_only_remat,
                                              offload=args.offload,
                                              pcie_gbps=args.pcie_gbps,
                                              max_microbatches=args.max_microbatches),
        "dtr": lambda: DTRSimPlanner(lm, budget, mesh_budget=mesh_budget,
                                     max_microbatches=args.max_microbatches),
        "none": lambda: NonePlanner(lm),
    }[args.planner]()
    if offload_degraded and isinstance(getattr(planner, "stats", None), dict):
        planner.stats["offload_fallbacks"] = (
            planner.stats.get("offload_fallbacks", 0) + 1)

    opt = AdamW(lr=cosine_schedule(args.lr, 10, args.steps))
    snapshots = None
    if args.checkpoint_dir:
        snapshots = SnapshotManager(args.checkpoint_dir,
                                    every_steps=args.checkpoint_every_steps,
                                    every_secs=args.checkpoint_every_secs,
                                    keep=args.checkpoint_keep)
    injector = (FaultInjector(args.inject_oom) if args.inject_oom
                else FaultInjector.from_env())
    watchdog = OOMWatchdog(max_retries=args.max_oom_retries,
                           injector=injector)
    telemetry = build_telemetry(metrics_path=args.metrics,
                                events_path=args.events_out,
                                trace_path=args.trace_out,
                                to_profiler=bool(args.profile_dir))
    trainer = Trainer(lm, planner, opt, mesh=mesh,
                      watchdog=watchdog, snapshots=snapshots,
                      telemetry=telemetry)
    batches = make_batches(args.dataset, batch_size=args.batch_size,
                           vocab_size=cfg.vocab_size,
                           num_batches=args.steps, quantum=args.quantum,
                           seed=0)
    t0 = time.time()
    opt_state = opt.init(params)
    if args.resume:
        if snapshots is None:
            ap.error("--resume needs --checkpoint-dir")
        restored = snapshots.restore_latest(params_like=params,
                                            opt_like=opt_state,
                                            planner=planner)
        params, opt_state = restored.params, restored.opt_state
        trainer.global_step = restored.step
        trainer.data_cursor = restored.data_cursor
        trainer.restores = 1
        # the batch stream is deterministic (seeded) — the cursor says
        # how many batches the snapshot already consumed
        batches = itertools.islice(iter(batches), restored.data_cursor,
                                   None)
        print(f"resumed {restored.path} at step {restored.step} "
              f"(cursor={restored.data_cursor}, "
              f"planner={restored.planner_summary})")
    if args.prewarm:
        likely = top_buckets(args.dataset, batch_size=args.batch_size,
                             quantum=max(args.quantum,
                                         getattr(planner, "quantum", 1)),
                             k=args.prewarm)
        tw = time.time()
        n = trainer.prewarm(params, opt_state, [S for S, _ in likely],
                            args.batch_size)
        print(f"prewarmed {n} bucket(s) {[S for S, _ in likely]} "
              f"in {time.time() - tw:.1f}s")
    if args.profile_dir:
        # host annotations without the Python call tracer, which would
        # slow the host and so widen the very gaps the trace shows
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(args.profile_dir, profiler_options=opts)
    for i, batch in enumerate(batches):
        params, opt_state, _ = trainer.step(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            st = trainer.history[-1]
            loss = st.loss    # reads the loss, and with it the step time
            print(f"step {i:4d} loss {loss:.4f} S={batch['tokens'].shape[1]}"
                  f" remat={st.remat_units} offload={st.offload_units}"
                  f" k={st.microbatches} step_s={st.step_time_s:.3f}")
    trainer.drain()
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"profile written to {args.profile_dir}")
    bs = getattr(planner, "background_solver", None)
    if bs is not None:
        # let in-flight solves land so the final snapshot and report see
        # the solved plans (bounded wait; training is already done)
        bs.drain(timeout=5.0)
    if snapshots is not None:
        final = snapshots.save(step=trainer.global_step, params=params,
                               opt_state=opt_state, planner=planner,
                               data_cursor=trainer.data_cursor)
        print("snapshot", final)
    print(f"done in {time.time() - t0:.1f}s")
    summary = trainer.summary()
    print("summary:", summary)
    print("\nengine report (where the padding went):")
    print(engine_report(trainer, planner))
    if hasattr(planner, "stats"):
        print("planner:", planner.stats, "plans cached:",
              len(getattr(planner, "cache", {})))
    if args.save:
        ckpt.save(args.save, params)
        print("saved", args.save)
    for kind, path in flush_telemetry(telemetry).items():
        print(f"{kind} written to {path}")
    return dict(summary,
                history=[dataclasses.asdict(s) for s in trainer.history])


if __name__ == "__main__":
    main()
