"""Engine benchmark: the compile-once bucketed execution path.

Measures the quantities the engine issues' acceptance criteria name and
writes everything to ``BENCH_engine.json``:

  1. scheduler  — ``greedy_plan`` (flat-array) vs the seed's python-list
     ``greedy_plan_reference`` on 24/96-unit inputs.
  2. collector  — deduplicated sheltered collection vs per-layer
     collection on an >= 8-layer homogeneous model.
  3. engine     — train steps over the SWAG-like length distributions for
     mimose / none / sublinear: XLA compile counts vs #buckets vs
     #distinct raw shapes, plan latency, cache hit rates, steps/s.
     Throughput is reported as *effective* (unpadded) tokens/s, with the
     raw padded rate as a secondary field, so padded and ragged runs are
     comparable.
  4. sharded    — the mesh-budget scenario sweep (1-device, (4, 2),
     (16, 16)): the same per-device HBM budget is infeasible on one
     device (the fixed param/grad/optimizer bytes alone exceed it) but
     the sharding-aware planner fits it on the meshes, validated by the
     per-device liveness simulator.  MeshBudget is pure axis-size math,
     so the 256-chip scenario plans on this single-CPU container.
  5. ragged     — the pad-fraction sweep: length-aware flash-attention /
     SSD kernels on a bucket-padded batch at 10/30/50% padding vs the
     unmasked kernels and the no-padding ideal; reports effective
     tokens/s and the fraction of the padding-induced throughput loss
     the masked kernels recover.
  6. remat_cost — cost-aware (bytes per recompute-FLOP) vs byte-only
     greedy selection on a heterogeneous (gemma3-style local/global)
     model under a per-device mesh budget: simulated recompute time at
     equal budget, feasibility per device.
  7. hybrid     — typed action plans (KEEP/REMAT/OFFLOAD-to-host) vs
     remat-only: a budget below the all-remat floor (fixed + boundary
     checkpoints) that only OFFLOAD can fit, an equal-budget sweep
     where the hybrid plan's simulated step overhead (recompute +
     non-overlapped PCIe transfer) never exceeds remat-only's, and a
     fully-overlapped-transfer point where hybrid is strictly faster.
  8. microbatch — adaptive microbatching (gradient accumulation as a
     planner knob): a budget below the bucket's global-minimum k=1
     footprint (exhaustive over ALL 3^n action plans) that a k=2 split
     fits, and an equal-budget sweep where the adaptive planner's
     simulated step overhead never exceeds the k=1 planner's (k=1
     always competes in the candidate search).
  9. serve      — continuous-batching serve engine vs sequential
     generation at equal HBM budget on one deterministic open-loop
     trace (warm pass both ways): throughput, token-for-token output
     equality, admission ledger (predicted peak bounds actual peak
     bounds budget), estimator accuracy on unsampled buckets, decode
     compile geometries vs the O(#buckets x #tiers) bound.
 10. offload_exec — MEASURED wall-clock of real double-buffered offload
     (repro.train.transfer.TransferLane) vs rematerialisation on a
     transfer-bound synthetic matmul chain: offload must beat remat at
     the point where recompute dwarfs the (hidden) transfer, and the
     lane's measured exposed transfer time must stay within the
     simulator's zero-overlap bound at the bandwidth the step actually
     achieved — the lane's own measured copy wall time — with a
     x1.5 + 5 ms tolerance band.

Usage:
    PYTHONPATH=src python benchmarks/bench_engine.py [--smoke] \
        [--out BENCH_engine.json]

``--smoke`` shrinks every axis so the whole file runs in under a minute
on CI while still exercising each measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (MeshBudget, MimosePlanner, NonePlanner,
                        SublinearPlanner, greedy_plan_adaptive, simulate,
                        simulate_sharded, solve)
from repro.core.collector import ShuttlingCollector
from repro.core.planner import fixed_train_bytes
from repro.core.scheduler import greedy_plan, greedy_plan_reference
from repro.data.pipeline import DISTRIBUTIONS, bucket_edges, make_batches
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ssd
from repro.models.lm import build_model
from repro.models.registry import get_config
from repro.obs import Telemetry, build_telemetry, flush_telemetry
from repro.optim.adamw import AdamW
from repro.sharding.budget import fixed_train_bytes_per_device
from repro.train.trainer import Trainer


def bench_scheduler(smoke: bool) -> dict:
    """(c) greedy_plan latency: flat-array vs seed implementation."""
    rng = np.random.default_rng(0)
    reps = 30 if smoke else 300
    out = {}
    for n in (24, 96):
        est = rng.uniform(1e6, 1e9, n)
        budget = est.sum() * 0.4          # ~60% of units rematerialised
        rows = {}
        for fn, name in ((greedy_plan, "fast"),
                         (greedy_plan_reference, "reference")):
            fn(est, budget)               # warm any lazy imports
            t0 = time.perf_counter()
            for _ in range(reps):
                fn(est, budget)
            rows[name] = (time.perf_counter() - t0) / reps * 1e6
        agree = (greedy_plan(est, budget).remat
                 == greedy_plan_reference(est, budget).remat)
        out[f"units_{n}"] = {
            "fast_us": round(rows["fast"], 1),
            "reference_us": round(rows["reference"], 1),
            "speedup": round(rows["reference"] / rows["fast"], 2),
            "plans_identical": bool(agree),
        }
    return out


def bench_collector(smoke: bool) -> dict:
    """(b) sheltered collection: deduplicated vs per-layer traces."""
    layers = 8
    cfg = get_config("bert_base_paper").reduced(
        num_layers=layers, d_model=96 if smoke else 128,
        d_ff=192 if smoke else 256, vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))

    def one(dedup: bool, S: int) -> float:
        col = ShuttlingCollector(lm, dedup=dedup)
        batch = {"tokens": jnp.ones((2, S), jnp.int32),
                 "labels": jnp.ones((2, S), jnp.int32)}
        t0 = time.perf_counter()
        res = col.collect(params, batch)
        return time.perf_counter() - t0, res

    reps = 2 if smoke else 3
    t_base = min(one(False, 128)[0] for _ in range(reps))
    t_dedup, res = min(((t, r) for t, r in (one(True, 128)
                                            for _ in range(reps))),
                       key=lambda p: p[0])
    base_res = one(False, 128)[1]
    return {
        "layers": layers,
        "per_layer_s": round(t_base, 4),
        "dedup_s": round(t_dedup, 4),
        "speedup": round(t_base / t_dedup, 2),
        "traced_units": res.traced_units,
        "dedup_hits": res.dedup_hits,
        "byte_identical": bool(np.array_equal(res.activation_vector(),
                                              base_res.activation_vector())),
    }


def bench_engine(smoke: bool) -> dict:
    """(a) compile counts bounded by #buckets + throughput comparison.

    The pipeline emits batches at a fine quantum (many distinct raw
    shapes); the mimose planner buckets at a coarser quantum, so the
    engine's compile count collapses onto the bucket set while the
    unbucketed baseline compiles once per raw shape.
    """
    cfg = get_config("bert_base_paper").reduced(
        num_layers=2 if smoke else 4, d_model=128, d_ff=256,
        vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))

    dataset = "swag"
    batch_size = 4
    steps = 10 if smoke else 30
    raw_quantum = 8                  # fine-grained -> many raw shapes
    engine_quantum = 64              # planner bucket granularity

    col = ShuttlingCollector(lm)
    S_hi = DISTRIBUTIONS[dataset].hi
    tot = col.collect(params, {
        "tokens": jnp.ones((batch_size, S_hi), jnp.int32)
    }).total_activation_bytes()
    budget = fixed_train_bytes(params) + 0.5 * tot

    batches = list(make_batches(dataset, batch_size=batch_size,
                                vocab_size=cfg.vocab_size,
                                num_batches=steps, quantum=raw_quantum,
                                seed=1))
    raw_shapes = {b["tokens"].shape for b in batches}
    n_buckets_possible = len(bucket_edges(DISTRIBUTIONS[dataset],
                                          engine_quantum))

    results = {}
    for kind in ("mimose", "none", "sublinear"):
        if kind == "mimose":
            planner = MimosePlanner(lm, budget, quantum=engine_quantum,
                                    warmup_samples=3)
        elif kind == "sublinear":
            planner = SublinearPlanner(
                lm, budget,
                max_input_size=batch_size * S_hi, warmup_samples=3)
        else:
            planner = NonePlanner(lm)
        tr = Trainer(lm, planner, AdamW(lr=1e-3))
        p = jax.tree_util.tree_map(jnp.copy, params)
        opt_state = tr.optimizer.init(p)
        t0 = time.perf_counter()
        for b in batches:
            p, opt_state, _ = tr.step(p, opt_state, b)
        tr.drain()
        wall = time.perf_counter() - t0
        s = tr.summary()
        results[kind] = {
            "steps": steps,
            "compiles": s["compiles"],
            "buckets_seen": s["buckets"],
            "jit_hits": s["jit_hits"],
            "steps_per_s": round(steps / wall, 3),
            # effective (unpadded) tokens/s — the comparable number;
            # the raw padded rate rides along as a diagnostic
            "tokens_per_s": round(s["tokens_per_s"], 1),
            "padded_tokens_per_s": round(s["padded_tokens_per_s"], 1),
            "pad_fraction": round(s["pad_fraction"], 4),
            "mean_plan_ms": round(s["total_plan_s"] / steps * 1e3, 3),
            "mean_remat_units": s["mean_remat_units"],
        }
        if kind == "mimose":
            results[kind]["plan_cache"] = {
                "hits": planner.stats["cache_hits"],
                "misses": planner.stats["cache_misses"],
                "collections": planner.stats["collections"],
            }
    results["distinct_raw_shapes"] = len(raw_shapes)
    results["bucket_set_size"] = n_buckets_possible
    results["engine_quantum"] = engine_quantum
    return results


def bench_sharded(smoke: bool) -> dict:
    """(d) mesh-budget scenario sweep: 1-device vs (4, 2) vs (16, 16).

    One per-device HBM budget (75% of the single-device fixed bytes, so
    a lone device cannot even hold the param/grad/optimizer state) is
    planned on each mesh shape; the per-device liveness simulation then
    checks the plan's peak against the budget.
    """
    cfg = get_config("bert_base_paper").reduced(
        num_layers=2 if smoke else 4, d_model=128, d_ff=256,
        vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    S = 32 if smoke else 64
    batch = {"tokens": jnp.ones((16, S), jnp.int32),
             "labels": jnp.ones((16, S), jnp.int32)}

    fixed_global = fixed_train_bytes(params)
    hbm = 0.75 * fixed_global
    out = {"hbm_per_device_bytes": int(hbm),
           "single_device_fixed_bytes": int(fixed_global),
           "scenarios": {}}
    for shape in ((1,), (4, 2), (16, 16)):
        budget = MeshBudget.from_shape(shape, hbm, zero1=True)
        # the scheduler models peak as fixed + saved residuals; the
        # liveness replay additionally charges the executing unit's
        # recomputed residuals + gradient working set (up to 2x the
        # largest unit), so plan with that much headroom
        col = ShuttlingCollector(lm, mesh_budget=budget).collect(
            params, batch)
        margin = 2 * float(col.device_activation_vector().max(initial=0.0))
        planner = MimosePlanner(lm, max(hbm - margin, 0.0),
                                mesh_budget=budget,
                                warmup_samples=1, quantum=32)
        t0 = time.perf_counter()
        mask, _info = planner.plan(params, batch)
        t_plan = time.perf_counter() - t0
        sim = simulate_sharded(col.device_activation_vector(), mask,
                               planner.resolve_fixed_bytes(params), budget.n_devices)
        name = "x".join(str(s) for s in shape)
        out["scenarios"][name] = {
            "n_devices": budget.n_devices,
            "fixed_bytes_per_device": int(planner.resolve_fixed_bytes(params)),
            "peak_bytes_per_device": int(sim.peak_bytes_per_device),
            "budget_bytes_per_device": int(hbm),
            "fits": bool(sim.fits(hbm)),
            "n_remat": int(sum(mask)),
            "plan_ms": round(t_plan * 1e3, 3),
        }
    sc = out["scenarios"]
    out["single_device_infeasible"] = not sc["1"]["fits"]
    out["sharded_fit_per_device"] = sc["4x2"]["fits"] and sc["16x16"]["fits"]
    return out


def _time_best(fn, args, reps: int) -> float:
    """Best-of-``reps`` wall time of an already-jitted callable."""
    jax.block_until_ready(fn(*args))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _flash_executed_flops(B, H, hd, S, L, bq, bk) -> float:
    """MXU FLOPs the causal flash kernel executes at bucket S with true
    length L — mirrors the kernel's trip-count clamps exactly: per query
    block, upper = min(causal bound, cdiv(L, bk)), zero once the block
    is fully inside the padding; 2 matmuls (qk^T, p@v) per trip."""
    nqb = -(-S // bq)
    nkb = -(-S // bk)
    trips = 0
    for qi in range(nqb):
        if qi * bq >= L:
            continue
        trips += min(-(-((qi + 1) * bq) // bk), nkb, -(-L // bk))
    return float(B * H * trips) * 4.0 * bq * bk * hd


def _ssd_executed_flops(B, H, P, N, S, L, chunk) -> float:
    """MXU FLOPs the SSD kernel executes at bucket S with true length L
    — the dynamic chunk loop runs cdiv(L, chunk) of the S/chunk chunks;
    per chunk: CB^T (Q,Q,N), w@x (Q,Q,P), two (Q,P,N) state terms."""
    Q = chunk
    chunks = -(-L // Q)
    per_chunk = 2.0 * Q * Q * N + 2.0 * Q * Q * P + 4.0 * Q * P * N
    return float(B * H * chunks) * per_chunk


def bench_ragged(smoke: bool) -> dict:
    """(e) pad-fraction sweep: masked (length-aware) kernels on a padded
    bucket vs unmasked kernels vs the no-padding ideal.

    For each pad fraction p the bucket sequence length S carries
    L = S*(1-p) real tokens.  Three variants per kernel:

      * ideal    — kernel at shape L (what a shape-per-length engine
                   would pay per step, ignoring its recompiles);
      * unmasked — kernel at shape S with no length operand (computes
                   over padding: the PR-1 engine's behaviour);
      * masked   — kernel at shape S with ``kv_len = L`` (same compiled
                   executable for every L — compile-once preserved).

    Two views of effective (real tokens only) throughput:

      * modeled  — executed kernel FLOPs (exact trip counts of the
                   length-aware clamps, above) at the TPU roofline
                   (``PEAK_FLOPS``) — deterministic, the number the
                   acceptance gate reads, in the same hardware-free
                   methodology as the dry-run/roofline benchmarks;
      * measured — interpret-mode wall time on this host (secondary
                   evidence that the dynamic trip counts really shrink
                   at runtime; CPU emulation overhead per grid cell
                   makes it an undercount of the TPU win).

    ``recovered`` = (masked - unmasked) / (ideal - unmasked): the
    fraction of the padding-induced throughput loss the masked kernel
    wins back.
    """
    from repro.launch.roofline import PEAK_FLOPS
    key = jax.random.PRNGKey(0)
    reps = 3 if smoke else 8

    B, H, hd = 1, 1, 32
    S = 2048 if smoke else 4096
    bq, bk = 128, 32
    flash_padded = jax.jit(lambda q, k, v, kvl: fa.flash_attention_fwd(
        q, k, v, kvl, causal=True, block_q=bq, block_k=bk, interpret=True))

    def make_qkv(s):
        ks = jax.random.split(key, 3)
        return tuple(jax.random.normal(k_, (B, H, s, hd), jnp.float32)
                     for k_ in ks)

    P, N, chunk, K = 64, 64, 64, 4
    Hs = 2

    def ssd_fn():
        return jax.jit(lambda x, dt, A, Bm, Cm, kvl: ssd.ssd_scan(
            x, dt, A, Bm, Cm, kv_len=kvl, chunk=chunk, chunks_per_block=K,
            interpret=True))

    Ss = 2048

    def make_ssd(s):
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, s, Hs, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, Hs)))
        A = -jnp.exp(jax.random.normal(ks[2], (Hs,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, s, N))
        Cm = jax.random.normal(ks[4], (B, s, N))
        return x, dt, A, Bm, Cm

    ssd_padded = ssd_fn()
    qkv_S = make_qkv(S)
    ssd_S = make_ssd(Ss)
    out = {"flash_bucket_seq": S, "ssd_bucket_seq": Ss, "batch": B,
           "method": "modeled = executed kernel FLOPs / PEAK_FLOPS "
                     "(deterministic); measured = interpret-mode wall "
                     "time on this host",
           "sweep": {}}
    for pf in (0.1, 0.3, 0.5):
        row = {}
        for name, bucket, span in (("flash", S, bq), ("ssd", Ss, chunk * K)):
            # real length kept span-aligned so the ideal shape exists
            L = max(span, int(round(bucket * (1.0 - pf) / span)) * span)
            kvl = jnp.full((B,), L, jnp.int32)
            full = jnp.full((B,), bucket, jnp.int32)
            if name == "flash":
                w_id = _flash_executed_flops(B, H, hd, L, L, bq, bk)
                w_un = _flash_executed_flops(B, H, hd, bucket, bucket, bq, bk)
                w_mk = _flash_executed_flops(B, H, hd, bucket, L, bq, bk)
                args_S = qkv_S
                args_L = tuple(a[:, :, :L] for a in qkv_S)  # seq axis 2
                fn_p = flash_padded
                fn_i = jax.jit(lambda q, k, v, kvl: fa.flash_attention_fwd(
                    q, k, v, kvl, causal=True, block_q=bq, block_k=bk,
                    interpret=True))
            else:
                w_id = _ssd_executed_flops(B, Hs, P, N, L, L, chunk)
                w_un = _ssd_executed_flops(B, Hs, P, N, Ss, Ss, chunk)
                w_mk = _ssd_executed_flops(B, Hs, P, N, Ss, L, chunk)
                args_S = ssd_S
                x_, dt_, A_, Bm_, Cm_ = ssd_S                # seq axis 1
                args_L = (x_[:, :L], dt_[:, :L], A_, Bm_[:, :L], Cm_[:, :L])
                fn_p, fn_i = ssd_padded, ssd_fn()
            tok = B * L
            m_id, m_un, m_mk = (tok / (w / PEAK_FLOPS)
                                for w in (w_id, w_un, w_mk))
            # tether the executed-work model to the executable: the
            # masked run over the padded bucket must reproduce the
            # ideal (unpadded-shape) run at the valid positions, or the
            # modeled numbers describe a kernel that doesn't exist
            got = np.asarray(fn_p(*(args_S + (kvl,))))
            want = np.asarray(fn_i(*(args_L + (kvl,))))
            got = got[:, :, :L] if name == "flash" else got[:, :L]
            want = want[:, :, :L] if name == "flash" else want[:, :L]
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            t_id = _time_best(fn_i, args_L + (kvl,), reps)
            t_un = _time_best(fn_p, args_S + (full,), reps)
            t_mk = _time_best(fn_p, args_S + (kvl,), reps)
            r_id, r_un, r_mk = tok / t_id, tok / t_un, tok / t_mk
            row[name] = {
                "real_len": L,
                "modeled_eff_tokens_per_s": {
                    "ideal": round(m_id, 1), "unmasked": round(m_un, 1),
                    "masked": round(m_mk, 1)},
                "modeled_recovered": round((m_mk - m_un) / (m_id - m_un), 3)
                                     if m_id > m_un else 1.0,
                "measured_eff_tokens_per_s": {
                    "ideal": round(r_id, 1), "unmasked": round(r_un, 1),
                    "masked": round(r_mk, 1)},
                "measured_recovered": round((r_mk - r_un) / (r_id - r_un), 3)
                                      if r_id > r_un else 1.0,
            }
        out["sweep"][f"pad_{int(pf * 100)}pct"] = row
    return out


def bench_remat_cost(smoke: bool) -> dict:
    """(f) cost-aware vs byte-only remat selection at equal budget.

    A gemma3-style reduced model (sliding-window local layers with a
    global layer every 2nd) under the flash-attention kernels is the
    motivating heterogeneous case: every unit's O(S) flash residuals
    free the SAME bytes, but a global full-attention layer costs far
    more FLOPs to recompute than a windowed local layer.  Byte-only
    selection cannot tell them apart (one bucket, timestamp order);
    cost-aware selection remats the cheap local layers first.  Both
    selectors plan the same per-device mesh budget sweep; the per-device
    liveness simulator reports recompute time and validates feasibility.
    """
    cfg = get_config("gemma3_12b").reduced(
        num_layers=4 if smoke else 8, d_model=128, d_ff=256,
        vocab_size=512, dtype="float32", sliding_window=64,
        global_interval=2)
    lm = build_model(cfg, attn_impl="flash")
    params = lm.init(jax.random.PRNGKey(0))
    B, S = 4, 512
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}

    mesh_shape = (4, 2)
    budget_probe = MeshBudget.from_shape(mesh_shape, 1e18, zero1=True)
    col = ShuttlingCollector(lm, mesh_budget=budget_probe).collect(
        params, batch)
    act = col.device_activation_vector()
    fl = col.flops_vector()                       # cost model rides along
    fl_dev = fl / budget_probe.n_devices          # SPMD: per-device share
    fixed = fixed_train_bytes_per_device(params, budget_probe)
    # liveness replay charges the executing unit's working set on top of
    # fixed + saved residuals; plan with that much headroom (cf. sharded)
    margin = 2 * float(act.max(initial=0.0))

    out = {"arch": cfg.name, "units": lm.num_plan_units(),
           "mesh": "x".join(map(str, mesh_shape)), "budgets": {}}
    for cover in (0.3, 0.5, 0.7):
        budget = fixed + (1.0 - cover) * float(act.sum()) + margin
        row = {}
        for name, byte_only in (("byte_only", True), ("cost_aware", False)):
            plan = greedy_plan(act, budget - margin, fixed, flops=fl_dev,
                               byte_only=byte_only)
            sim = simulate_sharded(act, plan.remat, fixed,
                                   budget_probe.n_devices, flops=fl_dev)
            row[name] = {
                "n_remat": plan.n_remat,
                "recompute_gflops_per_dev": round(
                    sim.per_device.recompute_flops / 1e9, 3),
                "recompute_time_us": round(sim.recompute_time_s * 1e6, 3),
                "peak_bytes_per_device": int(sim.peak_bytes_per_device),
                "fits_budget": bool(sim.fits(budget)),
            }
        b, c = row["byte_only"], row["cost_aware"]
        row["time_reduction"] = round(
            1.0 - c["recompute_time_us"] / b["recompute_time_us"], 4) \
            if b["recompute_time_us"] else 0.0
        out["budgets"][f"cover_{int(cover * 100)}pct"] = row
    return out


def bench_hybrid(smoke: bool) -> dict:
    """(g) hybrid remat+offload action plans vs remat-only.

    Three claims, all validated by the liveness simulator on collected
    (exact, abstract) byte vectors:

      * feasibility gap — REMAT must keep every unit's boundary tensor
        on device as its recompute checkpoint (and KEEP keeps all of
        it), so every boolean plan has a peak floor; OFFLOAD streams
        the checkpoint to host too.  A budget between the exhaustive
        best-boolean-plan peak and the all-offload peak is infeasible
        for every remat mask but feasible hybrid.
      * floor property — at equal (feasible-for-both) budgets the hybrid
        plan's simulated step overhead (recompute + non-overlapped PCIe
        transfer) never exceeds the remat-only plan's: the remat-only
        plan always competes in the scheduler's candidate set.
      * overlapped win — with the transfer fully hidden under compute
        (``offload_overlap=1``) OFFLOAD is strictly cheaper than any
        recompute, so the hybrid plan eliminates recompute time at a
        budget where remat-only pays it.
    """
    cfg = get_config("bert_base_paper").reduced(
        num_layers=4 if smoke else 8, d_model=128, d_ff=256,
        vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    B, S = 4, 128 if smoke else 256
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    col = ShuttlingCollector(lm).collect(params, batch)
    act = col.activation_vector()
    out = col.output_vector()
    off = col.offloadable_vector()
    fl = col.flops_vector()
    fixed = fixed_train_bytes(params)
    pcie = 16e9
    # liveness headroom: fwd charges act+out over the saved set, bwd
    # resurrects an offloaded/rematted unit's residuals under its own
    # gradient working set (up to 2x the largest unit)
    margin = 2 * float(act.max()) + float(out.max())

    def replay(plan, overlap=0.5):
        return simulate(act, plan.actions, fixed, out, fl,
                        offload_bytes=off, pcie_bytes_per_s=pcie,
                        overlap=overlap)

    res = {"arch": cfg.name, "units": lm.num_plan_units(),
           "pcie_gbps": pcie / 1e9,
           "remat_floor_bytes": int(fixed + out.sum()),
           "hybrid_floor_bytes": int(fixed + (act - off).sum())}

    # -- feasibility gap: a budget NO boolean remat mask can fit --------
    # exhaustive over all 2^n masks (n <= 8 here): the true remat-only
    # floor, not just the all-remat plan
    import itertools
    bool_floor = min(
        simulate(act, mask, fixed, out, fl).peak_bytes
        for mask in itertools.product([False, True], repeat=len(act)))
    all_off_peak = simulate(act, [2] * len(act), fixed, out, fl,
                            offload_bytes=off,
                            pcie_bytes_per_s=pcie).peak_bytes
    gap_budget = 0.5 * (all_off_peak + bool_floor)
    hyb = greedy_plan(act, gap_budget, fixed, flops=fl, output_bytes=out,
                      offload_bytes=off, pcie_bytes_per_s=pcie)
    sim_h = replay(hyb)
    res["below_remat_floor"] = {
        "budget_bytes": int(gap_budget),
        "best_bool_plan_peak_bytes": int(bool_floor),
        "any_bool_plan_fits": bool(bool_floor <= gap_budget),
        "hybrid_peak_bytes": int(sim_h.peak_bytes),
        "hybrid_fits": bool(sim_h.fits(gap_budget)),
        "n_offload": hyb.n_offload,
        "offload_time_us": round(sim_h.offload_time_s * 1e6, 3),
    }

    # -- equal-budget sweep: hybrid never worse than remat-only ---------
    # scheduling-vs-simulation headroom (cf. the sharded sweep): plans
    # are built against budget - margin, validated against budget
    res["equal_budget"] = {}
    for cover in (0.3, 0.5, 0.7):
        budget = fixed + (1.0 - cover) * float(act.sum()) \
            + float(out.sum()) + margin
        # the legacy remat-only greedy needs the margin convention; the
        # hybrid planner replays liveness internally, so it takes the
        # true budget and handles transients itself
        ro = greedy_plan(act, budget - margin, fixed, flops=fl)
        hy = greedy_plan(act, budget, fixed, flops=fl,
                         output_bytes=out, offload_bytes=off,
                         pcie_bytes_per_s=pcie)
        sim_r, sim_y = replay(ro), replay(hy)
        res["equal_budget"][f"cover_{int(cover * 100)}pct"] = {
            "budget_bytes": int(budget),
            "remat_only": {
                "n_remat": ro.n_remat,
                "overhead_us": round(sim_r.step_overhead_s * 1e6, 3),
                "fits": bool(sim_r.fits(budget))},
            "hybrid": {
                "n_remat": hy.n_remat, "n_offload": hy.n_offload,
                "overhead_us": round(sim_y.step_overhead_s * 1e6, 3),
                "fits": bool(sim_y.fits(budget))},
        }

    # -- fully-overlapped transfer: offload strictly beats recompute ----
    budget = fixed + 0.5 * float(act.sum()) + float(out.sum()) + margin
    ro = greedy_plan(act, budget - margin, fixed, flops=fl)
    hy = greedy_plan(act, budget, fixed, flops=fl,
                     output_bytes=out, offload_bytes=off,
                     pcie_bytes_per_s=pcie, offload_overlap=1.0)
    sim_r, sim_y = replay(ro, 1.0), replay(hy, 1.0)
    res["overlapped_transfer"] = {
        "budget_bytes": int(budget),
        "remat_only_overhead_us": round(sim_r.step_overhead_s * 1e6, 3),
        "hybrid_overhead_us": round(sim_y.step_overhead_s * 1e6, 3),
        "hybrid_n_offload": hy.n_offload,
        "both_fit": bool(sim_r.fits(budget) and sim_y.fits(budget)),
    }
    return res


def bench_microbatch(smoke: bool) -> dict:
    """(h) adaptive microbatching vs the k=1 planner.

    Two claims, both on collected (exact, abstract) per-microbatch byte
    vectors and validated by the liveness simulator:

      * feasibility gap — every k=1 plan has a peak floor: even
        all-OFFLOAD keeps the non-offloadable residues plus the
        executing unit's transient working set on device, so there is
        a global-minimum footprint for the bucket (exhaustive over ALL
        3^n action plans).  Splitting the batch shrinks the per-unit
        activation terms themselves, so a budget between the k=2 and
        k=1 exhaustive floors is infeasible for every k=1 action plan
        yet feasible at k=2 — the scenario the pre-microbatching
        system flatly could not run.
      * never-worse floor — the adaptive candidate search always
        includes k=1, so at every equal (k=1-feasible) budget the
        chosen (k, action-plan) pair's simulated step overhead
        (recompute + exposed transfer + accumulation) never exceeds
        the k=1 planner's.
    """
    import itertools

    cfg = get_config("bert_base_paper").reduced(
        num_layers=4 if smoke else 6, d_model=128, d_ff=256,
        vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    B, S = 8, 128 if smoke else 256
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    fixed = fixed_train_bytes(params)
    pcie = 16e9
    candidate_ks = (1, 2, 4)

    # exact per-microbatch vectors per split: one abstract collection
    # on each split geometry (what the planner's estimator predicts
    # once warm — collections keep the benchmark deterministic)
    vecs = {}
    for k in candidate_ks:
        Bk = -(-B // k)
        probe = {key: v[:Bk] for key, v in batch.items()}
        col = ShuttlingCollector(lm).collect(params, probe)
        vecs[k] = {"est_mem": col.activation_vector(),
                   "output_bytes": col.output_vector(),
                   "offload_bytes": col.offloadable_vector(),
                   "flops": col.flops_vector()}

    def vectors_of_k(k):
        return vecs[k]

    def exhaustive_floor(k: int) -> float:
        """Minimum simulated peak over EVERY action plan at split k —
        the true global-minimum footprint of the bucket (small n)."""
        v = vecs[k]
        n = len(v["est_mem"])
        return min(
            simulate(v["est_mem"], plan, fixed, v["output_bytes"],
                     v["flops"], offload_bytes=v["offload_bytes"],
                     pcie_bytes_per_s=pcie, microbatch=k).peak_bytes
            for plan in itertools.product((0, 1, 2), repeat=n))

    def replay(plan):
        v = vecs[plan.microbatch]
        return simulate(v["est_mem"], plan.actions, fixed,
                        v["output_bytes"], v["flops"],
                        offload_bytes=v["offload_bytes"],
                        pcie_bytes_per_s=pcie,
                        microbatch=plan.microbatch,
                        accum_overhead_s=5e-4)

    res = {"arch": cfg.name, "units": lm.num_plan_units(),
           "batch": B, "seq": S, "candidate_ks": list(candidate_ks)}

    # -- feasibility gap: below the k=1 global-minimum footprint --------
    k1_floor = exhaustive_floor(1)
    k2_floor = exhaustive_floor(2)
    gap_budget = 0.5 * (k1_floor + k2_floor)
    plan = greedy_plan_adaptive(vectors_of_k, gap_budget, fixed,
                                candidate_ks=[1, 2],
                                pcie_bytes_per_s=pcie,
                                accum_overhead_s=5e-4)
    sim = replay(plan)
    res["below_k1_floor"] = {
        "budget_bytes": int(gap_budget),
        "k1_global_min_peak_bytes": int(k1_floor),
        "k2_global_min_peak_bytes": int(k2_floor),
        "any_k1_plan_fits": bool(k1_floor <= gap_budget),
        "chosen_microbatch": plan.microbatch,
        "adaptive_peak_bytes": int(sim.peak_bytes),
        "adaptive_fits": bool(sim.fits(gap_budget)),
    }

    # -- equal-budget sweep: adaptive never worse than the k=1 planner --
    act1 = vecs[1]["est_mem"]
    margin = 2 * float(act1.max()) + float(vecs[1]["output_bytes"].max())
    res["equal_budget"] = {}
    for cover in (0.3, 0.5, 0.7):
        budget = fixed + (1.0 - cover) * float(act1.sum()) \
            + float(vecs[1]["output_bytes"].sum()) + margin
        p1 = greedy_plan_adaptive(vectors_of_k, budget, fixed,
                                  candidate_ks=[1],
                                  pcie_bytes_per_s=pcie,
                                  accum_overhead_s=5e-4)
        pk = greedy_plan_adaptive(vectors_of_k, budget, fixed,
                                  candidate_ks=list(candidate_ks),
                                  pcie_bytes_per_s=pcie,
                                  accum_overhead_s=5e-4)
        s1, sk = replay(p1), replay(pk)
        res["equal_budget"][f"cover_{int(cover * 100)}pct"] = {
            "budget_bytes": int(budget),
            "k1": {"n_remat": p1.n_remat,
                   "overhead_us": round(s1.step_overhead_s * 1e6, 3),
                   "fits": bool(s1.fits(budget))},
            "adaptive": {"microbatch": pk.microbatch,
                         "n_remat": pk.n_remat,
                         "overhead_us": round(sk.step_overhead_s * 1e6, 3),
                         "fits": bool(sk.fits(budget))},
        }
    return res


def bench_solver(smoke: bool) -> dict:
    """(i) the optimal-plan tier vs the greedy density heuristic.

    The PR-5 hybrid point is the motivating case: a gemma3-style
    heterogeneous model (cheap sliding-window layers, expensive global
    layers every 2nd) with remat+offload+microbatch all in play.  The
    greedy scores one (unit, action) density at a time, so at budgets
    where the optimum mixes actions across the local/global cost gap it
    over-pays; ``solve()`` (exhaustive here — n <= 8 — i.e. the same
    ground truth as ``tests/oracle.py``) finds the true optimum.  The
    sweep replays both plans through the same scalar simulator:

      * never worse — at every (budget, PCIe, overlap) point where the
        greedy plan fits, the solved plan fits at overhead <= greedy's
        (greedy competes as a candidate, so this holds by construction
        — the bench validates the construction);
      * strictly better — at the tight-budget points the solved plan's
        simulated step overhead beats greedy's outright;
      * dp == exhaustive — the chain DP reproduces the brute-force
        optimum at every point (the oracle property, on real collected
        vectors rather than randomized ones).
    """
    cfg = get_config("gemma3_12b").reduced(
        num_layers=6, d_model=128, d_ff=256, vocab_size=512,
        dtype="float32", sliding_window=64, global_interval=2)
    lm = build_model(cfg, attn_impl="flash")
    params = lm.init(jax.random.PRNGKey(0))
    B, S = 4, 512
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    fixed = fixed_train_bytes(params)
    candidate_ks = (1, 2, 4)
    accum = 5e-4

    vecs = {}
    for k in candidate_ks:
        Bk = -(-B // k)
        probe = {key: v[:Bk] for key, v in batch.items()}
        col = ShuttlingCollector(lm).collect(params, probe)
        vecs[k] = {"est_mem": col.activation_vector(),
                   "output_bytes": col.output_vector(),
                   "offload_bytes": col.offloadable_vector(),
                   "flops": col.flops_vector()}

    def vectors_of_k(k):
        return vecs[k]

    act1 = vecs[1]["est_mem"]

    def replay(plan, pcie, overlap):
        v = vecs[plan.microbatch]
        return simulate(v["est_mem"], plan.actions, fixed,
                        v["output_bytes"], v["flops"],
                        offload_bytes=v["offload_bytes"],
                        pcie_bytes_per_s=pcie, overlap=overlap,
                        microbatch=plan.microbatch,
                        accum_overhead_s=accum)

    # (budget multiplier on act.sum(), pcie GB/s, overlap) — the tight
    # points are where the greedy's one-action-at-a-time densities
    # misprice the local/global recompute gap
    points = [(0.09, 4.0, 0.75), (0.35, 28.0, 0.95)]
    if not smoke:
        points += [(0.09, 24.0, 0.75), (0.60, 16.0, 0.5),
                   (0.90, 16.0, 0.5)]
    res = {"arch": cfg.name, "units": lm.num_plan_units(),
           "candidate_ks": list(candidate_ks), "sweep": {}}
    for m, pcie_g, ov in points:
        pcie = pcie_g * 1e9
        budget = fixed + m * float(act1.sum())
        g = greedy_plan_adaptive(vectors_of_k, budget, fixed,
                                 candidate_ks=list(candidate_ks),
                                 pcie_bytes_per_s=pcie,
                                 offload_overlap=ov,
                                 accum_overhead_s=accum)
        gs = replay(g, pcie, ov)
        r_ex = solve(vectors_of_k, budget, fixed,
                     candidate_ks=list(candidate_ks),
                     pcie_bytes_per_s=pcie, offload_overlap=ov,
                     accum_overhead_s=accum, method="exhaustive")
        r_dp = solve(vectors_of_k, budget, fixed,
                     candidate_ks=list(candidate_ks),
                     pcie_bytes_per_s=pcie, offload_overlap=ov,
                     accum_overhead_s=accum, method="dp",
                     include_greedy=False)
        greedy_fits = bool(gs.peak_bytes <= budget + 1e-6)
        row = {
            "budget_mult": m, "pcie_gbps": pcie_g, "overlap": ov,
            "greedy": {"overhead_us": round(gs.step_overhead_s * 1e6, 3),
                       "microbatch": g.microbatch, "fits": greedy_fits},
            "solved": {"overhead_us": round(r_ex.overhead_s * 1e6, 3),
                       "microbatch": r_ex.plan.microbatch
                       if r_ex.plan else 0,
                       "feasible": r_ex.feasible,
                       "solve_ms": round(r_ex.solve_s * 1e3, 3)},
            "dp_overhead_us": round(r_dp.overhead_s * 1e6, 3),
            "dp_matches_exhaustive":
                bool(r_dp.feasible == r_ex.feasible
                     and abs(r_dp.score - r_ex.score)
                     <= 1e-9 * max(abs(r_ex.score), 1e-12)),
            "never_worse": bool((not greedy_fits)
                                or (r_ex.feasible and r_ex.overhead_s
                                    <= gs.step_overhead_s + 1e-12)),
            "strict_win": bool(greedy_fits and r_ex.feasible
                               and r_ex.overhead_s
                               < gs.step_overhead_s * (1.0 - 1e-9)),
        }
        if row["strict_win"]:
            row["improvement_pct"] = round(
                100.0 * (1.0 - r_ex.overhead_s / gs.step_overhead_s), 2)
        res["sweep"][f"m{m}_pcie{pcie_g}_ov{ov}"] = row
    return res


def bench_offload_exec(smoke: bool) -> dict:
    """(j) real overlapped offload, MEASURED — not simulated.

    A synthetic n-unit matmul chain where each unit's backward needs a
    d x d residual the forward produced.  Two executions of the SAME
    math (final gradients compared bitwise-close):

      * offload — the residual streams to host on the TransferLane
        right after the forward dispatches the next unit, and streams
        back (prefetched one unit ahead) behind the backward's compute:
        the double-buffered path the trainer's OFFLOAD_OPT choreography
        uses.
      * remat  — the residual is discarded and the backward re-runs the
        unit's forward chain to regenerate it (keeping only the unit's
        boundary input, exactly what a REMAT action keeps on device).

    The point is transfer-bound by construction: the recompute chain
    costs r heavy matmuls per unit while the residual is ~1 d^2 buffer,
    so hidden transfer must beat recompute on wall-clock.  The second
    gate holds the lane's measured exposed time to the simulator's
    zero-overlap exposure evaluated at the bandwidth the step actually
    achieved — i.e. the lane's own measured copy wall time (``copy_s``,
    == bytes / realised GB/s) — with a x1.5 + 5 ms tolerance band
    (documented in docs/ARCHITECTURE.md "Real overlapped offload").  A
    caller can wait each copy out at most once, so exposure above the
    band means the accounting broke (double-charged waits), not just a
    slow link; below it is overlap doing its job.  The idle-link
    calibration (``measure_pcie_gbps``) is reported alongside as a
    ``contention_factor`` — ~1 on hosts with a real DMA engine, large
    on this CPU container where copies and compute share cores.
    """
    from repro.train.transfer import TransferLane, measure_pcie_gbps

    d = 256 if smoke else 384        # residual is one d x d f32 buffer
    r = 4                            # matmuls per unit chain (recompute)
    n = 4 if smoke else 6            # units
    reps = 2 if smoke else 3
    scale = np.float32(1.0 / np.sqrt(d))
    W = jax.random.normal(jax.random.PRNGKey(0), (d, d), jnp.float32) * scale
    h0 = jax.random.normal(jax.random.PRNGKey(1), (d, d), jnp.float32)

    @jax.jit
    def chain(h):                     # the unit's heavy forward
        z = h
        for _ in range(r):
            z = jnp.tanh(z @ W)
        return z

    @jax.jit
    def boundary(z):                  # unit output handed to unit i+1
        return jnp.tanh(z @ W)

    @jax.jit
    def unit_bwd(z, g):               # backward consumes the residual
        for _ in range(r):
            g = jnp.tanh(g @ W.T) + z * np.float32(1e-3)
        return g

    def run_offload(lane):
        handles = []
        h = h0
        for _ in range(n):
            z = chain(h)
            h = boundary(z)           # next unit dispatches async...
            handles.append(lane.offload(z))   # ...the copy rides behind it
        g = jnp.ones_like(h)
        pre = list(handles)
        pre[n - 1] = lane.prefetch(handles[n - 1])
        for i in reversed(range(n)):
            if i > 0:                 # start the next return copy early
                pre[i - 1] = lane.prefetch(handles[i - 1])
            z = lane.fetch(pre[i])
            g = unit_bwd(z, g)
        jax.block_until_ready(g)
        lane.drain()
        return g

    def run_remat():
        ins = []                      # REMAT keeps only boundary inputs
        h = h0
        for _ in range(n):
            ins.append(h)
            z = chain(h)
            h = boundary(z)
        g = jnp.ones_like(h)
        for i in reversed(range(n)):
            z = chain(ins[i])         # regenerate the residual: recompute
            g = unit_bwd(z, g)
        jax.block_until_ready(g)
        return g

    # warm-up: compile both paths + first-touch the lane's worker thread
    warm_lane = TransferLane()
    g_off = run_offload(warm_lane)
    warm_lane.close()
    g_rm = run_remat()
    results_match = bool(np.allclose(np.asarray(g_off), np.asarray(g_rm),
                                     rtol=1e-5, atol=1e-5))

    best_off, best_exposed, best_copy, moved = float("inf"), 0.0, 0.0, 0.0
    for _ in range(reps):
        lane = TransferLane()
        t0 = time.perf_counter()
        run_offload(lane)
        dt = time.perf_counter() - t0
        st = lane.reset_stats()
        lane.close()
        if dt < best_off:
            best_off = dt
            best_exposed = float(st["exposed_s"])
            best_copy = float(st["copy_s"])
            moved = float(st["bytes_out"] + st["bytes_in"])
    best_rm = _time_best(run_remat, (), reps)

    # simulator-side bound at the bandwidth the step ACTUALLY achieved:
    # at zero overlap every copy's wall time is exposed, and a caller
    # can wait each copy out at most once, so measured exposure must sit
    # inside [0, 1.5 x copy_s + 5 ms] — above the band the exposure
    # accounting double-charged waits.  The idle-link calibration is
    # reported as a contention factor, not gated on: without a DMA
    # engine (CPU containers) contended copies run far below idle
    # bandwidth, while on real accelerators copy_s ~= bytes/pcie and
    # this band collapses onto the bandwidth model.
    tol_s = 1.5 * best_copy + 5e-3
    cal = measure_pcie_gbps(size_mb=4 if smoke else 16, repeats=2)
    idle_round_trip_s = moved / (cal["pcie_gbps"] * 1e9)
    return {
        "units": n, "chain_matmuls": r, "residual_bytes": d * d * 4,
        "results_match": results_match,
        "offload_step_s": round(best_off, 6),
        "remat_step_s": round(best_rm, 6),
        "speedup": round(best_rm / max(best_off, 1e-12), 4),
        "bytes_moved": int(moved),
        "measured_exposed_s": round(best_exposed, 6),
        "measured_copy_s": round(best_copy, 6),
        "tolerance_s": round(tol_s, 6),
        "exposed_within_tolerance": bool(0.0 <= best_exposed <= tol_s),
        "overlap_measured": round(
            max(0.0, 1.0 - best_exposed / max(best_copy, 1e-12)), 4),
        "idle_round_trip_s": round(idle_round_trip_s, 6),
        "contention_factor": round(
            best_copy / max(idle_round_trip_s, 1e-12), 2),
        "calibrated_pcie_gbps": cal["pcie_gbps"],
        "pinned_host": cal["pinned_host"],
    }


def bench_serve(smoke: bool) -> dict:
    """(k) continuous-batching serve engine vs sequential generation.

    One deterministic open-loop trace (``repro.data.trace.gen_trace``,
    the same generator the serve tests use) is served twice at equal
    HBM budget:

      * engine     — ``ServeEngine``: bucketed cache pools, input-aware
                     admission, batched multi-token decode;
      * sequential — the old path: one ``generate()`` per request in
                     arrival order, cache bucketed to the same quantum
                     so both paths compile the same geometry family.

    Both paths run twice; the second (warm — every executable cached on
    the LM) pass is timed, so the comparison is steady-state serving
    throughput, not XLA compile time.  Alongside throughput:

      * admission  — the engine's predicted peak HBM must stay under
                     the budget AND bound the actual allocated peak
                     (admit-before-allocate is only safe if the
                     prediction is conservative);
      * estimator  — per-slot cache bytes predicted for buckets the
                     estimator never sampled vs the exact eval_shape
                     truth (relative error);
      * compiles   — decode geometries seen vs the O(#buckets x #tiers)
                     bound and vs #requests (continuous batching must
                     NOT compile per request).
    """
    from repro.data.trace import gen_trace
    from repro.train.engine import ServeEngine, cache_leaf_bytes
    from repro.train.serve import generate

    cfg = get_config("bert_base_paper").reduced(
        num_layers=2, d_model=96 if smoke else 128,
        d_ff=192 if smoke else 256, vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))

    quantum, max_slots = 32, 4
    n_req = 8 if smoke else 16
    new_tok = 8 if smoke else 16
    hbm = 64e6
    # burst trace (all arrive at t=0): throughput is service-bound, so
    # the engine/sequential comparison measures batching, not idle time
    trace = gen_trace(num_requests=n_req, vocab_size=cfg.vocab_size,
                      rate_rps=0.0, max_new_tokens=new_tok,
                      prompt_scale=0.25, seed=7)

    def run_engine():
        eng = ServeEngine(lm, params, hbm_bytes=hbm, quantum=quantum,
                          max_slots=max_slots, prefill_chunk=16,
                          decode_steps=4)
        return eng, eng.run(trace)

    def run_sequential():
        t0 = time.perf_counter()
        outs, total = {}, 0
        for r in trace:
            bucket = -(-(len(r.prompt) + r.max_new_tokens)
                       // quantum) * quantum
            out = generate(lm, params, jnp.asarray(r.prompt[None, :]),
                           r.max_new_tokens, cache_len=bucket)
            outs[r.rid] = np.asarray(out)[0]
            total += out.shape[1]
        jax.block_until_ready(out)
        return outs, total, time.perf_counter() - t0

    eng_cold, res_cold = run_engine()          # compile pass
    run_sequential()
    eng, res = run_engine()                    # warm: executables cached
    seq_outs, seq_tokens, seq_wall = run_sequential()

    outputs_match = all(
        np.array_equal(seq_outs[r.rid], np.asarray(res.outputs[r.rid]))
        for r in trace)

    # estimator accuracy on buckets it never sampled (warm-fit uses
    # quantum * {1, 3, 5}): predicted per-slot bytes vs eval_shape truth
    errs = []
    for bucket in (2 * quantum, 4 * quantum, 8 * quantum):
        truth = float(cache_leaf_bytes(lm, bucket).sum())
        errs.append(abs(eng.slot_bytes(bucket) - truth) / truth)

    n_buckets = len({eng.bucket_of(r) for r in trace})
    decode_geoms = res.compile_counts.get("decode", 0)
    eng_tps = res.total_tokens / res.wall_s
    seq_tps = seq_tokens / seq_wall
    return {
        "requests": n_req, "new_tokens": new_tok, "quantum": quantum,
        "max_slots": max_slots, "hbm_budget_mb": hbm / 1e6,
        "engine": res.summary(),
        "cold_wall_s": round(res_cold.wall_s, 4),
        "sequential_wall_s": round(seq_wall, 4),
        "sequential_tokens_per_s": round(seq_tps, 1),
        "engine_tokens_per_s": round(eng_tps, 1),
        "speedup_vs_sequential": round(eng_tps / seq_tps, 3),
        "outputs_match_sequential": bool(outputs_match),
        "peak_predicted_bytes": int(res.stats["peak_predicted_bytes"]),
        "peak_actual_bytes": int(res.stats["peak_actual_bytes"]),
        "budget_bytes": int(hbm),
        "estimator_max_rel_err": round(max(errs), 5),
        "buckets_seen": n_buckets,
        "decode_geometries": decode_geoms,
        "decode_geometry_bound": n_buckets * len(eng.tiers),
    }


def bench_telemetry(smoke: bool) -> dict:
    """(l) telemetry overhead + disabled-path identity.

    Runs the SAME training loop twice from the same initial params:
    once with ``Telemetry.disabled()`` (the default everywhere) and
    once with every surface on — structured events, span tracing, and
    all three file sinks.  The two loops are *interleaved* step-by-step
    so machine noise (frequency scaling, neighbours on a CI runner)
    hits both modes alike, and the comparison uses the **min** warm
    step time: noise only ever adds time, so the min is the clean
    estimate of intrinsic per-step cost.  Two acceptance gates read
    this point:

    * full telemetry costs <= 2% of the warm step time (min of the
      warm steps, compile excluded);
    * the disabled path is bitwise identical: the two loss trajectories
      match float-for-float, so telemetry can never change training.
    """
    cfg = get_config("bert_base_paper").reduced(
        num_layers=2 if smoke else 4, d_model=128, d_ff=256,
        vocab_size=512, dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    B, S = 4, 128 if smoke else 256
    steps = 8 if smoke else 16
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}

    def make(telemetry):
        planner = MimosePlanner(lm, 1e18, quantum=64, warmup_samples=1)
        tr = Trainer(lm, planner, AdamW(lr=1e-3), telemetry=telemetry)
        p = jax.tree_util.tree_map(jnp.copy, params)
        return {"tr": tr, "p": p, "opt": tr.optimizer.init(p),
                "losses": [], "times": []}

    tmp = tempfile.mkdtemp(prefix="bench_obs_")
    tel = build_telemetry(metrics_path=os.path.join(tmp, "metrics.json"),
                          events_path=os.path.join(tmp, "events.jsonl"),
                          trace_path=os.path.join(tmp, "trace.json"))
    modes = [make(Telemetry.disabled()), make(tel)]
    for _ in range(steps):
        for st in modes:              # interleaved: noise hits both alike
            t0 = time.perf_counter()
            st["p"], st["opt"], loss = st["tr"].step(
                st["p"], st["opt"], dict(batch))
            st["times"].append(time.perf_counter() - t0)
            st["losses"].append(float(loss))
    losses_off, t_off = modes[0]["losses"], modes[0]["times"]
    losses_on, t_on = modes[1]["losses"], modes[1]["times"]
    n_spans = len([e for e in tel.tracer.events() if e.get("ph") == "X"])
    flush_telemetry(tel)
    n_events = sum(1 for _ in open(os.path.join(tmp, "events.jsonl")))

    # min of the warm steps: step 0 compiles, step 1 still touches cold
    # caches — both excluded; min, not median, because noise is strictly
    # additive and the gate measures intrinsic cost, not runner load
    off = float(np.min(t_off[2:]))
    on = float(np.min(t_on[2:]))
    return {
        "steps": steps,
        "warm_step_off_s": round(off, 6),
        "warm_step_on_s": round(on, 6),
        "overhead_ratio": round(max(on - off, 0.0) / off, 6),
        "losses_bitwise_identical": losses_on == losses_off,
        "trace_spans": n_spans,
        "event_records": n_events,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI (<1 min)")
    ap.add_argument("--out", default="BENCH_engine.json")
    args = ap.parse_args(argv)

    report = {
        "smoke": args.smoke,
        "scheduler": bench_scheduler(args.smoke),
        "collector": bench_collector(args.smoke),
        "engine": bench_engine(args.smoke),
        "sharded": bench_sharded(args.smoke),
        "ragged": bench_ragged(args.smoke),
        "remat_cost": bench_remat_cost(args.smoke),
        "hybrid": bench_hybrid(args.smoke),
        "microbatch": bench_microbatch(args.smoke),
        "solver": bench_solver(args.smoke),
        "offload_exec": bench_offload_exec(args.smoke),
        "serve": bench_serve(args.smoke),
        "telemetry": bench_telemetry(args.smoke),
    }
    sched96 = report["scheduler"]["units_96"]
    coll = report["collector"]
    eng = report["engine"]
    shd = report["sharded"]
    rag50 = report["ragged"]["sweep"]["pad_50pct"]
    rc = report["remat_cost"]["budgets"]
    hyb = report["hybrid"]
    mb = report["microbatch"]
    sv = report["solver"]["sweep"]
    ox = report["offload_exec"]
    srv = report["serve"]
    report["acceptance"] = {
        "compile_count_bounded_by_buckets":
            eng["mimose"]["compiles"] <= eng["mimose"]["buckets_seen"]
            and eng["mimose"]["compiles"] < eng["distinct_raw_shapes"],
        "collection_speedup_ge_5x": coll["speedup"] >= 5.0,
        "scheduler_faster_than_seed_96_units": sched96["speedup"] > 1.0,
        "sharded_fits_where_single_device_cannot":
            shd["single_device_infeasible"] and shd["sharded_fit_per_device"],
        # masked kernels win back >= half the padding throughput loss:
        # gated on the executed-work numbers (deterministic, and
        # bench_ragged asserts the masked executables reproduce the
        # ideal runs, so they describe real kernel behaviour) for both
        # kernels.  The flash wall-clock term is a regression tripwire
        # at a threshold below 0.5 on purpose: CPU interpret emulation
        # pays per-grid-cell overhead a TPU doesn't, and shared CI
        # runners add noise (this container measures ~0.84) — a masked
        # kernel that stopped skipping would read ~0.
        "ragged_recovers_half_loss_at_50pct_pad":
            all(rag50[k]["modeled_recovered"] >= 0.5
                for k in ("flash", "ssd"))
            and rag50["flash"]["measured_recovered"] >= 0.25,
        # cost-aware never recomputes longer than byte-only, is strictly
        # faster somewhere, and every plan stays per-device feasible
        "cost_aware_reduces_recompute_time":
            all(r["cost_aware"]["recompute_time_us"]
                <= r["byte_only"]["recompute_time_us"]
                and r["cost_aware"]["fits_budget"]
                and r["byte_only"]["fits_budget"]
                for r in rc.values())
            and any(r["time_reduction"] > 0 for r in rc.values()),
        # a budget no boolean remat mask can fit is feasible hybrid-only
        "hybrid_fits_below_remat_only_floor":
            not hyb["below_remat_floor"]["any_bool_plan_fits"]
            and hyb["below_remat_floor"]["hybrid_fits"]
            and hyb["below_remat_floor"]["n_offload"] > 0,
        # the floor property: at every equal (remat-feasible) budget the
        # hybrid plan's simulated step overhead is <= remat-only's
        "hybrid_never_worse_at_equal_budget":
            all(r["hybrid"]["fits"] and r["remat_only"]["fits"]
                and r["hybrid"]["overhead_us"]
                <= r["remat_only"]["overhead_us"] + 1e-6
                for r in hyb["equal_budget"].values()),
        # with the transfer fully overlapped, offload beats recompute
        "hybrid_wins_when_transfer_overlapped":
            hyb["overlapped_transfer"]["both_fit"]
            and hyb["overlapped_transfer"]["hybrid_overhead_us"]
            < hyb["overlapped_transfer"]["remat_only_overhead_us"],
        # a budget below the bucket's k=1 global-minimum footprint
        # (exhaustive over every action plan) is feasible only by
        # splitting the batch — k=2 gradient accumulation fits it
        "microbatch_fits_below_k1_floor":
            not mb["below_k1_floor"]["any_k1_plan_fits"]
            and mb["below_k1_floor"]["adaptive_fits"]
            and mb["below_k1_floor"]["chosen_microbatch"] == 2,
        # the floor property: k=1 always competes, so at every equal
        # (k=1-feasible) budget the adaptive planner's simulated step
        # overhead never exceeds the k=1 planner's
        "microbatch_never_worse_at_equal_budget":
            all(r["k1"]["fits"] and r["adaptive"]["fits"]
                and r["adaptive"]["overhead_us"]
                <= r["k1"]["overhead_us"] + 1e-6
                for r in mb["equal_budget"].values()),
        # the solver tier: never worse than greedy at any swept point,
        # strictly better on the PR-5 heterogeneous hybrid point, and
        # the chain DP reproduces the exhaustive (oracle) optimum
        "solver_never_worse_than_greedy":
            all(r["never_worse"] for r in sv.values()),
        "solver_strictly_beats_greedy_somewhere":
            any(r["strict_win"] for r in sv.values()),
        "solver_dp_matches_exhaustive":
            all(r["dp_matches_exhaustive"] for r in sv.values()),
        # MEASURED, not simulated: at the transfer-bound point the
        # double-buffered offload execution beats rematerialisation on
        # wall-clock (same math both ways — gated on the outputs
        # matching too)
        "measured_offload_beats_remat_only":
            ox["results_match"]
            and ox["offload_step_s"] < ox["remat_step_s"],
        # and the lane's measured exposed transfer stays inside the
        # simulator's zero-overlap bound at the realised bandwidth —
        # the lane's own copy wall time (x1.5 + 5 ms band)
        "measured_transfer_within_tolerance":
            ox["exposed_within_tolerance"],
        # continuous batching strictly beats one-generate-per-request
        # at equal HBM budget (warm pass both ways), token-for-token
        # identical outputs
        "serve_engine_beats_sequential":
            srv["outputs_match_sequential"]
            and srv["speedup_vs_sequential"] > 1.0,
        # admit-before-allocate safety: the admission ledger's peak
        # prediction bounds the actual allocated peak AND the budget —
        # zero admission OOMs by construction
        "serve_admission_within_budget":
            srv["peak_actual_bytes"] <= srv["peak_predicted_bytes"]
            <= srv["budget_bytes"],
        # the estimator's per-slot cache-bytes prediction tracks the
        # eval_shape ground truth on buckets it never sampled
        "serve_predicted_tracks_actual":
            srv["estimator_max_rel_err"] <= 0.05,
        # compile-once under serving: decode geometries bounded by
        # #buckets x #slot-tiers, and NOT one per request
        "serve_decode_compiles_bounded_by_buckets":
            srv["decode_geometries"] <= srv["decode_geometry_bound"]
            and srv["decode_geometries"] < srv["requests"],
        # full telemetry (events + spans + file sinks) costs <= 2% of
        # warm step time, and spans/events were actually recorded (the
        # cheap way to pass an overhead gate is to record nothing)
        "telemetry_overhead_le_2pct":
            report["telemetry"]["overhead_ratio"] <= 0.02
            and report["telemetry"]["trace_spans"] > 0
            and report["telemetry"]["event_records"] > 0,
        # telemetry off (the default) is bitwise identical to the
        # instrumented build: the loss trajectories match exactly
        "telemetry_disabled_bitwise_identical":
            report["telemetry"]["losses_bitwise_identical"],
    }

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(f"\nwrote {args.out}")
    ok = all(report["acceptance"].values())
    print("acceptance:", "PASS" if ok else "FAIL", report["acceptance"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
