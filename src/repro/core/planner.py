"""MimosePlanner — the input-aware checkpointing planner (paper §4).

Ties together the shuttling collector, the lightning estimator, the
responsive scheduler and the plan cache:

    planner = MimosePlanner(lm, budget_bytes=6 << 30)
    mask, info = planner.plan(params, batch)     # < 1 ms after warm-up
    loss, _ = lm.loss(params, batch, remat_mask=mask)

Phases (paper §4.1):
  * sheltered execution — while the estimator has fewer than
    ``warmup_samples`` distinct input sizes, each new size triggers the
    collector (the measured bytes are used directly for that iteration's
    plan, so training proceeds under budget from step one);
  * responsive execution — the estimator predicts per-unit bytes for any
    size, the greedy scheduler emits a plan in O(n log n), and the plan
    cache keyed by quantised input size makes repeats free.

Cost-aware selection (default): every plan is scored on bytes freed per
recompute-FLOP using the ``launch/roofline.py`` per-unit cost model, so
the scheduler rematerialises cheap MLP/SSM units before FLOP-heavy
attention units that free the same bytes — and never does worse than the
paper's byte-only Algorithm 1 (``cost_aware=False`` restores it).

Sharding-aware mode: pass ``mesh_budget=MeshBudget.from_shape(...)`` and
every quantity above becomes *per-device* — the collector divides each
activation leaf by its PartitionSpec divisor, the estimator fits
per-device bytes, the fixed bytes are the param/grad/optimizer *shards*
(ZeRO-1 aware), and the scheduler plans against
``mesh_budget.hbm_per_device_bytes``.  Plan-cache keys embed the mesh
signature so plans never leak across mesh shapes.

Hybrid remat+offload mode (``offload=True``): plans become typed action
tuples (``repro.actions.Action``) and every unit may also be OFFLOADed
to pinned host memory — priced at the ``pcie_gbps`` link with
``offload_overlap`` of the traffic hidden under compute.  Two extra
estimators (same PolyEstimator machinery) track the per-unit boundary
and offloadable byte vectors the hybrid scheduler needs.  All planners
return ``Plan.as_actions()`` now; a plan with no OFFLOAD unit is
value-identical to the old bool mask (``KEEP == 0 == False``,
``REMAT == 1 == True``).

Adaptive microbatching (``max_microbatches > 1``): the candidate search
additionally spans the gradient-accumulation split factor ``k`` per
bucket — the per-unit byte vectors at split ``k`` come straight from
the PolyEstimator fits evaluated at input size ``s/k`` (or an abstract
collection on the split geometry during sheltered execution), and the
``(k, action-plan)`` pair with the lowest simulated step overhead wins
(``scheduler.greedy_plan_adaptive``).  ``k = 1`` always competes, so
enabling microbatching never loses at equal budget; plan-cache keys
grow the ``max_microbatches`` component so plans never leak across
knob settings, and ``Plan.microbatch`` tells the trainer to execute
the step as ``k`` accumulated microbatches
(``repro.train.accumulate``).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import LRUCache
from repro.core.collector import ShuttlingCollector, input_size_of, _tree_bytes
from repro.core.estimator import PolyEstimator
from repro.obs import StatsView, Telemetry, TRACK_PLANNER
from repro.core.scheduler import (Plan, escalate_plan, greedy_plan,
                                  greedy_plan_adaptive)
from repro.core.solver import BackgroundSolver, SolveRequest
from repro.data.pipeline import bucket_length
from repro.launch.roofline import MICROBATCH_OVERHEAD_S, plan_unit_flops
from repro.models.lm import LM
from repro.sharding.budget import MeshBudget, fixed_train_bytes_per_device


def fixed_train_bytes(params, optimizer: str = "adamw",
                      grad_dtype_bytes: Optional[int] = None) -> int:
    """Resident bytes independent of input size: params + grads + opt state."""
    pb = _tree_bytes(params)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    gb = pb if grad_dtype_bytes is None else n_params * grad_dtype_bytes
    ob = 2 * 4 * n_params if optimizer == "adamw" else 0   # fp32 m + v
    return pb + gb + ob


@dataclasses.dataclass
class PlanInfo:
    input_size: int
    quantized_size: int
    cache_hit: bool
    collected: bool
    plan: Plan
    estimate_time_s: float = 0.0
    schedule_time_s: float = 0.0
    collect_time_s: float = 0.0
    # bytes of the blocked output head and loss at this bucket, which
    # the plan's peak holds on top of the fixed bytes (0: not counted)
    head_bytes: float = 0.0


class PlannerBase:
    name = "base"
    telemetry: Optional[Telemetry] = None
    quantum: int = 1          # batch geometry granularity (1 = no bucketing)
    mesh_budget: Optional[MeshBudget] = None
    fixed_bytes: Optional[float] = None
    shard_divisor: int = 1    # legacy scalar activation ways (global mode)
    # hybrid remat+offload knobs (set via _init_hybrid; off by default)
    offload: bool = False
    pcie_gbps: float = 16.0
    offload_overlap: float = 0.5
    # optimizer-state offload (ZeRO-Offload style): let the scheduler
    # park a unit's fp32 AdamW moments on the host for the whole step
    opt_offload: bool = False
    _opt_vector = None        # cached: moment bytes are input-independent
    # adaptive microbatching: largest gradient-accumulation split the
    # planner may pick per bucket (1 = plain full-batch steps), and the
    # fixed per-extra-microbatch cost it prices the split at
    max_microbatches: int = 1
    microbatch_overhead_s: float = MICROBATCH_OVERHEAD_S

    def plan(self, params, batch) -> Tuple[tuple, PlanInfo]:
        """Returns ``(Plan.as_actions(), PlanInfo)`` — a typed action
        tuple; bool-mask consumers keep working because KEEP/REMAT are
        value-identical to False/True."""
        raise NotImplementedError

    # -- observability (repro.obs) ---------------------------------------
    def bind_telemetry(self, telemetry: Telemetry) -> None:
        """Re-home this planner's metrics into ``telemetry``'s registry.

        Called by the trainer (and the serve engine) so every component
        of a run shares ONE registry: same-named metrics merge, which is
        exactly how planner and watchdog converge on a single
        ``train_oom_events`` counter instead of double-booking."""
        self.telemetry = telemetry
        st = getattr(self, "stats", None)
        if isinstance(st, StatsView):
            st.attach(telemetry.metrics)

    # -- OOM-watchdog hooks (repro.train.resilience) ---------------------
    def record_oom(self, bucket: int) -> None:
        """Book a device-OOM (real or injected) against ``bucket`` in
        ``stats`` — a planner without a stats mapping just drops it.

        NOTE: when the planner shares a registry with an
        ``OOMWatchdog`` (the trainer binds both), the watchdog's
        ``on_oom`` bumps the SAME ``train_oom_events`` counter — call
        one or the other per OOM, never both."""
        st = getattr(self, "stats", None)
        if isinstance(st, StatsView):
            st.inc("oom_events", bucket=bucket)
        elif isinstance(st, dict):
            st["oom_events"] = st.get("oom_events", 0) + 1
            by = st.setdefault("oom_by_bucket", {})
            by[bucket] = by.get(bucket, 0) + 1

    def escalate(self, params, batch) -> bool:
        """Replace the cached plan for this batch's bucket with a more
        memory-aggressive one (DTR-style recovery after an OOM).  The
        base planner cannot — only planners with an online estimator
        implement the ladder; returning False tells the watchdog to
        re-raise instead of retrying."""
        return False

    # -- shared mesh-vs-global accounting (one implementation for the
    # Mimose planner and both baselines, so their byte accounting can
    # never drift apart) --------------------------------------------------
    def resolve_budget_bytes(self, budget_bytes: Optional[float]) -> float:
        """The planning budget: explicit bytes win (interpreted
        per-device when a mesh budget is set), else the budget's HBM."""
        if budget_bytes is None:
            if self.mesh_budget is None:
                raise ValueError("pass budget_bytes or mesh_budget")
            budget_bytes = self.mesh_budget.hbm_per_device_bytes
        return float(budget_bytes)

    def collected_vector(self, res) -> np.ndarray:
        """The byte vector planning runs on: per-device when sharding-
        aware, global otherwise."""
        return (res.device_activation_vector()
                if self.mesh_budget is not None
                else res.activation_vector())

    def collected_output_vector(self, res) -> np.ndarray:
        """Boundary-tensor bytes per unit, in the same (per-device or
        global) frame as ``collected_vector``."""
        return (res.device_output_vector()
                if self.mesh_budget is not None
                else res.output_vector())

    def collected_offload_vector(self, res) -> np.ndarray:
        """Offloadable residual bytes per unit, same frame as above."""
        return (res.device_offloadable_vector()
                if self.mesh_budget is not None
                else res.offloadable_vector())

    def collected_opt_vector(self, res) -> np.ndarray:
        """Optimizer-moment bytes per unit (fp32 AdamW m+v), same frame
        as above.  Input-size independent — pure parameter-shape math."""
        return (res.device_opt_vector()
                if self.mesh_budget is not None
                else res.opt_vector())

    def planning_flops(self, flops):
        """Recompute-cost vector in the SAME frame as the byte vectors:
        per-device under a mesh budget (SPMD divides every unit's
        recompute across the chips), global otherwise.  Remat-only
        selection is scale-invariant so the frame never mattered before,
        but the hybrid path compares recompute seconds against
        per-device PCIe transfer seconds — mixed frames would inflate
        remat cost by n_devices and over-offload."""
        if flops is None or self.mesh_budget is None:
            return flops
        return np.asarray(flops, dtype=np.float64) / self.mesh_budget.n_devices

    # -- shared hybrid remat+offload state (Mimose + Sublinear) ----------
    def _init_hybrid(self, *, offload: bool, pcie_gbps: float,
                     offload_overlap: float, cost_aware: bool,
                     degree: int, min_samples: int,
                     opt_offload: bool = False) -> None:
        """One implementation of the offload knobs + the two extra
        per-unit fits (boundary and offloadable bytes) the hybrid
        scheduler needs, so the planners cannot drift apart."""
        if offload and not cost_aware:
            raise ValueError("offload=True needs cost_aware=True: the "
                             "hybrid selection compares remat FLOPs "
                             "against transfer time")
        if opt_offload and not offload:
            raise ValueError("opt_offload=True needs offload=True: "
                             "moment parking rides the same host link "
                             "and link pricing as residual offload")
        self.offload = offload
        self.opt_offload = opt_offload
        self.pcie_gbps = pcie_gbps
        self.offload_overlap = offload_overlap
        self.est_output = PolyEstimator(degree, min_samples=min_samples)
        self.est_offload = PolyEstimator(degree, min_samples=min_samples)
        # NOT an estimator: moment bytes depend only on the parameter
        # shapes, so the first collection pins the vector exactly (and
        # the snapshot estimator dict keeps its three-key format)
        self._opt_vector = None

    def _feed_hybrid_estimators(self, s: int, res) -> None:
        self.est_output.add_sample(s, self.collected_output_vector(res))
        self.est_offload.add_sample(s, self.collected_offload_vector(res))
        if self._opt_vector is None:
            v = self.collected_opt_vector(res)
            if v is not None and len(v):
                self._opt_vector = np.asarray(v, dtype=np.float64)

    def _hybrid_vectors(self, size: int, res=None):
        """Boundary/offloadable byte vectors in the planning frame —
        exact from a collection when ``res`` is given, predicted
        otherwise.  ``None`` when offload is disabled."""
        if not self.offload:
            return None
        div = self.activation_divisor_scalar()
        out_v = (self.collected_output_vector(res) if res is not None
                 else self.est_output.predict(size))
        off_v = (self.collected_offload_vector(res) if res is not None
                 else self.est_offload.predict(size))
        return out_v / div, off_v / div

    def _opt_bytes_planning(self):
        """The moment-bytes vector in the planning frame, or ``None``
        when optimizer offload is off / not yet pinned.  The per-device
        frame is already divided by the mesh moment sharding, so only
        the legacy scalar divisor applies here."""
        if not self.opt_offload or self._opt_vector is None:
            return None
        cfg = getattr(getattr(self, "lm", None), "cfg", None)
        if cfg is not None and getattr(cfg, "remat_mode", "") == "scan":
            # scan-mode moments are stacked across a chunk's layers in
            # ONE leaf — parking a chunk cannot free a slice of a live
            # buffer, so the trainer could not realise the bytes the
            # plan would claim; don't offer the action
            return None
        return self._opt_vector / self.activation_divisor_scalar()

    def _hybrid_kwargs(self, size: int, res=None) -> dict:
        """The extra ``greedy_plan`` arguments for hybrid selection:
        the ``_hybrid_vectors`` plus the link pricing.  Empty when
        offload is disabled."""
        v = self._hybrid_vectors(size, res)
        if v is None:
            return {}
        d = dict(output_bytes=v[0],
                 offload_bytes=v[1],
                 pcie_bytes_per_s=self.pcie_gbps * 1e9,
                 offload_overlap=self.offload_overlap)
        ov = self._opt_bytes_planning()
        if ov is not None:
            d["opt_bytes"] = ov
        return d

    def resolve_fixed_bytes(self, params) -> float:
        """Resident (input-independent) bytes, resolved lazily from the
        params: the per-device param/grad/optimizer shards under a mesh
        budget, the legacy global bytes / shard_divisor otherwise."""
        if self.fixed_bytes is None:
            if self.mesh_budget is not None:
                self.fixed_bytes = fixed_train_bytes_per_device(
                    params, self.mesh_budget,
                    scanned=self.lm.cfg.remat_mode == "scan")
            else:
                self.fixed_bytes = (fixed_train_bytes(params)
                                    / self.shard_divisor)
        return self.fixed_bytes

    def activation_divisor_scalar(self) -> int:
        """Mesh-aware vectors are already per-device; the legacy scalar
        divisor only applies in global mode."""
        return 1 if self.mesh_budget is not None else self.shard_divisor

    def bucket_key(self, batch) -> int:
        """The shared bucket id: quantised input size.  Batches padded to
        ``quantum`` (data layer or trainer) make this key align 1:1 with
        the jitted-step cache, so a repeated bucket never replans *or*
        recompiles — the engine's compile count is O(#buckets)."""
        return bucket_length(input_size_of(batch), self.quantum)

    def mesh_sig(self) -> tuple:
        """Mesh identity component of every cache key: () when planning
        for a single global budget, the MeshBudget signature otherwise.
        Plans (and jitted steps, via the trainer) built for one mesh
        shape must never be replayed under another."""
        return (self.mesh_budget.sig()
                if self.mesh_budget is not None else ())

    def plan_key(self, batch) -> tuple:
        """Full plan-cache key: (bucket id, mesh signature, microbatch
        ceiling, PCIe GB/s, offload overlap).  ``max_microbatches`` is
        part of the key so plans built under one microbatching knob are
        never replayed under another (the chosen ``k`` itself is plan
        *output*, carried by ``Plan.microbatch``); the roofline knobs
        are part of it so a background-solved plan priced at one link
        speed can never be resurrected — from the cache or a snapshot —
        after a ``--pcie-gbps`` / ``--offload-overlap`` change that
        would re-rank its actions."""
        return (self.bucket_key(batch), self.mesh_sig(),
                self.max_microbatches,
                round(float(self.pcie_gbps), 6),
                round(float(self.offload_overlap), 6))

    # -- shared adaptive-microbatching machinery -------------------------
    def candidate_microbatches(self, batch) -> list:
        """Candidate gradient-accumulation splits for this batch: every
        ``k`` in ``1..max_microbatches``, capped at the batch size (a
        split cannot produce more microbatches than there are rows)."""
        B = int(np.shape(batch["tokens"])[0])
        kmax = max(min(int(self.max_microbatches), B), 1)
        return list(range(1, kmax + 1))

    @staticmethod
    def pad_waste_s(batch, k: int, flops_mb) -> float:
        """Per-step time a non-divisor split wastes on batch-axis pad
        rows: ``split_batch`` pads ``B`` up to ``ceil(B/k)*k`` rows and
        the step computes a full forward+backward over them.  The
        per-microbatch flops vector is already priced at the padded
        ``ceil(B/k)``-row geometry, so the waste is its pad-row share
        across all ``k`` microbatches at the roofline (backward ~= 2x
        forward).  Zero when ``k`` divides ``B`` — the simulator's
        overhead model covers everything else, so divisor splits stay
        exactly the floor-property candidates."""
        from repro.launch.roofline import PEAK_FLOPS
        B = int(np.shape(batch["tokens"])[0])
        k = max(int(k), 1)
        rows = -(-B // k) * k
        if rows == B or flops_mb is None:
            return 0.0
        frac = (rows - B) / rows
        return frac * 3.0 * k * float(np.sum(flops_mb)) / PEAK_FLOPS

    @staticmethod
    def microbatch_probe(batch, k: int) -> dict:
        """The batch geometry of ONE microbatch at split ``k``: every
        entry's batch axis cut to ``ceil(B/k)`` rows.  Works on arrays
        and ``ShapeDtypeStruct`` batches alike (the abstract dry-run
        plans through here too) — only shapes matter downstream
        (collection is abstract, ``plan_unit_flops`` reads geometry).
        """
        B = int(np.shape(batch["tokens"])[0])
        Bk = max(-(-B // max(int(k), 1)), 1)

        def cut(v):
            if isinstance(v, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct((Bk,) + tuple(v.shape[1:]),
                                            v.dtype)
            return v[:Bk]

        return {key: cut(v) for key, v in batch.items()}


class NonePlanner(PlannerBase):
    """No checkpointing (the paper's PyTorch Baseline)."""
    name = "none"

    def __init__(self, lm: LM):
        self.lm = lm

    def plan(self, params, batch):
        n = self.lm.num_plan_units()
        p = Plan([False] * n, 0.0, 0.0, 0.0)
        s = input_size_of(batch)
        # report the real bucket id (not a hard-coded 0) so
        # launch/report.engine_report groups baseline runs by bucket
        return p.as_actions(), PlanInfo(s, self.bucket_key(batch), True,
                                        False, p)


class MimosePlanner(PlannerBase):
    name = "mimose"

    def __init__(self, lm: LM, budget_bytes: Optional[float] = None, *,
                 fixed_bytes: Optional[float] = None,
                 shard_divisor: int = 1,
                 mesh_budget: Optional[MeshBudget] = None,
                 quantum: int = 256,
                 degree: int = 2,
                 warmup_samples: int = 4,
                 bucket_tol: float = 0.10,
                 cost_aware: bool = True,
                 offload: bool = False,
                 opt_offload: bool = False,
                 pcie_gbps: float = 16.0,
                 offload_overlap: float = 0.5,
                 max_microbatches: int = 1,
                 microbatch_overhead_s: float = MICROBATCH_OVERHEAD_S,
                 max_plans: int = 256,
                 audit_every: int = 0,
                 audit_tol: float = 0.02,
                 escalate_shrink: float = 0.85,
                 solver: str = "off",
                 solver_budget_ms: float = 50.0,
                 telemetry: Optional[Telemetry] = None):
        self.lm = lm
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry.disabled()
        self.mesh_budget = mesh_budget
        self.budget_bytes = self.resolve_budget_bytes(budget_bytes)
        self.fixed_bytes = fixed_bytes          # resolved lazily from params
        self.shard_divisor = shard_divisor
        self.quantum = quantum
        self.warmup_samples = warmup_samples
        self.bucket_tol = bucket_tol
        # adaptive microbatching: the scheduler may split a bucket into
        # up to this many gradient-accumulation microbatches when that
        # beats (or alone fits) the budget
        self.max_microbatches = max(int(max_microbatches), 1)
        self.microbatch_overhead_s = microbatch_overhead_s
        # cost-aware selection (bytes freed per recompute-FLOP, floored
        # by the byte-only oracle); False = the paper's Algorithm 1
        self.cost_aware = cost_aware
        # hybrid remat+offload: let the scheduler also stream a unit's
        # residuals to pinned host memory, priced at the PCIe link (the
        # shared base helper also builds the two extra per-unit fits)
        self._init_hybrid(offload=offload, pcie_gbps=pcie_gbps,
                          offload_overlap=offload_overlap,
                          cost_aware=cost_aware, degree=degree,
                          min_samples=warmup_samples,
                          opt_offload=opt_offload)
        # adaptive-estimator extension (the paper's §4.3 future work):
        # every ``audit_every``-th unseen size, re-collect abstractly and
        # re-fit if the prediction drifted beyond ``audit_tol``.
        self.audit_every = audit_every
        self.audit_tol = audit_tol
        self.collector = ShuttlingCollector(lm, mesh_budget=mesh_budget)
        self.estimator = PolyEstimator(degree, min_samples=warmup_samples)
        # bounded: a long-tailed bucket distribution must not grow the
        # plan cache without limit (the jit-step cache is bounded too)
        self.cache = LRUCache(max_plans)
        # OOM recovery (repro.train.resilience): per-plan-key escalation
        # level, and the per-rung budget shrink that keeps each retry
        # strictly more aggressive than the last
        self.escalate_shrink = float(escalate_shrink)
        self._escalation: dict = {}
        # every (input size, batch geometry) the estimators were fed —
        # collection is abstract and shape-determined, so this log IS
        # the warmup state: a snapshot carries it and a restore onto a
        # different mesh replays it (eval_shape, zero FLOPs) instead of
        # re-paying the online warmup Mimose exists to avoid
        self._sample_log: list = []
        # stats (paper Table 2) + resilience counters (watchdog/restore)
        # + optimal-plan-tier counters (repro.core.solver) — a
        # dict-shaped view over the shared metrics registry, so one
        # store serves the legacy ``stats[...]`` call sites, Prometheus
        # export and the exit report alike
        self.stats = StatsView(
            self.telemetry.metrics,
            scalars={"cache_hits": "plan_cache_hits",
                     "cache_misses": "plan_cache_misses",
                     "collections": "planner_collections",
                     "collect_time_s": "planner_collect_time_s",
                     "estimate_time_s": "planner_estimate_time_s",
                     "schedule_time_s": "planner_schedule_time_s",
                     "audits": "planner_audits",
                     "refits": "planner_refits",
                     "evictions": "plan_cache_evictions",
                     "oom_events": "train_oom_events",
                     "escalations": "train_escalations",
                     "poisoned_plans": "plan_cache_poisoned",
                     "restored_samples": "planner_restored_samples",
                     "restored_plans": "planner_restored_plans",
                     "dropped_plans": "planner_dropped_plans",
                     "solves": "solver_solves",
                     "solver_swaps": "solver_swaps",
                     "solver_wins": "solver_wins",
                     "solver_timeouts": "solver_timeouts",
                     "offload_fallbacks": "offload_fallbacks"},
            labeled={"oom_by_bucket": ("train_oom_events", "bucket"),
                     "escalations_by_bucket": ("train_escalations",
                                               "bucket")})
        # optimal-plan tier: a daemon thread solves the (k, action)
        # assignment exactly and swaps strictly better plans into the
        # cache above — all cache access goes through _cache_lock so
        # the swap is atomic against the training thread
        if solver not in ("off", "dp"):
            raise ValueError(f"solver must be 'off' or 'dp', got "
                             f"{solver!r}")
        self.solver = solver
        self.solver_budget_ms = float(solver_budget_ms)
        self._cache_lock = threading.RLock()
        self.background_solver = (
            BackgroundSolver(self, budget_ms=self.solver_budget_ms)
            if solver == "dp" else None)

    # ------------------------------------------------------------------
    def _quantize(self, s: int) -> int:
        # MUST stay identical to bucket_key's rounding: the plan cache
        # (keyed here) and the trainer's jit cache (keyed by bucket_key)
        # align only because both delegate to the same bucket_length
        return bucket_length(s, self.quantum)

    def _feed_estimators(self, s: int, res, probe=None) -> None:
        """One collection feeds all three per-unit fits (activation,
        boundary, offloadable) so they become ready together.  The
        probe's geometry is logged so a snapshot can replay the sample
        abstractly under a different mesh (``train/resilience.py``)."""
        self.estimator.add_sample(s, self.collected_vector(res))
        self._feed_hybrid_estimators(s, res)
        if probe is not None:
            self._sample_log.append(
                {"size": int(s),
                 "probe": {k: [list(np.shape(v)),
                               str(getattr(v, "dtype", "int32"))]
                           for k, v in probe.items()
                           if np.shape(v)}})

    def planned_fixed_bytes(self, params, batch) -> float:
        """What every plan of this batch's bucket holds whatever it
        remats: parameters, gradients and optimizer state, and the
        blocked output head and loss at the bucket's geometry
        (``ShuttlingCollector.head_bytes``)."""
        return (self.resolve_fixed_bytes(params)
                + self.collector.head_bytes(batch))

    def _record_drift_point(self, bucket: int, size: int, est, truth,
                            fixed: float, rel_err: float = 0.0,
                            refit: bool = False) -> None:
        """One point of the predicted-vs-actual peak-bytes series: the
        drift-audit (and every sheltered collection) compares the
        estimator's activation-byte prediction against an exact
        abstract re-collection — this publishes that comparison as
        per-bucket gauges and a ``drift`` event instead of discarding
        it after the refit decision."""
        div = self.activation_divisor_scalar()
        pred = fixed + float(np.sum(est)) / div
        act = fixed + float(np.sum(truth)) / div
        m = self.telemetry.metrics
        m.gauge("plan_predicted_peak_bytes",
                "predicted per-device peak bytes at the bucket's "
                "geometry").set(pred, bucket=bucket)
        m.gauge("plan_actual_peak_bytes",
                "collected (ground-truth) per-device peak bytes").set(
                    act, bucket=bucket)
        if self.telemetry.events_on:
            self.telemetry.events.emit(
                "drift", bucket=int(bucket), size=int(size),
                predicted_bytes=pred, actual_bytes=act,
                rel_err=float(rel_err), refit=bool(refit))

    def _microbatch_vectors(self, params, batch, k: int, est1, flops1,
                            res) -> dict:
        """Per-microbatch planning vectors at split ``k`` for
        ``greedy_plan_adaptive``: estimator predictions at the
        microbatch input size ``~s/k`` once the fits are ready, an
        abstract collection on the split geometry during sheltered
        execution (the extra sample also feeds the fits).  ``k == 1``
        reuses the vectors the plain path already derived."""
        div = self.activation_divisor_scalar()
        if k == 1:
            est, flops, size, res_k = est1, flops1, input_size_of(batch), res
        else:
            probe = self.microbatch_probe(batch, k)
            size = input_size_of(probe)
            res_k = None
            if res is None and self.estimator.ready:
                # responsive execution: the per-unit fits price any
                # split for free
                est = self.estimator.predict(size)
            else:
                # sheltered execution (this plan() already collected at
                # k=1): collect the split geometry too — exact vectors,
                # and the extra sample feeds the fits
                res_k = self.collector.collect(params, probe)
                self._feed_estimators(size, res_k, probe)
                self.stats["collections"] += 1
                self.stats["collect_time_s"] += res_k.collect_time_s
                est = self.collected_vector(res_k)
            flops = None
            if self.cost_aware:
                flops = (res_k.flops_vector() if res_k is not None
                         else plan_unit_flops(self.lm, probe))
        d = {"est_mem": est / div}
        if flops is not None:
            d["flops"] = self.planning_flops(flops)
            d["pad_overhead_s"] = self.pad_waste_s(batch, k, d["flops"])
        hv = self._hybrid_vectors(size, res_k)
        if hv is not None:
            d["output_bytes"], d["offload_bytes"] = hv
        ov = self._opt_bytes_planning()
        if ov is not None:
            d["opt_bytes"] = ov
        return d

    def plan(self, params, batch):
        s = input_size_of(batch)
        qs = self._quantize(s)
        # the ONE cache-key construction (PlannerBase.plan_key): growing
        # a key component there covers every planner at once
        key = self.plan_key(batch)
        head = self.collector.head_bytes(batch)
        fixed = self.resolve_fixed_bytes(params) + head
        with self._cache_lock:
            p = self.cache.get(key)
        if p is not None:
            self.stats["cache_hits"] += 1
            # a background-solved plan lands here on the next step of
            # its bucket — no blocking, the daemon already swapped it in
            self._maybe_submit_solve(params, batch, key, p)
            return p.as_actions(), PlanInfo(s, qs, True, False, p,
                                            head_bytes=head)
        self.stats["cache_misses"] += 1

        tel = self.telemetry
        collected = False
        audited = False
        flops = None
        res = None
        t_est = t_col = 0.0
        if not self.estimator.ready:
            # sheltered execution: collect this size online (the
            # collection carries the recompute-cost vector for this
            # geometry, so the scheduler reads it straight off)
            with tel.tracer.span("collect", TRACK_PLANNER):
                res = self.collector.collect(params, batch)
            self._feed_estimators(s, res, batch)
            est = self.collected_vector(res)
            if self.cost_aware:
                flops = res.flops_vector()
            collected = True
            t_col = res.collect_time_s
            self.stats["collections"] += 1
            self.stats["collect_time_s"] += t_col
            self._record_drift_point(qs, s, est, est, fixed)
        else:
            t0 = time.perf_counter()
            with tel.tracer.span("predict", TRACK_PLANNER):
                est = self.estimator.predict(s)
            t_est = time.perf_counter() - t0
            self.stats["estimate_time_s"] += t_est
            if (self.audit_every
                    and self.stats["cache_misses"] % self.audit_every == 0):
                # drift audit: exact abstract re-collection for this size
                self.stats["audits"] += 1
                with tel.tracer.span("collect", TRACK_PLANNER):
                    audit_res = self.collector.collect(params, batch)
                truth = self.collected_vector(audit_res)
                err = abs(truth.sum() - est.sum()) / max(truth.sum(), 1.0)
                refit = err > self.audit_tol
                audited = True
                self._record_drift_point(qs, s, est, truth, fixed,
                                         rel_err=err, refit=refit)
                if refit:
                    self._feed_estimators(s, audit_res, batch)
                    self.estimator.fit()
                    self.est_output.fit()
                    self.est_offload.fit()
                    est = truth
                    res = audit_res          # exact vectors for this plan
                    self.stats["refits"] += 1
                    with self._cache_lock:
                        self.cache.clear()  # stale plans out — also
                    # invalidates in-flight solves: their swap is
                    # identity-checked against the evicted objects
                    if tel.events_on:
                        tel.events.emit("refit", bucket=qs, size=s,
                                        rel_err=float(err))
                    tel.tracer.instant("refit", TRACK_PLANNER,
                                       args={"bucket": qs})

        t0 = time.perf_counter()
        # analytic recompute cost at this bucket's geometry (pure python
        # math, microseconds) — makes selection cost-aware: cheap units
        # are rematerialised before FLOP-heavy ones freeing equal bytes
        if self.cost_aware and flops is None:
            flops = plan_unit_flops(self.lm, batch)
        ks = self.candidate_microbatches(batch)
        with tel.tracer.span("schedule", TRACK_PLANNER):
            if ks == [1]:
                # plain path — bit-identical to planning without the
                # microbatching subsystem
                div = self.activation_divisor_scalar()
                plan = greedy_plan(est / div,
                                   self.budget_bytes,
                                   fixed,
                                   tol=self.bucket_tol,
                                   flops=self.planning_flops(flops),
                                   **self._hybrid_kwargs(s, res))
            else:
                plan = greedy_plan_adaptive(
                    lambda k: self._microbatch_vectors(params, batch, k,
                                                       est, flops, res),
                    self.budget_bytes,
                    fixed,
                    candidate_ks=ks,
                    tol=self.bucket_tol,
                    pcie_bytes_per_s=self.pcie_gbps * 1e9,
                    offload_overlap=self.offload_overlap,
                    accum_overhead_s=self.microbatch_overhead_s)
        t_sch = time.perf_counter() - t0
        self.stats["schedule_time_s"] += t_sch
        if not collected and not audited:
            # responsive plans carry a prediction but no ground truth;
            # keep the predicted-peak gauge current for the drift column
            # (an audit this call already published the fresher
            # predicted/actual pair — don't clobber it)
            div = self.activation_divisor_scalar()
            self.telemetry.metrics.gauge(
                "plan_predicted_peak_bytes").set(
                    fixed + float(np.sum(est)) / div, bucket=qs)

        ev_before = self.cache.evictions
        with self._cache_lock:
            self.cache[key] = plan
        self.stats["evictions"] = self.cache.evictions
        if tel.events_on:
            tel.events.emit(
                "plan", bucket=qs, size=s, source=plan.source,
                collected=bool(collected),
                k=int(getattr(plan, "microbatch", 1) or 1),
                n_remat=int(plan.n_remat),
                n_offload=int(plan.n_offload),
                n_opt=int(plan.n_opt),
                recompute_flops=float(plan.recompute_flops),
                offload_bytes=float(plan.offload_bytes),
                schedule_time_s=t_sch)
            if self.cache.evictions > ev_before:
                tel.events.emit("plan_evicted", bucket=qs,
                                evictions=int(self.cache.evictions))
        self._maybe_submit_solve(params, batch, key, plan)
        return plan.as_actions(), PlanInfo(s, qs, False, collected, plan,
                                           t_est, t_sch, t_col,
                                           head_bytes=head)

    # ------------------------------------------------------------------
    def _maybe_submit_solve(self, params, batch, key, plan) -> None:
        """Queue an exact background solve for this bucket (the
        optimal-plan tier, ``repro.core.solver``).  Greedy already
        served the step — this never blocks.  Skipped while the
        estimator is warming up (the sheltered plans are exact for
        their collections), for plans the solver already produced or
        checked, and for OOM-escalated buckets (their repaired plan
        encodes information the simulator does not have).  The
        planning vectors are materialised HERE, on the training
        thread, so the daemon stays numpy-only."""
        bs = self.background_solver
        if (bs is None or not self.estimator.ready
                or getattr(plan, "solver_checked", False)
                or plan.source == "dp"
                or self._escalation.get(key, 0)
                or bs.pending(key)):
            return
        s = input_size_of(batch)
        est1 = self.estimator.predict(s)
        flops1 = plan_unit_flops(self.lm, batch) if self.cost_aware else None
        ks = self.candidate_microbatches(batch)
        vectors = {int(k): self._microbatch_vectors(params, batch, k,
                                                    est1, flops1, None)
                   for k in ks}
        req = SolveRequest(key=key, bucket=self.bucket_key(batch),
                           vectors=vectors,
                           budget_bytes=self.budget_bytes,
                           fixed_bytes=self.planned_fixed_bytes(params,
                                                                batch),
                           candidate_ks=tuple(ks),
                           pcie_bytes_per_s=self.pcie_gbps * 1e9,
                           offload_overlap=self.offload_overlap,
                           accum_overhead_s=self.microbatch_overhead_s,
                           baseline=plan)
        if bs.submit(req):
            # one submission per cached plan object; the daemon re-marks
            # it after the solve completes (covers the queue-full path,
            # where a later hit may retry)
            plan.solver_checked = True

    # ------------------------------------------------------------------
    def escalate(self, params, batch) -> bool:
        """DTR-style recovery ladder after a device OOM on this batch's
        bucket (called by the ``repro.train.resilience`` watchdog).

        The predicted plan was wrong — reality ran out of memory — so
        each call replaces the cached plan with a strictly more
        aggressive one, planned against a budget shrunk by
        ``escalate_shrink ** level`` (the prediction error is unknown;
        the shrink is the safety margin).  Rungs, in order:

          1. **more remat** — re-plan remat-only at the shrunken budget;
          2. **offload** — upgrade the current plan's actions
             (KEEP -> REMAT -> OFFLOAD) in density order via
             ``scheduler.escalate_plan`` until the liveness replay fits;
          3. **higher microbatch k** — double the gradient-accumulation
             split (``greedy_plan_adaptive`` with the forced candidate),
             repeating until ``k`` reaches the batch size.

        The escalated plan is cached under the same plan key (the old
        entry is poisoned), so later steps of the bucket reuse it.
        Returns False when the ladder is exhausted (``k`` cannot grow
        further) — the watchdog then re-raises the OOM.
        """
        key = self.plan_key(batch)
        level = self._escalation.get(key, 0) + 1
        s = input_size_of(batch)
        bucket = self.bucket_key(batch)
        B = int(np.shape(batch["tokens"])[0])

        res = None
        if not self.estimator.ready:
            res = self.collector.collect(params, batch)
            self._feed_estimators(s, res, batch)
            self.stats["collections"] += 1
            self.stats["collect_time_s"] += res.collect_time_s
            est = self.collected_vector(res)
        else:
            est = self.estimator.predict(s)
        div = self.activation_divisor_scalar()
        flops = (res.flops_vector() if res is not None
                 else plan_unit_flops(self.lm, batch))
        fixed = self.planned_fixed_bytes(params, batch)
        budget = self.budget_bytes * (self.escalate_shrink ** level)
        with self._cache_lock:
            prev = self.cache.get(key)
        prev_k = max(int(getattr(prev, "microbatch", 1) or 1), 1)

        if level == 1 and prev_k == 1:
            # rung 1: more remat — the full cost-aware replan at the
            # shrunken budget frees strictly more bytes than the plan
            # that just OOMed
            plan = greedy_plan(est / div, budget, fixed,
                               tol=self.bucket_tol,
                               flops=self.planning_flops(flops))
        elif level == 2 and prev_k == 1:
            # rung 2: offload — upgrade the failed plan's actions until
            # the replayed peak fits (works even when the offload knob
            # is off: the hybrid estimators are fed on every collection)
            out_v = (self.collected_output_vector(res) if res is not None
                     else self.est_output.predict(s)) / div
            off_v = (self.collected_offload_vector(res) if res is not None
                     else self.est_offload.predict(s)) / div
            base = prev.actions if prev is not None else None
            plan = escalate_plan(base, est / div,
                                 self.planning_flops(flops), budget, fixed,
                                 output_bytes=out_v, offload_bytes=off_v,
                                 pcie_bytes_per_s=self.pcie_gbps * 1e9,
                                 offload_overlap=self.offload_overlap,
                                 opt_bytes=self._opt_bytes_planning())
        else:
            # rung 3+: gradient accumulation — shrink the per-microbatch
            # footprint itself, the one lever that reaches below the
            # bucket's k=1 minimum footprint
            k_new = min(B, max(2, prev_k * 2))
            if k_new <= prev_k:
                self._escalation[key] = level
                return False
            plan = greedy_plan_adaptive(
                lambda k: self._microbatch_vectors(params, batch, k,
                                                   est, flops, res),
                budget, fixed, candidate_ks=[k_new],
                tol=self.bucket_tol,
                pcie_bytes_per_s=self.pcie_gbps * 1e9,
                offload_overlap=self.offload_overlap,
                accum_overhead_s=self.microbatch_overhead_s)

        plan.source = "escalated"
        with self._cache_lock:
            if key in self.cache:
                self.stats["poisoned_plans"] += 1
                if self.telemetry.events_on:
                    self.telemetry.events.emit("plan_poisoned",
                                               bucket=bucket, level=level)
            # installing a NEW object also invalidates any in-flight
            # solve for this key (identity-checked swap)
            self.cache[key] = plan
        self._escalation[key] = level
        self.stats.inc("escalations", bucket=bucket)
        tel = self.telemetry
        if tel.events_on:
            tel.events.emit("escalation", bucket=bucket, level=level,
                            k=int(getattr(plan, "microbatch", 1) or 1),
                            n_remat=int(plan.n_remat),
                            n_offload=int(plan.n_offload))
        tel.tracer.instant("escalation", TRACK_PLANNER,
                           args={"bucket": bucket, "level": level})
        return True
