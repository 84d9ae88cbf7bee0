"""Shuttling online collector (paper §4.2), adapted to JAX.

The paper's collector runs every block's forward twice on the GPU — once
to measure per-layer activation memory from the CUDA allocator, once
checkpointed to keep the footprint at the Sublinear level.  Under XLA
there is no runtime allocator to poll, but there is something strictly
better: the residuals JAX AD will save for a block are *exactly* the
leaves of the ``jax.vjp`` closure, and they can be obtained abstractly
with ``jax.eval_shape`` — zero FLOPs, zero bytes allocated, and the
numbers are exact rather than sampled.  The "shuttle" (forward twice)
degenerates to a single abstract trace per block; we keep the paper's
online character: the collector runs lazily, on the live training batch,
only when a new input size appears, with no model pre-analysis.

For wall-time data (used in the paper's Table 2 overhead breakdown) the
collector can also time a concrete forward per block on request.

Sharding-aware collection: given a ``MeshBudget`` the collector also
records each unit's *per-device* activation bytes — every leaf of the
vjp closure is divided by its ``MeshBudget.activation_divisor`` (the
``sharding/specs.py`` rules: batch over the data axes, tensor-parallel
intermediates over ``model``), so downstream estimation and planning can
run against a per-device HBM budget instead of a fictitious global one.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.roofline import plan_unit_flops
from repro.models.lm import LM, PlanUnit
from repro.sharding.budget import MeshBudget, unit_moment_bytes


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "shape"))


@dataclasses.dataclass
class UnitRecord:
    name: str
    index: int                 # forward timestamp
    activation_bytes: int      # residuals AD would save (excluding weights)
    output_bytes: int          # inter-block tensor (kept even when rematted)
    param_bytes: int
    forward_time_s: float = 0.0
    # per-device residual bytes after the unit's PartitionSpec divisors
    # (== activation_bytes when collected without a MeshBudget)
    device_activation_bytes: int = 0
    # analytic forward FLOPs at the collection geometry — the recompute
    # cost of rematerialising this unit (launch/roofline.py cost model)
    flops: float = 0.0
    # residual bytes worth a host DMA (matrix-shaped leaves; scalars and
    # 1-d leaves stay on device) — what an OFFLOAD action can free
    offloadable_bytes: int = 0
    device_offloadable_bytes: int = 0
    # per-device boundary-tensor bytes (the checkpoint REMAT must keep)
    device_output_bytes: int = 0
    # fp32 AdamW moment bytes (m + v) owned by the unit — what an
    # OFFLOAD_OPT action parks on the host.  Param-shape-determined
    # (input-size-independent), ZeRO-divided in the device_ variant.
    opt_bytes: int = 0
    device_opt_bytes: int = 0


@dataclasses.dataclass
class CollectionResult:
    input_size: int            # elements in the mini-batch input tensor
    records: List[UnitRecord]
    collect_time_s: float = 0.0
    traced_units: int = 0      # abstract traces actually run
    dedup_hits: int = 0        # units served from an identical unit's trace

    def activation_vector(self) -> np.ndarray:
        return np.array([r.activation_bytes for r in self.records], dtype=np.float64)

    def device_activation_vector(self) -> np.ndarray:
        """Per-unit bytes landing on ONE device under the collection's
        MeshBudget (identical to ``activation_vector`` without one)."""
        return np.array([r.device_activation_bytes for r in self.records],
                        dtype=np.float64)

    def flops_vector(self) -> np.ndarray:
        """Per-unit analytic forward FLOPs (= recompute cost) at the
        collection geometry — the scheduler's cost-aware score input."""
        return np.array([r.flops for r in self.records], dtype=np.float64)

    def output_vector(self) -> np.ndarray:
        """Per-unit boundary (inter-block) tensor bytes — what REMAT
        keeps on device as its recompute checkpoint."""
        return np.array([r.output_bytes for r in self.records],
                        dtype=np.float64)

    def device_output_vector(self) -> np.ndarray:
        return np.array([r.device_output_bytes for r in self.records],
                        dtype=np.float64)

    def offloadable_vector(self) -> np.ndarray:
        """Per-unit residual bytes an OFFLOAD action can stream to host
        (DMA-worthy matrix leaves; always <= ``activation_vector``)."""
        return np.array([r.offloadable_bytes for r in self.records],
                        dtype=np.float64)

    def device_offloadable_vector(self) -> np.ndarray:
        return np.array([r.device_offloadable_bytes for r in self.records],
                        dtype=np.float64)

    def opt_vector(self) -> np.ndarray:
        """Per-unit fp32 AdamW moment bytes — the OFFLOAD_OPT action's
        price vector.  Input-size-independent (param shapes only)."""
        return np.array([r.opt_bytes for r in self.records],
                        dtype=np.float64)

    def device_opt_vector(self) -> np.ndarray:
        """Per-device (ZeRO-divided) counterpart of ``opt_vector``."""
        return np.array([r.device_opt_bytes for r in self.records],
                        dtype=np.float64)

    def total_activation_bytes(self) -> int:
        return int(sum(r.activation_bytes for r in self.records))


def unit_residual_bytes(unit: PlanUnit, x_struct,
                        mesh_budget: Optional[MeshBudget] = None
                        ) -> Dict[str, int]:
    """Exact residual footprint of one block, computed abstractly.

    ``jax.vjp(f, x)[1]`` is a pytree whose array leaves are precisely the
    tensors AD keeps live between forward and backward.  Weights appear in
    that closure too but are resident anyway, so they are subtracted.

    With a ``mesh_budget`` the per-device footprint is also computed:
    closure leaves matching a parameter's (shape, dtype) are excluded
    (they are counted in the fixed per-device bytes instead) and each
    remaining activation leaf is divided by its sharding divisor.

    Offloadable bytes (what an OFFLOAD action can stream to pinned host
    memory) are the non-param residual leaves with >= 2 dimensions —
    scalars and 1-d leaves are not worth a DMA descriptor and stay on
    device — clamped to never exceed the activation bytes.
    """
    def capture(p, x):
        out, vjp_fn = jax.vjp(lambda xx: unit.apply(p, xx), x)
        return out, vjp_fn

    out_struct, vjp_struct = jax.eval_shape(capture, unit.params, x_struct)
    resid = _tree_bytes(vjp_struct)
    params = _tree_bytes(unit.params)
    info = {
        "activation_bytes": max(0, resid - params),
        "output_bytes": _tree_bytes(out_struct),
        "param_bytes": params,
    }

    B = int(x_struct.shape[0])
    d_model = int(x_struct.shape[-1])

    def divisor(shape) -> float:
        if mesh_budget is None:
            return 1.0
        return mesh_budget.activation_divisor(shape, batch=B,
                                              d_model=d_model)

    # params appear in the closure at their own (sharded) residency; match
    # them out by (shape, dtype) multiset so only activations are counted
    param_sig = collections.Counter(
        (tuple(l.shape), str(jnp.dtype(l.dtype)))
        for l in jax.tree_util.tree_leaves(unit.params)
        if hasattr(l, "shape"))
    dev = offl = dev_offl = 0.0
    for leaf in jax.tree_util.tree_leaves(vjp_struct):
        if not hasattr(leaf, "shape"):
            continue
        key = (tuple(leaf.shape), str(jnp.dtype(leaf.dtype)))
        if param_sig.get(key, 0) > 0:
            param_sig[key] -= 1
            continue
        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        dev += nbytes / divisor(leaf.shape)
        if len(leaf.shape) >= 2:
            offl += nbytes
            dev_offl += nbytes / divisor(leaf.shape)
    # global activation bytes keep the seed's aggregate formula (resid -
    # params) so existing byte accounting is bit-identical; per-device
    # bytes come from the leaf-wise walk as before
    info["device_activation_bytes"] = (info["activation_bytes"]
                                       if mesh_budget is None else int(dev))
    info["offloadable_bytes"] = int(min(offl, info["activation_bytes"]))
    info["device_offloadable_bytes"] = int(
        min(dev_offl, info["device_activation_bytes"]))
    info["device_output_bytes"] = int(sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        / divisor(l.shape)
        for l in jax.tree_util.tree_leaves(out_struct)
        if hasattr(l, "shape")))
    return info


def input_size_of(batch) -> int:
    """Paper §3.1: input size = number of elements in the input tensor."""
    t = batch["tokens"]
    size = int(np.prod(t.shape))
    if "frames" in batch:
        size += int(np.prod(batch["frames"].shape[:2]))
    if "vision_embeds" in batch:
        size += int(np.prod(batch["vision_embeds"].shape[:2]))
    return size


def _tree_struct_sig(tree) -> tuple:
    """Hashable (treedef, leaf shapes/dtypes) signature of a pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef,
            tuple((tuple(l.shape), str(jnp.dtype(l.dtype))) for l in leaves))


class ShuttlingCollector:
    """Collects per-unit activation bytes for the live batch geometry.

    Plan units are deduplicated by (behavioural signature, param-shape
    signature, input-struct signature): a 24-layer homogeneous model needs
    ONE ``eval_shape`` trace per *unique* block, not 24 — sheltered
    execution becomes O(#unique units).  The trace cache persists across
    calls (keys embed the input geometry, so a new input size only
    re-traces the unique units).  ``dedup=False`` restores the seed's
    per-unit behaviour; the dedup path is byte-for-byte identical because
    ``eval_shape`` depends only on shapes, never on parameter values.
    """

    def __init__(self, lm: LM, measure_time: bool = False,
                 dedup: bool = True,
                 mesh_budget: Optional[MeshBudget] = None):
        self.lm = lm
        self.measure_time = measure_time
        self.dedup = dedup
        # sharding-aware mode: also record per-device bytes under this
        # budget's divisors.  Part of the trace-cache key so a collector
        # is safe to rebuild with a different mesh shape.
        self.mesh_budget = mesh_budget
        self._mesh_sig = mesh_budget.sig() if mesh_budget is not None else None
        self._trace_cache: Dict[tuple, dict] = {}
        self._head_cache: Dict[tuple, int] = {}
        self.stats = {"traces": 0, "dedup_hits": 0, "collections": 0}

    def collect(self, params, batch) -> CollectionResult:
        t0 = time.perf_counter()
        units = self.lm.plan_units(params, batch)
        # analytic recompute cost per unit (pure python math, ~us): rides
        # along with the byte records so schedulers can score bytes
        # freed per recompute-FLOP without re-deriving geometry
        unit_flops = plan_unit_flops(self.lm, batch)
        x_struct = self._residual_stream_struct(params, batch)
        records: List[UnitRecord] = []
        traced = hits = 0
        for u in units:
            if u.name.startswith("enc"):
                xs = self._encoder_stream_struct(batch)
            else:
                xs = x_struct
            key = None
            info = None
            if self.dedup and u.signature is not None:
                key = (u.signature, _tree_struct_sig(u.params),
                       tuple(xs.shape), str(xs.dtype), self._mesh_sig)
                info = self._trace_cache.get(key)
            if info is None:
                info = dict(unit_residual_bytes(u, xs, self.mesh_budget))
                if key is not None:
                    self._trace_cache[key] = info
                traced += 1
            else:
                hits += 1
            # wall-clock is NOT shape-determined: unlike the byte counts,
            # timings must be measured per unit, never replayed from the
            # trace cache (they feed the paper's Table 2 overhead data)
            t_fwd = self._time_unit(u, xs) if self.measure_time else 0.0
            # optimizer-moment bytes are param-shape math (no tracing):
            # scan chunks carry stacked leaves whose leading layer axis
            # needs the synthetic ``blocks`` path prefix
            scanned_u = u.name.startswith("chunk")
            opt_b = unit_moment_bytes(u.params, None, scanned=scanned_u)
            dev_opt_b = (unit_moment_bytes(u.params, self.mesh_budget,
                                           scanned=scanned_u)
                         if self.mesh_budget is not None else opt_b)
            rec = UnitRecord(u.name, u.index, info["activation_bytes"],
                             info["output_bytes"], info["param_bytes"],
                             t_fwd, info["device_activation_bytes"],
                             float(unit_flops[u.index]),
                             offloadable_bytes=info["offloadable_bytes"],
                             device_offloadable_bytes=info[
                                 "device_offloadable_bytes"],
                             device_output_bytes=info["device_output_bytes"],
                             opt_bytes=int(opt_b),
                             device_opt_bytes=int(dev_opt_b))
            records.append(rec)
        self.stats["traces"] += traced
        self.stats["dedup_hits"] += hits
        self.stats["collections"] += 1
        return CollectionResult(input_size_of(batch), records,
                                time.perf_counter() - t0,
                                traced_units=traced, dedup_hits=hits)

    def head_bytes(self, batch) -> int:
        """Bytes of the blocked output head and loss at this batch's
        geometry: every leaf of ``LM.head_parts`` (one head
        block's forward and gradient under ``jax.eval_shape``, plus the
        final hidden state).  Under a mesh budget it is counted whole on
        each device, an upper bound.  Not a plan unit: it is never
        rematted, and the planner adds it to every peak it predicts.
        Cached by geometry."""
        key = tuple(int(s) for s in batch["tokens"].shape)
        if key not in self._head_cache:
            self._head_cache[key] = _tree_bytes(self.lm.head_parts(batch))
        return self._head_cache[key]

    # ------------------------------------------------------------------
    def _residual_stream_struct(self, params, batch):
        cfg = self.lm.cfg
        B, S = batch["tokens"].shape
        if cfg.family == "vlm" and cfg.vision_tokens:
            S = S + cfg.vision_tokens
        return jax.ShapeDtypeStruct((B, S, cfg.d_model), self.lm.dtype)

    def _encoder_stream_struct(self, batch):
        cfg = self.lm.cfg
        B, F = batch["frames"].shape[:2]
        return jax.ShapeDtypeStruct((B, F, cfg.d_model), self.lm.dtype)

    def _time_unit(self, u: PlanUnit, x_struct) -> float:
        x = jnp.zeros(x_struct.shape, x_struct.dtype)
        fn = jax.jit(u.apply)
        fn(u.params, x).block_until_ready()          # compile + warm
        t0 = time.perf_counter()
        fn(u.params, x).block_until_ready()
        return time.perf_counter() - t0
