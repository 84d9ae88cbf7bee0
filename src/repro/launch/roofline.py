"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms per (arch × shape × mesh), at the peaks of the chip's row in
``PEAKS`` (TPU v5e):

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bandwidth_per_chip
    collective = collective_bytes_per_device / ICI_link_bandwidth

``compiled.cost_analysis()`` reports *per-participating-device* FLOPs and
bytes (verified empirically: a 2MKN matmul across 256 chips reports
2MKN/256).  Collective bytes are parsed from the per-device SPMD HLO —
we sum the result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (all-reduce counted
twice: reduce-scatter + all-gather equivalent traffic).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per chip-to-chip link
    source: str


# keyed by ``jax.Device.device_kind``
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links"),
}
# the row plans are priced against where no TPU is attached (CPU tests,
# dry runs): the chip this repository targets
PLANNING_KIND = "TPU v5 lite"


def device_peaks(device=None) -> ChipPeaks:
    """Peaks of ``device`` (default: the first visible device).  A TPU
    whose kind has no row in ``PEAKS`` is an error, never the v5e row;
    a host without a TPU plans against the v5e row by name."""
    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        return PEAKS[PLANNING_KIND]
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for TPU kind "
                       f"{device.device_kind!r}; add its row to "
                       f"repro.launch.roofline.PEAKS") from None


# the planner's pricing constants: the planning chip's row
PEAK_FLOPS = PEAKS[PLANNING_KIND].flops
HBM_BW = PEAKS[PLANNING_KIND].hbm_bw
ICI_BW = PEAKS[PLANNING_KIND].ici_bw
# effective host<->device link for activation offload (PCIe 4.0 x16 is
# ~32 GB/s raw; 16 GB/s is the sustained-DMA default the --pcie-gbps
# knob overrides).  The hybrid scheduler prices OFFLOAD actions with it.
PCIE_BW = 16e9               # bytes/s host<->device
# fixed per-microbatch cost of gradient accumulation: one extra step
# dispatch plus the grad-buffer read-modify-write (~params bytes at
# HBM_BW) per additional microbatch.  The adaptive-microbatching
# scheduler charges (k - 1) of these when scoring a k-way split, so k
# never escalates for free — it must buy back more remat/offload
# overhead than the accumulation costs (planners override per model via
# ``microbatch_overhead_s=``).
MICROBATCH_OVERHEAD_S = 5e-4


def calibrated_pcie_gbps(default: float = PCIE_BW / 1e9) -> float:
    """The host link bandwidth planning should actually price:
    ``$MIMOSE_PCIE_GBPS`` wins, then this host's measured calibration
    file (``tools/bench_offload_bw.py`` writes it), then ``default`` —
    the 16 GB/s roofline constant unless a caller knows better."""
    from repro.train.transfer import calibrated_pcie_gbps as _measured
    return _measured(default)


def offload_transfer_s(bytes_moved: float,
                       pcie_bytes_per_s: float = PCIE_BW) -> float:
    """Round-trip host-offload time for ``bytes_moved`` residual bytes.

    An offloaded unit's residuals cross the link twice — out during the
    forward pass, back in before the unit's backward — so the charged
    time is ``2 x bytes / bandwidth``.  This is the OFFLOAD counterpart
    of the REMAT cost ``flops / PEAK_FLOPS``: the two numbers the hybrid
    scheduler compares when choosing how to free a unit's bytes.
    """
    return 2.0 * float(bytes_moved) / float(pcie_bytes_per_s)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^\s]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
# tuple-shaped collectives: "= (f32[..], f32[..]) all-reduce(...)"
_TUPLE_RE = re.compile(
    r"=\s+\(((?:[a-z0-9]+\[[\d,]*\][^,)]*,?\s*)+)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes moved by each collective kind."""
    out: Dict[str, float] = {}
    seen_spans = []
    for m in _TUPLE_RE.finditer(hlo_text):
        total = sum(_shape_bytes(dt, dims)
                    for dt, dims in _SHAPE_RE.findall(m.group(1)))
        kind = m.group(2)
        out[kind] = out.get(kind, 0.0) + total
        seen_spans.append(m.span())
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        if any(s <= m.start() < e for s, e in seen_spans):
            continue
        dtype, dims, kind = m.groups()
        out[kind] = out.get(kind, 0.0) + _shape_bytes(dtype, dims)
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    coll_breakdown: Dict[str, float]
    temp_bytes_per_dev: float
    arg_bytes_per_dev: float
    model_flops: float              # 6 * N_active * tokens (global)

    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_dev / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops aggregated over chips)."""
        hlo_global = self.flops_per_dev * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def step_time_bound_s(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mfu_bound(self) -> float:
        """MFU if the step ran exactly at the dominant roofline term."""
        t = self.step_time_bound_s
        if not t:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS * t)

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "t_compute_ms": round(self.t_compute * 1e3, 3),
            "t_memory_ms": round(self.t_memory * 1e3, 3),
            "t_collective_ms": round(self.t_collective * 1e3, 3),
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": round(self.useful_flops_ratio, 3),
            "mfu_bound": round(self.mfu_bound, 3),
            "temp_gib_per_dev": round(self.temp_bytes_per_dev / 2**30, 2),
            "arg_gib_per_dev": round(self.arg_bytes_per_dev / 2**30, 2),
        }


# ---------------------------------------------------------------------------
# per-plan-unit analytic cost model
#
# Forward FLOPs of one schedulable unit (a block, or a layer chunk in
# scan mode) at a given batch geometry.  Rematerialising a unit re-runs
# exactly this forward, so these numbers ARE the recompute cost the
# cost-aware scheduler scores against (bytes freed per recompute-FLOP)
# and the simulator converts to seconds via PEAK_FLOPS.  Pure python
# math — no tracing, so the planner can evaluate it per bucket in
# microseconds.
# ---------------------------------------------------------------------------

def _attention_flops(cfg, B: int, S: int, *, causal: bool = True,
                     is_global: bool = True, kv_seq: int = 0) -> float:
    """QKVO projections + score/value matmuls for one attention layer.

    ``kv_seq`` > 0 switches to cross attention over that many keys
    (k/v projected from the encoder stream of length kv_seq).
    """
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    Sk = kv_seq or S
    proj = 2.0 * B * S * d * cfg.attn_dim()            # q
    proj += 2.0 * 2.0 * B * Sk * d * cfg.kv_dim()      # k, v
    proj += 2.0 * B * S * cfg.attn_dim() * d           # o
    W = cfg.sliding_window
    if kv_seq:
        pairs = float(S) * Sk                          # cross: full
    elif not is_global and W > 0:
        pairs = float(S) * min(W, S)                   # banded
    elif causal:
        pairs = float(S) * S / 2.0
    else:
        pairs = float(S) * S                           # bidirectional
    score = 4.0 * B * cfg.num_heads * hd * pairs       # qk^T and p@v
    return proj + score


def _mlp_flops(cfg, B: int, S: int, d_ff: int = 0) -> float:
    ff = d_ff or cfg.d_ff
    if not ff:
        return 0.0
    mult = 3.0 if cfg.mlp_act == "swiglu" else 2.0
    return 2.0 * B * S * cfg.d_model * ff * mult


def _moe_flops(cfg, B: int, S: int) -> float:
    router = 2.0 * B * S * cfg.d_model * cfg.num_experts
    experts = cfg.experts_per_token * _mlp_flops(cfg, B, S, cfg.moe_d_ff)
    shared = (_mlp_flops(cfg, B, S, cfg.shared_expert_d_ff)
              if cfg.shared_expert_d_ff else 0.0)
    return router + experts + shared


def _ssm_flops(cfg, B: int, S: int) -> float:
    d = cfg.d_model
    d_inner = cfg.ssm_expand * d
    H = d_inner // cfg.ssm_head_dim
    N = cfg.ssm_state
    P = cfg.ssm_head_dim
    Q = cfg.ssm_chunk
    conv_dim = d_inner + 2 * N
    proj_out = 2 * d_inner + 2 * N + H
    proj = 2.0 * B * S * d * proj_out + 2.0 * B * S * d_inner * d
    conv = 2.0 * B * S * cfg.conv_kernel * conv_dim
    # chunked SSD: intra-chunk (Q,Q) matmuls + inter-chunk state terms
    scan = B * S * (2.0 * Q * N + H * (2.0 * Q * P + 4.0 * P * N))
    return proj + conv + scan


def unit_fwd_flops(cfg, kind: str, *, batch: int, seq: int, layers: int = 1,
                   is_global: bool = True, enc_frames: int = 0) -> float:
    """Analytic forward FLOPs of one plan unit (= ``layers`` blocks of
    ``kind`` at geometry (batch, seq)).  This is the recompute cost of
    rematerialising the unit."""
    B, S = int(batch), int(seq)
    if kind == "enc":
        per = _attention_flops(cfg, B, S, causal=False) + _mlp_flops(cfg, B, S)
    elif kind == "moe":
        per = (_attention_flops(cfg, B, S, is_global=is_global)
               + _moe_flops(cfg, B, S))
    elif kind == "ssm":
        per = _ssm_flops(cfg, B, S) + _mlp_flops(cfg, B, S)
    elif kind == "hybrid":
        per = (_attention_flops(cfg, B, S, is_global=is_global)
               + _ssm_flops(cfg, B, S) + _mlp_flops(cfg, B, S))
    elif kind == "dec":
        per = (_attention_flops(cfg, B, S, is_global=is_global)
               + _attention_flops(cfg, B, S, kv_seq=enc_frames or S)
               + _mlp_flops(cfg, B, S))
    else:                                              # dense
        per = (_attention_flops(cfg, B, S, is_global=is_global)
               + _mlp_flops(cfg, B, S))
    return float(layers) * per


def plan_unit_flops(lm, batch):
    """Per-plan-unit forward FLOPs vector for ``lm`` at this batch's
    geometry (``LM.plan_unit_meta`` supplies the static per-unit facts).
    Returns a float64 numpy array aligned with the planner's byte
    vectors — the ``flops`` argument of ``greedy_plan``/``simulate``."""
    return np.array([unit_fwd_flops(lm.cfg, m["kind"], batch=m["batch"],
                                    seq=m["seq"], layers=m["layers"],
                                    is_global=m["is_global"],
                                    enc_frames=m.get("enc_frames", 0))
                     for m in lm.plan_unit_meta(batch)], dtype=np.float64)


def model_flops_for(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode counts one token/seq."""
    n = cfg.active_param_count()
    if shape.kind == "decode":
        tokens = shape.global_batch          # one new token per sequence
        return 2.0 * n * tokens              # forward only
    tokens = shape.global_batch * shape.seq_len
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def analyse(compiled, *, arch: str, shape_cfg, cfg, mesh_name: str,
            chips: int) -> Roofline:
    ca = compiled.cost_analysis()
    # jaxlib returns one dict per computation on some versions, a bare
    # dict on others; normalise to a dict
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    ma = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    # all-reduce traffic ~ 2x payload (reduce-scatter + all-gather phases)
    total_coll = sum(v * (2.0 if k == "all-reduce" else 1.0)
                     for k, v in coll.items())
    return Roofline(
        arch=arch, shape=shape_cfg.name, mesh=mesh_name, chips=chips,
        flops_per_dev=float(ca.get("flops", 0.0)),
        bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
        coll_bytes_per_dev=total_coll,
        coll_breakdown=coll,
        temp_bytes_per_dev=float(ma.temp_size_in_bytes),
        arg_bytes_per_dev=float(ma.argument_size_in_bytes),
        model_flops=model_flops_for(cfg, shape_cfg),
    )
