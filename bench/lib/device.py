"""The chip: its presence, its published peaks, its memory, its compiles.

``PEAKS`` is the benchmark's own copy of the table in
``repro/launch/roofline.py``, keyed by ``jax.Device.device_kind``, so a
change to the program cannot move the yardstick.  A device kind that is
not in the table is an error, not a default.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # dense bf16 FLOP/s per chip
    hbm_bw: float     # bytes/s per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


class NoChip(RuntimeError):
    pass


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise NoChip(f"no published peaks for device kind {kind!r}") from None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one.  Every
    program is cached, however quickly it compiled."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def require_chips(n: int):
    """The first ``n`` TPU devices, or ``NoChip``: never the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from None
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devices[0].platform!r}"
                     if devices else "needs a TPU, found no device")
    if len(devices) < n:
        raise NoChip(f"needs {n} TPU chips, found {len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:n]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where the backend
    reports no memory statistics)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.active = False
        self.counts = {e.rsplit("/", 1)[-1]: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, *args, **kwargs):
        if self.active and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[-1]] += 1

    @property
    def total(self) -> int:
        return sum(self.counts.values())
