"""Double-buffered device<->host transfer lane for real offload overlap.

The simulator prices an OFFLOAD action at ``2*bytes/pcie`` with a
``(1 - overlap)`` exposure factor; this module is the execution side
that makes the overlap real instead of aspirational:

* ``to_host`` moves an array to pinned host memory via
  ``jax.device_put`` with a ``pinned_host`` memory-kind sharding when
  the jaxlib build supports it, degrading to ``jax.device_get``
  (pageable numpy) otherwise.
* ``TransferLane`` runs those copies on ONE dedicated worker thread
  with a bounded in-flight depth of two (classic double buffering: one
  copy draining while the next is queued).  Only time a caller spends
  *blocked* on the lane — waiting for a slot, or waiting on a fetch the
  copy hasn't finished — is charged to ``stats['exposed_s']``; copies
  that complete behind compute cost nothing, which is exactly the
  quantity the simulator calls exposed transfer time.
* ``measure_pcie_gbps`` times a round trip through the lane's copy
  path and ``write_calibration`` persists it, so planners price the
  link at the bandwidth this host actually has instead of the 16 GB/s
  roofline default (``MIMOSE_PCIE_GBPS`` overrides both).
"""
from __future__ import annotations

import json
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.obs import Telemetry, TRACK_TRANSFER

# env overrides: bandwidth wins outright, path relocates the JSON
PCIE_ENV = "MIMOSE_PCIE_GBPS"
CALIBRATION_ENV = "MIMOSE_CALIBRATION"
DEFAULT_CALIBRATION_PATH = ".mimose_calibration.json"

# lane depth 2 == double buffering: one transfer in flight while the
# next is being produced; a third enqueue blocks (and the block is
# what gets charged as exposed time)
DEFAULT_DEPTH = 2

_pinned_supported: Optional[bool] = None
_pinned_lock = threading.Lock()


def host_memory_supported() -> bool:
    """True when this jaxlib can place arrays in pinned host memory
    (``memory_kind='pinned_host'``).  Probed once with a real 1-element
    transfer — constructing the sharding alone succeeds on builds that
    later fail at placement."""
    global _pinned_supported
    with _pinned_lock:
        if _pinned_supported is None:
            try:
                dev = jax.devices()[0]
                sh = jax.sharding.SingleDeviceSharding(
                    dev, memory_kind="pinned_host")
                y = jax.device_put(np.zeros((1,), np.float32), sh)
                jax.block_until_ready(y)
                _pinned_supported = True
            except Exception:
                _pinned_supported = False
        return bool(_pinned_supported)


def _host_sharding(x):
    """Pinned-host placement matching ``x``'s current sharding when the
    runtime offers one (keeps SPMD arrays shard-local on the host
    instead of gathering), else a single-device pinned sharding."""
    sh = getattr(x, "sharding", None)
    if sh is not None:
        try:
            return sh.with_memory_kind("pinned_host")
        except (AttributeError, TypeError, ValueError):
            pass
    return jax.sharding.SingleDeviceSharding(
        jax.devices()[0], memory_kind="pinned_host")


def to_host(x):
    """Move ``x`` to host memory: pinned (async-DMA-capable) when the
    build supports it, pageable numpy otherwise."""
    if host_memory_supported():
        return jax.device_put(x, _host_sharding(x))
    return jax.device_get(x)


def to_device(x, like=None):
    """Move a host buffer back to the device, restoring ``like``'s
    sharding when given (the round trip of ``to_host``)."""
    if like is not None:
        sh = getattr(like, "sharding", None)
        if sh is not None:
            return jax.device_put(x, sh)
    if isinstance(x, jax.Array):
        sh = getattr(x, "sharding", None)
        try:
            if sh is not None and sh.memory_kind == "pinned_host":
                return jax.device_put(x, sh.with_memory_kind("device"))
        except (AttributeError, TypeError, ValueError):
            pass
    return jax.device_put(x, jax.devices()[0])


def _nbytes(x) -> int:
    try:
        return int(x.nbytes)
    except (AttributeError, TypeError):
        return int(np.asarray(x).nbytes)


class HostHandle:
    """Ticket for one offloaded array: resolve with
    ``TransferLane.fetch``.  ``key`` identifies the host-buffer class
    ((shape, dtype, mesh signature)) so shard-local buffers from
    different meshes never alias."""

    __slots__ = ("future", "key", "nbytes", "like")

    def __init__(self, future: Future, key, nbytes: int, like=None):
        self.future = future
        self.key = key
        self.nbytes = nbytes
        self.like = like


class TransferLane:
    """One dedicated worker thread moving arrays device<->host with a
    bounded in-flight depth (default 2 = double buffered).

    stats:
      bytes_out / bytes_in   total bytes moved each direction
      transfers              completed copies (both directions)
      copy_s                 wall time the worker spent inside copies —
                             the step's realised round-trip transfer
                             time (== bytes / the bandwidth this step
                             actually achieved, contention included)
      exposed_s              wall time callers spent BLOCKED on the
                             lane — the measured counterpart of the
                             simulator's exposed transfer seconds, and
                             bounded by ``copy_s`` when the accounting
                             is consistent (a caller can wait each copy
                             out at most once)
    """

    def __init__(self, depth: int = DEFAULT_DEPTH,
                 mesh_sig: Optional[tuple] = None,
                 telemetry: Optional[Telemetry] = None):
        self.depth = max(int(depth), 1)
        self.mesh_sig = mesh_sig
        # ``stats`` stays a plain per-step scratch dict (the trainer
        # zeroes it every step via reset_stats); the telemetry registry
        # accumulates the run totals and the tracer gets copy/exposed
        # spans on the dedicated transfer track
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry.disabled()
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="mimose-xfer")
        self._in_flight: list = []          # oldest-first outbound futures
        self._lock = threading.Lock()
        self.stats: Dict[str, Any] = {"bytes_out": 0, "bytes_in": 0,
                                      "transfers": 0, "copy_s": 0.0,
                                      "exposed_s": 0.0}

    # -- internal ------------------------------------------------------
    def _charge(self, dt: float) -> None:
        with self._lock:
            self.stats["exposed_s"] += float(dt)
        self.telemetry.metrics.counter(
            "transfer_exposed_s",
            "wall time callers spent blocked on the lane").inc(float(dt))
        if dt > 0.0:
            # retroactive span: the caller was blocked for the interval
            # ending now — lands under the execute span that paid it
            self.telemetry.tracer.complete(
                "exposed", time.perf_counter() - dt, dt, TRACK_TRANSFER)

    def _reserve_slot(self) -> None:
        """Block until the lane has a free in-flight slot; the wait is
        exposed time (the producer stalled on the link)."""
        while True:
            with self._lock:
                self._in_flight = [f for f in self._in_flight
                                   if not f.done()]
                if len(self._in_flight) < self.depth:
                    return
                oldest = self._in_flight[0]
            t0 = time.perf_counter()
            oldest.result()
            self._charge(time.perf_counter() - t0)

    def _copy_out(self, x):
        t0 = time.perf_counter()
        y = to_host(x)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats["transfers"] += 1
            self.stats["copy_s"] += dt
        tel = self.telemetry
        tel.metrics.counter("transfer_copy_s").inc(dt)
        tel.metrics.counter("transfer_bytes_out").inc(_nbytes(x))
        tel.tracer.complete("copy_d2h", t0, dt, TRACK_TRANSFER,
                            args={"bytes": _nbytes(x)}
                            if tel.trace_on else None)
        return y

    def _copy_in(self, host, like):
        t0 = time.perf_counter()
        y = to_device(host, like)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats["transfers"] += 1
            self.stats["copy_s"] += dt
        tel = self.telemetry
        tel.metrics.counter("transfer_copy_s").inc(dt)
        tel.metrics.counter("transfer_bytes_in").inc(_nbytes(host))
        tel.tracer.complete("copy_h2d", t0, dt, TRACK_TRANSFER,
                            args={"bytes": _nbytes(host)}
                            if tel.trace_on else None)
        return y

    # -- API -----------------------------------------------------------
    def offload(self, x, *, like=None) -> HostHandle:
        """Start moving ``x`` to the host on the lane thread; returns
        immediately (unless both buffers are busy).  ``like`` pins the
        sharding ``fetch`` restores; defaults to ``x`` itself."""
        nbytes = _nbytes(x)
        key = (tuple(np.shape(x)), str(getattr(x, "dtype", "f32")),
               self.mesh_sig)
        self._reserve_slot()
        fut = self._pool.submit(self._copy_out, x)
        with self._lock:
            self._in_flight.append(fut)
            self.stats["bytes_out"] += nbytes
        return HostHandle(fut, key, nbytes, like=like if like is not None
                          else x)

    def upload(self, x, *, like=None) -> HostHandle:
        """Start moving a host buffer to the device on the lane thread
        (the H2D mirror of ``offload``); resolve with ``fetch``."""
        nbytes = _nbytes(x)
        key = (tuple(np.shape(x)), str(getattr(x, "dtype", "f32")),
               self.mesh_sig)
        self._reserve_slot()
        fut = self._pool.submit(self._copy_in, x, like)
        with self._lock:
            self._in_flight.append(fut)
            self.stats["bytes_in"] += nbytes
        return HostHandle(fut, key, nbytes, like=like)

    def host_value(self, handle: HostHandle):
        """Resolve a ``offload`` handle to its HOST buffer (no return
        trip).  Only the wait is exposed."""
        t0 = time.perf_counter()
        val = handle.future.result()
        self._charge(time.perf_counter() - t0)
        return val

    def prefetch(self, handle: HostHandle) -> HostHandle:
        """Start the return copy on the lane thread before the value is
        needed (the backward-pass half of double buffering).  Returns a
        new handle whose ``fetch`` yields the device array."""
        outbound = handle.future

        def back():
            return self._copy_in(outbound.result(), handle.like)

        self._reserve_slot()
        fut = self._pool.submit(back)
        with self._lock:
            self._in_flight.append(fut)
            self.stats["bytes_in"] += handle.nbytes
        h = HostHandle(fut, handle.key, handle.nbytes, like=handle.like)
        return h

    def fetch(self, handle: HostHandle):
        """Resolve a handle to a device array.  Only the time actually
        spent waiting (copy not yet finished) is exposed."""
        t0 = time.perf_counter()
        val = handle.future.result()
        self._charge(time.perf_counter() - t0)
        if isinstance(val, jax.Array):
            try:
                if val.sharding.memory_kind != "pinned_host":
                    return val              # prefetch already landed it
            except (AttributeError, TypeError):
                return val
            t0 = time.perf_counter()
            out = self._copy_in(val, handle.like)
            self._charge(time.perf_counter() - t0)
            with self._lock:
                self.stats["bytes_in"] += handle.nbytes
            return out
        # numpy fallback: the return trip is a plain device_put
        t0 = time.perf_counter()
        out = self._copy_in(val, handle.like)
        self._charge(time.perf_counter() - t0)
        with self._lock:
            self.stats["bytes_in"] += handle.nbytes
        return out

    def drain(self) -> None:
        """Wait for every in-flight copy (exposed: the step can't end
        with the link still busy)."""
        with self._lock:
            pending = list(self._in_flight)
            self._in_flight = []
        t0 = time.perf_counter()
        for f in pending:
            try:
                f.result()
            except Exception:
                pass
        self._charge(time.perf_counter() - t0)

    def reset_stats(self) -> Dict[str, Any]:
        """Return current stats and zero the counters (per-step use)."""
        with self._lock:
            out = dict(self.stats)
            self.stats = {"bytes_out": 0, "bytes_in": 0,
                          "transfers": 0, "copy_s": 0.0,
                          "exposed_s": 0.0}
        return out

    def close(self) -> None:
        self.drain()
        self._pool.shutdown(wait=True)


# ---------------------------------------------------------------------------
# bandwidth calibration
# ---------------------------------------------------------------------------

def calibration_path() -> str:
    return os.environ.get(CALIBRATION_ENV, DEFAULT_CALIBRATION_PATH)


def read_calibration(path: Optional[str] = None) -> Optional[dict]:
    p = path or calibration_path()
    try:
        with open(p) as f:
            cal = json.load(f)
        return cal if isinstance(cal, dict) else None
    except (OSError, ValueError):
        return None


def write_calibration(cal: dict, path: Optional[str] = None) -> str:
    p = path or calibration_path()
    with open(p, "w") as f:
        json.dump(cal, f, indent=2, sort_keys=True)
        f.write("\n")
    return p


def measure_pcie_gbps(size_mb: int = 64, repeats: int = 3) -> dict:
    """Time ``size_mb`` float32s through the lane's copy path, both
    directions; the reported figure is the round-trip-harmonic GB/s the
    simulator's ``2*bytes/pcie`` pricing wants.  Best-of-``repeats``
    (bandwidth is a capability, not an average).  On CPU-only builds
    this measures memcpy, which is still the honest cost of that
    build's 'offload'."""
    n = int(size_mb) * (1 << 20) // 4
    x = jax.device_put(np.ones((n,), np.float32))
    jax.block_until_ready(x)
    nbytes = float(n * 4)
    best_out = 0.0
    best_in = 0.0
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        h = to_host(x)
        jax.block_until_ready(h)
        dt = time.perf_counter() - t0
        best_out = max(best_out, nbytes / dt / 1e9)
        t0 = time.perf_counter()
        y = to_device(h, like=x)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        best_in = max(best_in, nbytes / dt / 1e9)
    # round-trip bandwidth: harmonic mean (the 2*bytes/pcie model moves
    # the same payload once each way)
    rt = 2.0 / (1.0 / best_out + 1.0 / best_in)
    return {"pcie_gbps": round(rt, 3),
            "device_to_host_gbps": round(best_out, 3),
            "host_to_device_gbps": round(best_in, 3),
            "pinned_host": host_memory_supported(),
            "backend": jax.default_backend(),
            "size_mb": int(size_mb), "repeats": int(repeats)}


def calibrated_pcie_gbps(default: float) -> float:
    """The link bandwidth planning should price: the ``MIMOSE_PCIE_GBPS``
    env wins, then this host's calibration file, then ``default``."""
    env = os.environ.get(PCIE_ENV)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    cal = read_calibration()
    if cal:
        try:
            v = float(cal.get("pcie_gbps", 0.0))
            if v > 0.0:
                return v
        except (TypeError, ValueError):
            pass
    return float(default)
