"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the
device operations that took most time, and the idle gaps by what the
host was doing.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation that ran.  Busy time is the union of
those intervals, averaged over the chips traced.  An idle gap is a stretch
of the traced window in which no operation runs on the first chip; it is
named by the innermost host span that covers its midpoint among those
whose name starts with one of ``SPAN_PREFIXES`` (the harness's own
``jax.profiler.TraceAnnotation``s and the program's spans it forwards),
else ``"unattributed"``.  Gaps shorter than 20 us, the launch gaps
between back-to-back operations, are summed as ``short_gaps``.
Timestamps of all planes share the session's clock, in nanoseconds; on
a TPU v5e the device's read about 1 ms earlier than the host's (the
recorded test trace shows operations ~1.1 ms before the host span that
launched them), so a window's edges, and a gap's name near a span's edge, are
uncertain by that much.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

SPAN_PREFIXES = ("bench:", "program:")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10
SHORT_GAP_NS = 20_000
SHORT_GAP = "short_gaps"


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load_events(path: str):
    """(device op events per chip, host span events) as plain tuples:
    ``{chip: [(name, start_ns, end_ns)]}``, ``[(name, start_ns, end_ns)]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            dev[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIXES))
    return dev, host


def reduce_events(dev: dict, host: list, window=None) -> dict | None:
    """``busy_s`` (averaged over chips), ``window_s``, ``device_ops`` and
    ``idle_gaps`` (each at most ``TOP`` entries of [name, seconds]).
    ``window``: (start_ns, end_ns) of the traced window on the trace's
    clock; by default the span from the first to the last operation.
    None where no operation ran on any device."""
    chips = [c for c in sorted(dev) if dev[c]]
    if not chips:
        return None
    if window is None:
        window = (min(s for c in chips for _, s, _ in dev[c]),
                  max(e for c in chips for _, _, e in dev[c]))
    w0, w1 = window
    busy = []
    for c in chips:
        iv = _union((max(s, w0), min(e, w1)) for _, s, e in dev[c]
                    if e > w0 and s < w1)
        busy.append(sum(e - s for s, e in iv))
    by_op = defaultdict(float)
    for name, s, e in dev[chips[0]]:
        if e > w0 and s < w1:
            by_op[name] += (min(e, w1) - max(s, w0))
    first = _union((max(s, w0), min(e, w1)) for _, s, e in dev[chips[0]]
                   if e > w0 and s < w1)
    gaps, t = [], w0
    for s, e in first:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    spans = sorted(host, key=lambda x: x[1])
    starts = [hs for _, hs, _ in spans]
    by_gap = defaultdict(float)
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            by_gap[SHORT_GAP] += e - s
            continue
        mid = (s + e) / 2
        # spans nest: the latest-starting span still open at the
        # midpoint is the innermost one
        name = "unattributed"
        for k in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[k][2] >= mid:
                name = spans[k][0]
                break
        by_gap[name] += e - s
    top = lambda d: [[k, v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (w1 - w0) / 1e9,
            "device_ops": top(by_op), "idle_gaps": top(by_gap),
            "chips": len(chips)}


def reduce_trace(log_dir: str, window_names=("bench:traced_window",)):
    """Reduce the newest trace under ``log_dir``.  The traced window is
    the host span named ``bench:traced_window`` when there is one."""
    path = find_xplane(log_dir)
    if path is None:
        return None
    dev, host = load_events(path)
    win = [(s, e) for name, s, e in host if name in window_names]
    return reduce_events(dev, host, win[0] if win else None)
