"""The reader of ``step_host_idle_share.train`` on hand-made runs: it
sums the idle gaps the program's spans name, and nothing else."""
from __future__ import annotations

import pytest

from bench import run

READ = run.reader("step_host_idle_share.train")


def _run(idle_gaps, window_s=2.0, kind="train"):
    return {"kind": kind, "steps": [], "window_s": 51.0,
            "trace": {"busy_s": 1.5, "window_s": window_s,
                      "device_ops": [], "idle_gaps": idle_gaps}}


def test_sums_the_program_gaps_only():
    gaps = [["program:record", 0.06], ["bench:feed", 0.3],
            ["program:prepare", 0.03], ["bench:step", 0.2],
            ["program:sync", 0.01], ["short_gaps", 0.05],
            ["bench:traced_window", 0.04], ["unattributed", 0.02]]
    assert READ(_run(gaps)) == pytest.approx(100.0 * 0.10 / 2.0)


def test_zero_when_no_gap_is_the_programs():
    assert READ(_run([["bench:feed", 0.3], ["short_gaps", 0.01]])) == 0.0


def test_none_without_a_trace_or_for_serving():
    r = _run([["program:step", 0.1]])
    r["trace"] = None
    assert READ(r) is None
    assert READ(_run([["program:step", 0.1]], window_s=0.0)) is None
    assert READ(_run([["program:decode_batch", 0.1]], kind="serve")) is None
