"""Tests for real asynchronous overlapped offload (ISSUE 8): the
double-buffered ``TransferLane``, host-memory capability probes, the
SPMD offload probe + visible degradation counters, OFFLOAD_OPT
planning (simulator / greedy / solver / planner wiring) and split-step
execution in the trainer, the Pallas DMA copy kernel, bandwidth
calibration, and snapshot restore under calibrated-bandwidth drift.

Marked ``offload`` (own CI job); everything here is CPU-safe and fast
so the full local run still includes it."""
import importlib.util
import json
import pathlib
import time
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.actions import Action
from repro.core import MimosePlanner, greedy_plan, simulate
from repro.core.planner import PlanInfo, PlannerBase
from repro.core.scheduler import Plan
from repro.core.solver import solve
from repro.kernels.offload_dma import dma_copy
from repro.kernels.ops import residual_dma_copy
from repro.launch.report import engine_report
from repro.models import lm as lm_mod
from repro.models.lm import (build_model, configure_offload,
                             spmd_offload_supported)
from repro.models.registry import get_config
from repro.train.resilience import planner_state, restore_planner_state
from repro.train.transfer import (CALIBRATION_ENV, PCIE_ENV, TransferLane,
                                  calibrated_pcie_gbps, measure_pcie_gbps,
                                  write_calibration)
from repro.train.trainer import Trainer

pytestmark = pytest.mark.offload

HBM = 8e9
PCIE = 16e9
ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("bert_base_paper").reduced(
        num_layers=4, d_model=64, d_ff=128, vocab_size=256)
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    return cfg, lm, params


def _batch(S, B=2):
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}


class StubPlanner(PlannerBase):
    """Fixed-action planner: lets trainer tests pick the exact plan."""
    name = "stub"
    quantum = 1

    def __init__(self, actions):
        self.actions = tuple(Action(int(a)) for a in actions)
        self.stats = {}

    def plan(self, params, batch):
        plan = Plan([a is Action.REMAT for a in self.actions],
                    0.0, 0.0, 0.0, actions=self.actions)
        return plan.as_actions(), PlanInfo(0, 0, False, False, plan)


# ---------------------------------------------------------------------------
# simulator: OFFLOAD_OPT semantics
# ---------------------------------------------------------------------------

def test_simulate_opt_offload_reduces_peak_by_parked_bytes():
    act = [10.0] * 4
    opt = [7.0, 5.0, 3.0, 2.0]
    plan = [Action.OFFLOAD_OPT, Action.KEEP, Action.KEEP,
            Action.OFFLOAD_OPT]
    base = simulate(act, [Action.KEEP] * 4, 100.0, opt_bytes=opt)
    parked = simulate(act, plan, 100.0, opt_bytes=opt,
                      pcie_bytes_per_s=PCIE, overlap=0.5)
    # parked moments leave the fixed footprint for the WHOLE step, so
    # every liveness sample — and therefore the peak — drops by exactly
    # the parked bytes
    assert parked.peak_bytes == base.peak_bytes - (7.0 + 2.0)
    assert parked.opt_offload_bytes == 9.0
    assert parked.opt_offload_units == 2
    assert parked.opt_transfer_s == pytest.approx(2.0 * 9.0 / PCIE)
    assert parked.exposed_transfer_s == pytest.approx(
        0.5 * 2.0 * 9.0 / PCIE)


def test_simulate_opt_traffic_is_per_step_not_per_microbatch():
    act = [10.0] * 4
    opt = [8.0] * 4
    plan = [Action.OFFLOAD_OPT] + [Action.KEEP] * 3
    one = simulate(act, plan, 50.0, opt_bytes=opt, microbatch=1,
                   pcie_bytes_per_s=PCIE)
    four = simulate(act, plan, 50.0, opt_bytes=opt, microbatch=4,
                    pcie_bytes_per_s=PCIE)
    # the optimizer update runs once per step: its round trip must not
    # scale with the gradient-accumulation split
    assert four.opt_transfer_s == one.opt_transfer_s
    assert four.opt_offload_bytes == one.opt_offload_bytes


def test_simulate_without_opt_vector_makes_offload_opt_a_free_noop():
    act = [10.0] * 3
    w = simulate(act, [Action.OFFLOAD_OPT, Action.KEEP, Action.KEEP],
                 40.0)
    k = simulate(act, [Action.KEEP] * 3, 40.0)
    # back-compat: plans replayed without a moment vector behave exactly
    # as 3-action plans did
    assert w.peak_bytes == k.peak_bytes
    assert w.opt_offload_bytes == 0.0 and w.opt_transfer_s == 0.0


# ---------------------------------------------------------------------------
# greedy + solver: OFFLOAD_OPT selection
# ---------------------------------------------------------------------------

def test_greedy_parks_moments_when_remat_alone_cannot_fit():
    act = [10.0] * 4
    out = [1.0] * 4
    off = [9.0] * 4
    fl = [1e9] * 4
    opt = [30.0] * 4
    fixed, budget = 100.0, 95.0   # fixed alone exceeds the budget
    p = greedy_plan(act, budget, fixed, flops=fl, output_bytes=out,
                    offload_bytes=off, opt_bytes=opt,
                    pcie_bytes_per_s=PCIE, offload_overlap=0.5)
    assert p.n_opt >= 1
    sim = simulate(act, p.actions, fixed, out, fl, offload_bytes=off,
                   opt_bytes=opt, pcie_bytes_per_s=PCIE, overlap=0.5)
    assert sim.fits(budget)


def test_greedy_opt_bytes_is_a_pure_extension_under_slack():
    act, out, off, fl = [10.0] * 4, [1.0] * 4, [9.0] * 4, [1e9] * 4
    base = greedy_plan(act, 500.0, 50.0, flops=fl, output_bytes=out,
                       offload_bytes=off, pcie_bytes_per_s=PCIE)
    w = greedy_plan(act, 500.0, 50.0, flops=fl, output_bytes=out,
                    offload_bytes=off, opt_bytes=[5.0] * 4,
                    pcie_bytes_per_s=PCIE)
    # generous budget: nothing needs to move, and offering OFFLOAD_OPT
    # must not perturb the plan
    assert w.n_opt == 0
    assert w.as_actions() == base.as_actions()


def test_solver_exhaustive_finds_offload_opt_when_required():
    vec = dict(est_mem=[10.0, 10.0, 10.0], flops=[1e9] * 3,
               output_bytes=[1.0] * 3, offload_bytes=[9.0] * 3,
               opt_bytes=[60.0, 0.0, 0.0])
    res = solve(lambda k: vec, budget_bytes=95.0, fixed_bytes=100.0,
                method="exhaustive", pcie_bytes_per_s=PCIE)
    # only parking unit 0's moments can bring the fixed footprint under
    # budget; the exhaustive enumeration must find it
    assert res.feasible
    assert res.plan.n_opt >= 1
    assert res.plan.actions[0] is Action.OFFLOAD_OPT


# ---------------------------------------------------------------------------
# planner wiring: the pinned moment vector + knob validation
# ---------------------------------------------------------------------------

def test_planner_opt_offload_requires_offload(tiny):
    _, lm, _ = tiny
    with pytest.raises(ValueError, match="needs offload=True"):
        MimosePlanner(lm, HBM, opt_offload=True)


def test_planner_pins_opt_vector_once(tiny):
    _, lm, params = tiny
    pl = MimosePlanner(lm, HBM, quantum=64, warmup_samples=1,
                       offload=True, opt_offload=True)
    pl.plan(params, _batch(64))
    v = pl._opt_vector
    assert v is not None and np.all(v > 0)
    np.testing.assert_allclose(pl._opt_bytes_planning(), v)
    assert "opt_bytes" in pl._hybrid_kwargs(64)
    pl.plan(params, _batch(128))
    # moment bytes are pure parameter-shape math: pinned by the first
    # collection, never refit per input size
    assert pl._opt_vector is v


def test_opt_bytes_planning_gated_off_in_scan_mode(tiny, monkeypatch):
    _, lm, params = tiny
    pl = MimosePlanner(lm, HBM, quantum=64, warmup_samples=1,
                       offload=True, opt_offload=True)
    pl.plan(params, _batch(64))
    assert pl._opt_bytes_planning() is not None
    # scan-mode moments are stacked across a chunk in one leaf: parking
    # cannot free a slice, so the action must not be offered
    monkeypatch.setattr(pl, "lm", types.SimpleNamespace(
        cfg=types.SimpleNamespace(remat_mode="scan")))
    assert pl._opt_bytes_planning() is None


# ---------------------------------------------------------------------------
# SPMD probe / degradation surfacing
# ---------------------------------------------------------------------------

def test_configure_offload_degrades_and_warns_once(monkeypatch):
    monkeypatch.setattr(lm_mod, "spmd_offload_supported",
                        lambda mesh=None: False)
    monkeypatch.setattr(lm_mod, "_spmd_offload_warned", set())
    stub = types.SimpleNamespace(offload_exec=True)
    with pytest.warns(RuntimeWarning, match="host offload unavailable"):
        assert configure_offload(stub) is True
    assert stub.offload_exec is False
    # warn-once per mesh signature: the second call stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert configure_offload(stub) is True


def test_configure_offload_keeps_capable_runtimes_enabled():
    assert spmd_offload_supported() is True       # single device
    stub = types.SimpleNamespace(offload_exec=False)
    assert configure_offload(stub) is False
    assert stub.offload_exec is True


def test_trainer_counts_offload_degradation():
    cfg = get_config("bert_base_paper").reduced(
        num_layers=4, d_model=64, d_ff=128, vocab_size=256)
    lm = build_model(cfg)
    lm.offload_exec = False           # what configure_offload sets on
    params = lm.init(jax.random.PRNGKey(0))   # a degraded mesh/runtime
    tr = Trainer(lm, StubPlanner([Action.OFFLOAD, Action.KEEP,
                                  Action.KEEP, Action.KEEP]))
    opt_state = tr.optimizer.init(params)
    for _ in range(3):
        params, opt_state, _ = tr.step(params, opt_state, _batch(32))
    assert all(s.offload_degraded for s in tr.history)
    assert tr.planner.stats["offload_fallbacks"] == 1   # once per bucket
    s = tr.summary()
    assert s["offload_degraded_steps"] == 3
    assert s["offload_fallbacks"] == 1
    assert "offload degraded to remat" in engine_report(tr, tr.planner)


# ---------------------------------------------------------------------------
# TransferLane
# ---------------------------------------------------------------------------

def test_transfer_lane_round_trip_and_stats():
    lane = TransferLane()
    x = jnp.arange(1024, dtype=jnp.float32)
    y = lane.fetch(lane.offload(x))
    assert isinstance(y, jax.Array)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    st = lane.reset_stats()
    assert st["bytes_out"] == 4096 and st["bytes_in"] == 4096
    assert st["transfers"] >= 2 and st["exposed_s"] >= 0.0
    assert lane.stats["bytes_out"] == 0       # reset zeroes the counters
    lane.close()


def test_transfer_lane_host_value_skips_return_trip():
    lane = TransferLane()
    h = lane.offload(jnp.full((256,), 3.0, jnp.float32))
    hv = lane.host_value(h)
    on_host = isinstance(hv, np.ndarray) or (
        isinstance(hv, jax.Array)
        and hv.sharding.memory_kind == "pinned_host")
    assert on_host
    np.testing.assert_array_equal(np.asarray(hv), np.full((256,), 3.0))
    st = lane.reset_stats()
    assert st["bytes_out"] == 1024 and st["bytes_in"] == 0
    lane.close()


def test_transfer_lane_upload_mirrors_offload():
    lane = TransferLane()
    host = np.full((128,), 7.0, np.float32)
    y = lane.fetch(lane.upload(host))
    assert isinstance(y, jax.Array)
    np.testing.assert_array_equal(np.asarray(y), host)
    assert lane.reset_stats()["bytes_in"] == 512
    lane.close()


def test_transfer_lane_prefetch_lands_on_device():
    lane = TransferLane()
    x = jnp.arange(64, dtype=jnp.float32)
    h2 = lane.prefetch(lane.offload(x))
    y = lane.fetch(h2)
    assert isinstance(y, jax.Array)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    st = lane.reset_stats()
    assert st["bytes_out"] == 256 and st["bytes_in"] == 256
    lane.close()


def test_transfer_lane_depth_bounds_in_flight_and_charges_waits():
    lane = TransferLane(depth=2)
    orig = lane._copy_out

    def slow(x):
        time.sleep(0.05)
        return orig(x)

    lane._copy_out = slow
    for _ in range(3):
        lane.offload(jnp.ones((8,), jnp.float32))
    # the third enqueue found both buffers busy: the wait for the oldest
    # copy is exactly what the lane books as exposed time
    assert lane.stats["exposed_s"] > 0.0
    lane.drain()
    lane.close()


# ---------------------------------------------------------------------------
# trainer: OFFLOAD_OPT split-step execution
# ---------------------------------------------------------------------------

def test_trainer_opt_split_matches_fused_step_exactly():
    cfg = get_config("bert_base_paper").reduced(
        num_layers=4, d_model=64, d_ff=128, vocab_size=256)
    lm = build_model(cfg)
    losses = {}
    trainers = {}
    for name, acts in (("fused", [Action.KEEP] * 4),
                       ("split", [Action.KEEP, Action.KEEP,
                                  Action.OFFLOAD_OPT, Action.KEEP])):
        params = lm.init(jax.random.PRNGKey(0))
        tr = Trainer(lm, StubPlanner(acts))
        opt_state = tr.optimizer.init(params)
        ls = []
        for _ in range(4):
            params, opt_state, loss = tr.step(params, opt_state,
                                              _batch(32))
            ls.append(loss)
        losses[name] = ls
        trainers[name] = (tr, opt_state)
    # parking moments on the host must not change the math at all
    assert losses["split"] == losses["fused"]
    tr, opt_state = trainers["split"]
    st = tr.history[-1]
    assert st.opt_offload_units == 1
    assert tr._parked == {2}
    leaf = jax.tree_util.tree_leaves(tr._moment_get(opt_state.m, 2))[0]
    on_host = isinstance(leaf, np.ndarray) or (
        isinstance(leaf, jax.Array)
        and leaf.sharding.memory_kind == "pinned_host")
    assert on_host                    # moments live off-device between steps
    # telemetry: the lane measured real traffic and the simulator priced
    # the same bytes
    assert st.sim_transfer_s > 0.0 and st.exposed_transfer_s >= 0.0
    s = tr.summary()
    assert s["mean_opt_offload_units"] > 0
    assert s["sim_transfer_s"] > 0.0
    assert "offload: exposed transfer" in engine_report(tr, tr.planner)


# ---------------------------------------------------------------------------
# Pallas DMA copy kernel (interpret mode on CPU)
# ---------------------------------------------------------------------------

def test_dma_copy_identity_including_padding_tail():
    cases = [((128,), jnp.float32), ((33,), jnp.float32),
             ((7, 5), jnp.bfloat16), ((1,), jnp.int32)]
    for shape, dtype in cases:
        n = int(np.prod(shape))
        x = jnp.arange(n, dtype=jnp.float32).astype(dtype).reshape(shape)
        y = dma_copy(x, chunk_elems=16, interpret=True)
        assert y.shape == x.shape and y.dtype == x.dtype
        np.testing.assert_array_equal(
            np.asarray(y, np.float32), np.asarray(x, np.float32))


def test_residual_dma_copy_wrapper():
    x = jnp.linspace(0.0, 1.0, 1000, dtype=jnp.float32).reshape(10, 100)
    y = residual_dma_copy(x)
    assert y.shape == x.shape and y.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# bandwidth calibration + snapshot restore under calibration drift
# ---------------------------------------------------------------------------

def test_calibrated_pcie_hierarchy(tmp_path, monkeypatch):
    path = tmp_path / "cal.json"
    monkeypatch.setenv(CALIBRATION_ENV, str(path))
    monkeypatch.delenv(PCIE_ENV, raising=False)
    assert calibrated_pcie_gbps(16.0) == 16.0     # nothing calibrated yet
    write_calibration({"pcie_gbps": 3.25})
    assert calibrated_pcie_gbps(16.0) == 3.25     # file beats default
    from repro.launch.roofline import calibrated_pcie_gbps as launch_cal
    assert launch_cal(12.0) == 3.25               # launch default delegates
    monkeypatch.setenv(PCIE_ENV, "7.5")
    assert calibrated_pcie_gbps(16.0) == 7.5      # env wins outright
    monkeypatch.delenv(PCIE_ENV)
    path.write_text("not json")
    assert calibrated_pcie_gbps(16.0) == 16.0     # corrupt file ignored


def test_measure_pcie_reports_round_trip_harmonic():
    cal = measure_pcie_gbps(size_mb=1, repeats=1)
    assert cal["pcie_gbps"] > 0
    assert cal["backend"] == jax.default_backend()
    hm = 2.0 / (1.0 / cal["device_to_host_gbps"]
                + 1.0 / cal["host_to_device_gbps"])
    assert cal["pcie_gbps"] == pytest.approx(hm, abs=0.01)


def test_bench_offload_bw_tool_writes_calibration(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench_offload_bw", ROOT / "tools" / "bench_offload_bw.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = tmp_path / "cal.json"
    assert mod.main(["--size-mb", "1", "--repeats", "1",
                     "--out", str(out)]) == 0
    cal = json.loads(out.read_text())
    assert cal["pcie_gbps"] > 0 and cal["size_mb"] == 1
    # the tool's output is exactly what the --pcie-gbps default reads
    monkeypatch.setenv(CALIBRATION_ENV, str(out))
    monkeypatch.delenv(PCIE_ENV, raising=False)
    assert calibrated_pcie_gbps(999.0) == cal["pcie_gbps"]


def test_restore_drops_plans_on_calibrated_bandwidth_change(
        tiny, tmp_path, monkeypatch):
    """A recalibration between snapshot and resume changes the planner's
    link pricing; plans solved at the old bandwidth must be dropped, not
    resurrected (satellite of the plan_key roofline-knob guarantee)."""
    _, lm, params = tiny
    src = MimosePlanner(lm, HBM, quantum=64, warmup_samples=1,
                        offload=True, pcie_gbps=16.0)
    src.plan(params, _batch(64))
    state = planner_state(src)
    assert state["plans"]
    monkeypatch.setenv(CALIBRATION_ENV, str(tmp_path / "cal.json"))
    monkeypatch.delenv(PCIE_ENV, raising=False)
    write_calibration({"pcie_gbps": 1.72})       # bench tool ran meanwhile
    gbps = calibrated_pcie_gbps(16.0)
    assert gbps == 1.72
    dst = MimosePlanner(lm, HBM, quantum=64, warmup_samples=1,
                        offload=True, pcie_gbps=gbps)
    summary = restore_planner_state(dst, state)
    assert summary["restored_plans"] == 0
    assert summary["dropped_plans"] == len(state["plans"])
    # the learned estimators still restore — only the stale plans drop
    assert dst.estimator.num_samples == src.estimator.num_samples
