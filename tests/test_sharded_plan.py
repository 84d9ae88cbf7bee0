"""Sharding-aware planning: per-device byte accounting, mesh budgets,
plan feasibility per device, and mesh-keyed caches.

MeshBudget is pure axis-size math (no jax.Mesh, no fake devices), so a
(16, 16) pod budget is exercised here on the single CPU device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MeshBudget, MimosePlanner, fixed_train_bytes,
                        fixed_train_bytes_per_device, greedy_plan_sharded,
                        simulate_sharded)
from repro.core.collector import ShuttlingCollector, unit_residual_bytes
from repro.launch.mesh import make_production_mesh, parse_mesh_shape
from repro.models.lm import PlanUnit, build_model
from repro.models.registry import get_config
from repro.sharding import specs as SP
from repro.sharding.budget import spec_divisor


@pytest.fixture(scope="module")
def toy():
    cfg = get_config("bert_base_paper").reduced(
        num_layers=4, d_model=128, d_ff=256, vocab_size=512,
        dtype="float32")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((4, 64), jnp.int32),
             "labels": jnp.ones((4, 64), jnp.int32)}
    return lm, params, batch


def _collect(lm, params, batch, budget=None):
    return ShuttlingCollector(lm, mesh_budget=budget).collect(params, batch)


# ---------------------------------------------------------------------------
# divisor accounting
# ---------------------------------------------------------------------------

def test_unit_divisors_exact_on_handmade_unit():
    """Per-device bytes match the specs.py divisor rules exactly on a
    unit whose vjp closure is known: two matmuls with a relu between.

    Closure leaves: x (B, S, d) boundary tensor (saved for the x*x
    term), the relu mask (B, S, f) bool and h = relu(x @ w1) (B, S, f)
    float — both tensor-parallel intermediates — plus the two weights
    (which must be excluded: they live in the fixed per-device bytes)."""
    B, S, d, f = 8, 16, 32, 64
    w1 = jnp.ones((d, f), jnp.float32)
    w2 = jnp.ones((f, d), jnp.float32)

    def apply(p, x):
        return jax.nn.relu(x @ p["w1"]) @ p["w2"] + x * x

    unit = PlanUnit("toy", 0, {"w1": w1, "w2": w2}, apply)
    x = jax.ShapeDtypeStruct((B, S, d), jnp.float32)
    x_bytes = B * S * d * 4
    h_bytes = B * S * f * 4
    mask_bytes = B * S * f * 1                     # bool relu mask

    info = unit_residual_bytes(unit, x)
    assert info["activation_bytes"] == x_bytes + h_bytes + mask_bytes
    assert info["device_activation_bytes"] == info["activation_bytes"]

    # data-only mesh: every leaf shards the batch axis over 4 ways
    b4 = MeshBudget.from_shape((4,), 1e9)
    info4 = unit_residual_bytes(unit, x, b4)
    assert info4["device_activation_bytes"] == (x_bytes + h_bytes
                                                + mask_bytes) // 4

    # (data=4, model=2): the boundary tensor (last dim == d_model) stays
    # replicated over model; the intermediates divide by data * model
    b42 = MeshBudget.from_shape((4, 2), 1e9)
    info42 = unit_residual_bytes(unit, x, b42)
    assert info42["device_activation_bytes"] == (x_bytes // 4
                                                 + (h_bytes + mask_bytes)
                                                 // 8)

    # seq-parallel shards the boundary tensor's sequence axis over model
    b42sp = MeshBudget.from_shape((4, 2), 1e9, seq_parallel=True)
    info42sp = unit_residual_bytes(unit, x, b42sp)
    assert info42sp["device_activation_bytes"] == (x_bytes
                                                   + h_bytes
                                                   + mask_bytes) // 8

    # non-divisible batch: the data axis cannot shard, divisor falls back
    b3 = MeshBudget.from_shape((3,), 1e9)
    info3 = unit_residual_bytes(unit, x, b3)
    assert info3["device_activation_bytes"] == (x_bytes + h_bytes
                                                + mask_bytes)


def test_model_level_divisors_bounded_and_consistent(toy):
    """On a real model the per-device vector obeys the divisor algebra:
    identical without a mesh, divided by up to data*model ways with one,
    monotone in the mesh size."""
    lm, params, batch = toy
    g = _collect(lm, params, batch).device_activation_vector()
    d1 = _collect(lm, params, batch,
                  MeshBudget.from_shape((1,), 1e9)).device_activation_vector()
    d4 = _collect(lm, params, batch,
                  MeshBudget.from_shape((4,), 1e9)).device_activation_vector()
    d22 = _collect(lm, params, batch,
                   MeshBudget.from_shape((2, 2), 1e9)
                   ).device_activation_vector()
    # a 1-device mesh shards nothing
    np.testing.assert_array_equal(d1, np.floor(d1))
    assert (d1 >= g * 0.99).all() and (d1 <= g * 1.01).all()
    # batch=4 over data=4: every batch-led leaf divides by 4 (scalars and
    # broadcast constants may not), so the vector sits in [g/4, g]
    assert (d4 >= d1 / 4 * 0.99).all() and (d4 < d1).all()
    assert (d4 <= d1 / 4 * 1.01).all()          # bert residuals all batch-led
    # (2,2): data 2 always, model 2 only on TP intermediates
    assert (d22 >= d1 / 4 * 0.99).all() and (d22 <= d1 / 2).all()


def test_fixed_bytes_per_device_matches_param_spec(toy):
    """The per-device fixed bytes equal the leaf-wise sum over
    specs.param_spec divisors (params + grads + fp32 moments)."""
    lm, params, batch = toy
    budget = MeshBudget.from_shape((4, 2), 1e9)
    got = fixed_train_bytes_per_device(params, budget, scanned=False)

    expected = 0.0
    axis = budget.axis_dict

    def one(path, leaf):
        nonlocal expected
        spec = SP.param_spec(path, leaf, scanned=False, mesh=None,
                             model_dim=2)
        div = spec_divisor(spec, axis)
        nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        n = int(np.prod(leaf.shape))
        expected += 2 * nbytes / div + 2 * 4 * n / div
        return leaf

    jax.tree_util.tree_map_with_path(one, params)
    assert got == pytest.approx(expected)
    # mesh (1,) degenerates to the global fixed bytes
    assert fixed_train_bytes_per_device(
        params, MeshBudget.from_shape((1,), 1e9)) == pytest.approx(
        fixed_train_bytes(params))


def test_attn_replicated_policy_raises_fixed_bytes(toy):
    """attn_replicated keeps attention projections replicated per
    device (specs.param_spec), so the per-device fixed bytes must grow
    and the budget signature must change — the dry-run passes the same
    policy flags it shards the real params with."""
    lm, params, batch = toy
    tp = MeshBudget.from_shape((4, 2), 1e9)
    rep = MeshBudget.from_shape((4, 2), 1e9, attn_replicated=True)
    assert fixed_train_bytes_per_device(params, rep) > \
        fixed_train_bytes_per_device(params, tp)
    assert rep.sig() != tp.sig()


def test_zero1_shards_moments_only(toy):
    lm, params, batch = toy
    plain = fixed_train_bytes_per_device(
        params, MeshBudget.from_shape((4, 2), 1e9))
    z1 = fixed_train_bytes_per_device(
        params, MeshBudget.from_shape((4, 2), 1e9, zero1=True))
    assert z1 < plain
    # params + grads are untouched; only the 8-bytes-per-param moments
    # shrink, by at most the data ways
    assert z1 >= plain - (plain * 8 / 16)


# ---------------------------------------------------------------------------
# planning under per-device budgets
# ---------------------------------------------------------------------------

def test_greedy_plan_respects_per_device_budget(toy):
    """A (4,) and a (2, 2) mesh get different per-device vectors and
    budgets; both plans must keep the scheduler's modelled footprint
    under their own per-device budget."""
    lm, params, batch = toy
    for shape in ((4,), (2, 2)):
        budget = MeshBudget.from_shape(
            shape, 0.9 * fixed_train_bytes(params), zero1=True)
        planner = MimosePlanner(lm, mesh_budget=budget, warmup_samples=1,
                                quantum=32)
        mask, info = planner.plan(params, batch)
        col = planner.collector.collect(params, batch)
        act = col.device_activation_vector()
        # what the planner holds whatever it remats: the fixed bytes and
        # the output head's at this geometry
        fixed = planner.planned_fixed_bytes(params, batch)
        # mask is a typed action tuple now: KEEP units are the saved ones
        saved = float(act[np.asarray(mask, dtype=int) == 0].sum())
        assert fixed + saved <= budget.hbm_per_device_bytes, shape
        # and the scheduler helper agrees with the planner's plan
        p2 = greedy_plan_sharded(act, budget, fixed)
        assert list(p2.remat) == list(mask)


def test_sharded_feasible_where_single_device_is_not(toy):
    """The acceptance scenario: one per-device HBM below the global
    fixed bytes is infeasible on 1 device but plannable on a mesh."""
    lm, params, batch = toy
    hbm = 0.75 * fixed_train_bytes(params)

    one = MeshBudget.from_shape((1,), hbm)
    p1 = MimosePlanner(lm, mesh_budget=one, warmup_samples=1, quantum=32)
    mask1, _ = p1.plan(params, batch)
    col1 = p1.collector.collect(params, batch)
    sim1 = simulate_sharded(col1.device_activation_vector(), mask1,
                            p1.resolve_fixed_bytes(params), 1)
    assert not sim1.fits(hbm)            # fixed bytes alone blow the budget

    mesh = MeshBudget.from_shape((4, 2), hbm, zero1=True)
    col = ShuttlingCollector(lm, mesh_budget=mesh).collect(params, batch)
    margin = 2 * float(col.device_activation_vector().max())
    pm = MimosePlanner(lm, max(hbm - margin, 0.0), mesh_budget=mesh,
                       warmup_samples=1, quantum=32)
    mask, _ = pm.plan(params, batch)
    sim = simulate_sharded(col.device_activation_vector(), mask,
                           pm.resolve_fixed_bytes(params), mesh.n_devices)
    assert sim.fits(hbm)
    assert sim.n_devices == 8
    assert sim.global_peak_bytes == pytest.approx(
        8 * sim.peak_bytes_per_device)


def test_cache_key_distinguishes_mesh_shapes(toy):
    lm, params, batch = toy
    a = MimosePlanner(lm, 1e9, mesh_budget=MeshBudget.from_shape((4,), 1e9),
                      warmup_samples=1, quantum=32)
    b = MimosePlanner(lm, 1e9, mesh_budget=MeshBudget.from_shape((2, 2), 1e9),
                      warmup_samples=1, quantum=32)
    c = MimosePlanner(lm, 1e9, warmup_samples=1, quantum=32)
    keys = {a.plan_key(batch), b.plan_key(batch), c.plan_key(batch)}
    assert len(keys) == 3                # same batch, three distinct keys
    # bucket component is shared; only the mesh signature differs
    assert len({k[0] for k in keys}) == 1
    # zero1 / seq-parallel flip the signature too (different divisors)
    z = MeshBudget.from_shape((4,), 1e9, zero1=True)
    assert z.sig() != MeshBudget.from_shape((4,), 1e9).sig()
    a.plan(params, batch)
    assert list(a.cache) == [a.plan_key(batch)]


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def test_make_production_mesh_explicit_shape():
    m = make_production_mesh(shape=(1, 1))
    assert m.axis_names == ("data", "model")
    m = make_production_mesh(shape=(1,))
    assert m.axis_names == ("data",)
    with pytest.raises(ValueError, match="positive"):
        make_production_mesh(shape=(0, 2))
    with pytest.raises(ValueError, match="axis_names"):
        make_production_mesh(shape=(1, 1, 1, 1))
    with pytest.raises(ValueError, match="does not match"):
        make_production_mesh(shape=(1,), axis_names=("data", "model"))
    if len(jax.devices()) < 8:
        with pytest.raises(RuntimeError, match="device_count"):
            make_production_mesh(shape=(4, 2))


def test_parse_mesh_shape():
    assert parse_mesh_shape("4x2") == (4, 2)
    assert parse_mesh_shape("2x16x16") == (2, 16, 16)
    with pytest.raises(ValueError):
        parse_mesh_shape("4x")
    with pytest.raises(ValueError):
        parse_mesh_shape("0x2")


def test_mesh_budget_validation():
    with pytest.raises(ValueError, match="positive"):
        MeshBudget.from_shape((), 1e9)
    with pytest.raises(ValueError, match="axis_names"):
        MeshBudget.from_shape((2, 2, 2, 2), 1e9)
    b = MeshBudget.from_shape((2, 4, 8), 1e9)
    assert b.n_devices == 64 and b.data_ways == 8 and b.model_ways == 8
