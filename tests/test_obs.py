"""Unified telemetry layer tests (repro.obs): metrics registry under
concurrent writers, event-log schema round-trip, Perfetto trace
well-formedness, disabled-path no-op guarantees, and the
predicted-vs-actual drift series agreeing with the planner's refit
trigger."""
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MimosePlanner
from repro.models.lm import build_model
from repro.models.registry import get_config
from repro.obs import (NULL_SPAN, SCHEMA_VERSION, EventLog, MetricsRegistry,
                       NullEventLog, NullTracer, SpanTracer, StatsView,
                       Telemetry, TRACK_STEP, build_telemetry,
                       flush_telemetry, read_events)
from repro.optim.adamw import AdamW
from repro.train.trainer import Trainer

pytestmark = pytest.mark.obs


@pytest.fixture(scope="module")
def small():
    cfg = get_config("bert_base_paper").reduced(
        num_layers=4, d_model=128, d_ff=256, vocab_size=512)
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    return cfg, lm, params


def _batch(S, B=2, vocab=512):
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_snapshot_under_concurrent_writers():
    """No lost increments: every (labelset, thread) cell has exactly one
    writer, so N threads x K bumps must sum exactly — the property the
    background solver thread relies on when it shares planner counters
    with the training thread."""
    reg = MetricsRegistry()
    c = reg.counter("hits", "test counter")
    h = reg.histogram("lat", "test histogram")
    N, K = 8, 5000

    def worker(i):
        for _ in range(K):
            c.inc()
            c.inc(1.0, bucket=i % 2)
            h.observe(0.001 * (i + 1))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == N * K
    assert c.value(bucket=0) == (N // 2) * K
    assert c.value(bucket=1) == (N // 2) * K
    assert c.total() == 2 * N * K
    assert h.total() == N * K
    snap = reg.snapshot()
    assert snap["hits"]["total"] == 2 * N * K
    assert snap["hits"]["kind"] == "counter"
    assert snap["lat"]["kind"] == "histogram"


def test_statsview_mapping_and_adopt_merge():
    """StatsView serves legacy dict call sites; attach() re-homes its
    metrics into another registry, merging same-named counters into one
    shared object (how planner and watchdog oom_events converge)."""
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    a = StatsView(r1, scalars={"oom_events": "oom_total"},
                  labeled={"by_bucket": ("oom_total", "bucket")})
    b = StatsView(r2, scalars={"oom_events": "oom_total"})
    a.inc("oom_events", bucket=128)
    b.inc("oom_events")
    b.attach(r1)                      # merge: both now back onto r1
    assert a["oom_events"] == 2
    assert b["oom_events"] == 2
    assert a.metric("oom_events") is b.metric("oom_events")
    assert dict(a["by_bucket"]) == {128: 1}
    # absolute set replaces the unlabeled cells; labeled cells
    # (bucket=128 above) are a separate labelset and keep counting
    c = StatsView(r1, scalars={"retries": "retry_total"})
    c["retries"] = 7
    assert c["retries"] == 7
    c["retries"] += 1
    assert c["retries"] == 8
    a["free_form"] = [1, 2]           # unknown keys -> aux passthrough
    assert dict(a)["free_form"] == [1, 2]
    with pytest.raises(TypeError):
        a["by_bucket"] = {}           # label views are not assignable


def test_prometheus_export_shape():
    reg = MetricsRegistry()
    reg.counter("c", "help c").inc(2, bucket=64)
    reg.histogram("h").observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE c counter" in text
    assert 'c{bucket="64"} 2' in text
    assert "# TYPE h histogram" in text
    assert 'h_bucket{le="1.0"}' in text
    assert "h_count 1" in text
    json.loads(reg.to_json())         # valid JSON doc


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def test_event_schema_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(capacity=8, path=path) as log:
        log.emit("plan", bucket=np.int64(128), source="greedy",
                 est=np.array([1.0, 2.0]))
        log.emit("drift", bucket=128, rel_err=0.25, refit=True)
        for i in range(10):
            log.emit("tick", i=i)
    recs = list(read_events(path))
    assert len(recs) == 12            # the file sink keeps everything
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[0]["kind"] == "plan"
    assert recs[0]["bucket"] == 128   # numpy degraded to plain JSON
    assert recs[0]["est"] == [1.0, 2.0]
    assert recs[1]["refit"] is True
    assert [r["i"] for r in read_events(path, kind="tick")] == list(range(10))
    # the in-memory ring is bounded: only the newest 8 survive
    with EventLog(capacity=8) as ring:
        for i in range(20):
            ring.emit("tick", i=i)
        assert len(ring) == 8
        assert [r["i"] for r in ring.tail(3)] == [17, 18, 19]


def test_event_log_skips_malformed_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path=path) as log:
        log.emit("a")
    with open(path, "a") as f:
        f.write("not json\n")
    with open(path, "a") as f:
        f.write(json.dumps({"v": 1, "ts": 0, "kind": "b"}) + "\n")
    assert [r["kind"] for r in read_events(path)] == ["a", "b"]


# ---------------------------------------------------------------------------
# span tracer / Perfetto
# ---------------------------------------------------------------------------

def test_perfetto_trace_wellformed(tmp_path):
    tr = SpanTracer()
    with tr.span("plan", TRACK_STEP, args={"bucket": 128}):
        pass
    tr.complete("execute", 1.0, 0.5, TRACK_STEP)
    tr.instant("oom", TRACK_STEP, args={"bucket": 128})
    path = str(tmp_path / "trace.json")
    tr.save(path)
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"plan", "execute"}
    assert all(e["dur"] >= 0 and "ts" in e for e in xs)
    ex = next(e for e in xs if e["name"] == "execute")
    assert ex["ts"] == pytest.approx(1.0e6)      # seconds -> microseconds
    assert ex["dur"] == pytest.approx(0.5e6)
    assert [e for e in evs if e["ph"] == "i" and e["name"] == "oom"]
    # exactly one thread_name metadata record for the one track used
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(metas) == 1 and metas[0]["args"]["name"] == "train.step"


def test_tracer_capacity_bounded():
    tr = SpanTracer(capacity=5)
    for i in range(50):
        tr.complete(f"s{i}", 0.0, 0.001, TRACK_STEP)
    assert len([e for e in tr.events() if e["ph"] == "X"]) <= 5


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------

def test_disabled_telemetry_is_noop():
    tel = Telemetry.disabled()
    assert not tel.events_on and not tel.trace_on
    assert isinstance(tel.events, NullEventLog)
    assert isinstance(tel.tracer, NullTracer)
    # zero allocation on the hot path: every span is the one shared
    # singleton, not a fresh object per call
    s1 = tel.tracer.span("plan", TRACK_STEP)
    s2 = tel.tracer.span("execute", TRACK_STEP, args={"k": 1})
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1:
        pass
    tel.events.emit("anything", x=1)
    assert len(tel.events) == 0
    tel.close()


def test_build_and_flush_telemetry(tmp_path):
    mp = str(tmp_path / "metrics.json")
    ep = str(tmp_path / "events.jsonl")
    tp = str(tmp_path / "trace.json")
    tel = build_telemetry(metrics_path=mp, events_path=ep, trace_path=tp)
    assert tel.events_on and tel.trace_on
    tel.metrics.counter("n").inc(3)
    tel.events.emit("x")
    with tel.tracer.span("s", TRACK_STEP):
        pass
    written = flush_telemetry(tel)
    assert written == {"metrics": mp, "events": ep, "trace": tp}
    assert json.load(open(mp))["n"]["total"] == 3
    assert [r["kind"] for r in read_events(ep)] == ["x"]
    assert json.load(open(tp))["traceEvents"]
    # no sinks requested -> fully disabled, nothing written
    off = build_telemetry()
    assert not off.events_on and not off.trace_on
    assert flush_telemetry(off) == {}


# ---------------------------------------------------------------------------
# drift series vs the refit trigger
# ---------------------------------------------------------------------------

def test_drift_series_matches_refit_trigger(small):
    """Every ``drift`` event must satisfy refit == (rel_err >
    audit_tol), and the per-bucket predicted/actual gauges must track
    the latest drift point — the series the drift audit is built on."""
    _, lm, params = small
    tel = Telemetry.enabled()
    planner = MimosePlanner(lm, budget_bytes=1e12, warmup_samples=2,
                            quantum=8, audit_every=1, telemetry=tel)
    for S in (32, 48):
        planner.plan(params, _batch(S))
    # corrupt the fitted coefficients to force drift on the next miss
    planner.estimator.fit()
    planner.estimator._coeffs = planner.estimator._coeffs * 3.0
    planner.plan(params, _batch(96))
    drifts = tel.events.tail(100, kind="drift")
    assert drifts, "drift events must be recorded"
    assert any(d["refit"] for d in drifts)
    for d in drifts:
        assert d["refit"] == (d["rel_err"] > planner.audit_tol)
    assert planner.stats["refits"] == sum(d["refit"] for d in drifts)
    # gauges carry the latest point per bucket
    last = drifts[-1]
    pred = tel.metrics.get("plan_predicted_peak_bytes")
    act = tel.metrics.get("plan_actual_peak_bytes")
    assert pred.value(bucket=last["bucket"]) == last["predicted_bytes"]
    assert act.value(bucket=last["bucket"]) == last["actual_bytes"]


# ---------------------------------------------------------------------------
# end-to-end: a short training run with full telemetry
# ---------------------------------------------------------------------------

def test_trainer_telemetry_end_to_end(small, tmp_path):
    _, lm, params = small
    ep = str(tmp_path / "events.jsonl")
    tp = str(tmp_path / "trace.json")
    tel = build_telemetry(events_path=ep, trace_path=tp)
    planner = MimosePlanner(lm, budget_bytes=1e12, quantum=8,
                            warmup_samples=1)
    tr = Trainer(lm, planner, AdamW(), telemetry=tel)
    p = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = tr.optimizer.init(p)
    for _ in range(3):
        p, opt_state, loss = tr.step(p, opt_state, _batch(32))
        assert np.isfinite(loss)
    # a step's train_step event carries its loss, so it is written when
    # that loss is read: the last one by the drain
    tr.drain()
    flush_telemetry(tel)
    steps = [r for r in read_events(ep) if r["kind"] == "train_step"]
    assert len(steps) == 3
    for r in steps:
        assert {"step", "bucket", "loss", "plan_source",
                "predicted_peak_bytes"} <= set(r)
    # the per-bucket predicted-vs-actual series is present
    assert [r for r in read_events(ep) if r["kind"] == "drift"]
    doc = json.load(open(tp))
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"plan", "execute"} <= names
    # stats mappings stayed dict-shaped for legacy consumers
    assert tr.cache_stats["compiles"] >= 1
    assert dict(tr.cache_stats["bucket_steps"])


# ---------------------------------------------------------------------------
# spans over the whole step, and their forwarding to jax.profiler
# ---------------------------------------------------------------------------

def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_step_spans_cover_the_step(small):
    """Over warm steps every host stretch of ``Trainer.step`` has a span:
    prepare/plan/execute/record inside step, dispatch/sync inside
    execute (``sync`` reads the previous step's loss, after this step's
    dispatch), and the children cover at least 80% of the steps' time."""
    _, lm, params = small
    tracer = SpanTracer()
    planner = MimosePlanner(lm, budget_bytes=1e12, quantum=8,
                            warmup_samples=1)
    tr = Trainer(lm, planner, AdamW(), telemetry=Telemetry(tracer=tracer))
    p = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = tr.optimizer.init(p)
    p, opt_state, _ = tr.step(p, opt_state, _batch(32))    # compiles
    n_cold = len(tracer)
    for _ in range(5):
        p, opt_state, _ = tr.step(p, opt_state, _batch(32))
    xs = [e for e in tracer.events()[n_cold:]
          if e["ph"] == "X" and e["tid"] == TRACK_STEP]
    steps = [e for e in xs if e["name"] == "step"]
    assert len(steps) == 5
    covered = 0.0
    for st in steps:
        inner = [e for e in xs if e is not st and _inside(e, st)]
        names = [e["name"] for e in inner]
        for name in ("prepare", "plan", "execute", "record", "dispatch",
                     "sync"):
            assert names.count(name) == 1, (name, names)
        assert "build_step" not in names          # a cache hit
        ex = next(e for e in inner if e["name"] == "execute")
        for name in ("dispatch", "sync"):
            assert _inside(next(e for e in inner if e["name"] == name), ex)
        covered += sum(e["dur"] for e in inner
                       if e["name"] in ("prepare", "plan", "execute",
                                        "record"))
    assert covered >= 0.8 * sum(st["dur"] for st in steps)
    # the cold step built its step function inside its step span
    cold = [e for e in tracer.events()[:n_cold] if e["ph"] == "X"]
    cold_step = next(e for e in cold if e["name"] == "step")
    build = next(e for e in cold if e["name"] == "build_step")
    assert _inside(build, cold_step)


def test_step_span_disabled_and_default_paths():
    """The disabled path stays the shared no-op, and forwarding to the
    profiler is off unless asked for."""
    assert NullTracer().step_span("step", 3) is NULL_SPAN
    tr = SpanTracer()
    assert not tr.to_profiler
    with tr.step_span("step", 3):
        pass
    assert [e["name"] for e in tr.events() if e["ph"] == "X"] == ["step"]


def test_spans_forwarded_to_the_profiler(tmp_path):
    """With ``to_profiler`` on, the spans are ``program:<name>``
    annotations on the profiler's host plane, nested as they were, and
    the step span is a ``StepTraceAnnotation`` with its step number."""
    from jax.profiler import ProfileData

    tr = SpanTracer(to_profiler=True)
    with jax.profiler.trace(str(tmp_path)):
        with tr.step_span("step", 7):
            with tr.span("plan", TRACK_STEP):
                jnp.ones(8).sum().block_until_ready()
    # the perf_counter record is kept as without forwarding
    assert [e["name"] for e in tr.events() if e["ph"] == "X"] == \
        ["plan", "step"]
    paths = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(paths) == 1
    host = {}
    for plane in ProfileData.from_file(str(paths[0])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns,
                         dict(e.stats) if e.name == "train" else None))
    assert len(host["program:step"]) == 1 and len(host["program:plan"]) == 1
    (s0, s1, _), = host["program:step"]
    (p0, p1, _), = host["program:plan"]
    assert s0 <= p0 and p1 <= s1
    (t0, t1, stats), = host["train"]
    assert stats.get("step_num") == 7 and t0 <= s0 and s1 <= t1


def test_train_profile_dir_names_the_steps(monkeypatch, tmp_path):
    """``launch/train.py --profile-dir`` writes a profiler trace whose
    host plane holds the trainer's spans under the ``program:`` prefix
    the benchmark's trace reduction reads."""
    from jax.profiler import ProfileData

    from repro.launch import compile_cache, train

    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path / "cache"))
    prof = tmp_path / "profile"
    train.main(["--reduced", "--steps", "2", "--batch-size", "2",
                "--quantum", "64", "--profile-dir", str(prof)])
    paths = list(prof.glob("plugins/profile/*/*.xplane.pb"))
    assert len(paths) == 1
    names = [e.name for plane in ProfileData.from_file(str(paths[0])).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("program:step") == 2
    for name in ("program:prepare", "program:plan", "program:dispatch",
                 "program:sync", "program:record"):
        assert name in names, name


def test_step_carries_named_scopes(small):
    """The compiled step's ops carry a scope path: plan unit, attention
    and MLP, output head, optimizer — and the backward and recompute
    wrappers around them."""
    from repro.actions import Action

    _, lm, params = small
    planner = MimosePlanner(lm, budget_bytes=1e12, quantum=8,
                            warmup_samples=1)
    tr = Trainer(lm, planner, AdamW())
    batch = tr._prepare(_batch(32))
    fwd = jax.jit(lm.loss).lower(params, batch).as_text(debug_info=True)
    for scope in ("unit0/attn", "unit0/mlp", "head/"):
        assert scope in fwd, scope
    # unit 0 recomputed, the rest kept
    mask = (Action.REMAT,) + (Action.KEEP,) * (lm.num_plan_units() - 1)
    text = tr._build_step(mask).lower(
        params, tr.optimizer.init(params), batch).as_text(debug_info=True)
    for scope in ("jit(train_step)/optimizer/", "jvp(unit1)/attn/",
                  "transpose(jvp(unit1))/mlp/",
                  "jvp(unit0)/checkpoint/rematted_computation/attn/",
                  "transpose(jvp(head))/"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# tools/trace_view.py CLI
# ---------------------------------------------------------------------------

def _load_trace_view():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "trace_view.py")
    spec = importlib.util.spec_from_file_location("trace_view", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_view_cli(tmp_path, capsys):
    tv = _load_trace_view()
    tp = str(tmp_path / "trace.json")
    tr = SpanTracer()
    tr.complete("execute", 0.0, 0.25, TRACK_STEP)
    tr.complete("plan", 0.3, 0.05, TRACK_STEP)
    tr.save(tp)
    tv.main([tp, "--top", "5"])
    out = capsys.readouterr().out
    assert "execute" in out and "total ms" in out
    ep = str(tmp_path / "events.jsonl")
    with EventLog(path=ep) as log:
        log.emit("plan", bucket=64, source="greedy", k=1,
                 n_remat=0, n_offload=0)
        log.emit("solver_swap", bucket=64, greedy_s=0.02, solved_s=0.015,
                 improvement_pct=25.0)
        log.emit("admit", rid=0, bucket=64, wait_s=0.1)
        log.emit("defer", rid=1, bucket=128)
    tv.main([ep])
    out = capsys.readouterr().out
    assert "solver_swap" in out and "admission outcomes" in out
