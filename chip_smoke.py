"""Smoke run of the Mimose trainer and serve engine on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip: kernels, train, offload, serve
    python chip_smoke.py --chips 4    # four chips: the 2x2 mesh train path only

Everything runs in this one process, through the entry points a user
calls (``repro.launch.train.main``, ``repro.launch.serve.main``, the
``repro.kernels.ops`` wrappers), at the published widths of
``bert_base_paper`` and ``qwen3-1.7b`` with random weights from a fixed
seed.  Each phase prints its checks; a failed check or an exception in
any phase ends the run with a non-zero exit and no result line.  The
times printed on the way (set-up, compile, steps) are set-up
information, not measurements.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or run from a directory that does not hold this
repository's ``src/``, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

# the paper's training job at full width: 12 x d=768, vocab 30522
TRAIN_ARCH, TRAIN_DATASET, TRAIN_QUANTUM, TRAIN_BATCH = \
    "bert_base_paper", "squad", 128, 8
TRAIN_ARGS = ["--arch", TRAIN_ARCH, "--dataset", TRAIN_DATASET,
              "--planner", "mimose", "--quantum", str(TRAIN_QUANTUM),
              "--batch-size", str(TRAIN_BATCH)]
TRAIN_STEPS = 8
MESH_STEPS = 4
# the budget sits this far below the planner's own no-remat peak of the
# largest bucket, so that bucket must plan REMAT
BUDGET_SHARE = 0.85
# serving: qwen3-1.7b at full width (28 x d=2048, vocab 151936, bf16)
SERVE_ARCH = "qwen3-1.7b"
SERVE_REQUESTS = 4
SERVE_NEW_TOKENS = 8
SERVE_HBM_GB = 8.0
SERVE_QUANTUM = 64
SERVE_PREFILL_CHUNK = 32
# kernel widths: bert-base attention, mamba2-1.3b SSD
FLASH_SHAPE = (8, 512, 12, 12, 64)       # B, S, H, Hkv, hd
SSD_SHAPE = (2, 512, 64, 64, 128, 64)    # B, S, H, P, N, chunk
# fp32 kernels on the chip against fp32 references computed at highest
# matmul precision: the bound allows bf16-pass MXU rounding (2**-8 per
# product) accumulated over a row, and sits well below the O(1) error
# of a wrong mask, block or recurrence
KERNEL_REL_TOL = 2e-2
# step 0's loss on the chip against the CPU: fp32 parameters, with the
# chip's default single-pass bf16 matmuls
LOSS_RTOL = 2e-3


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'pass' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def phase(name: str):
    print(f"\n== {name}", flush=True)
    return time.perf_counter()


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def compiled_text(fn, *args) -> str:
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# kernels: the Pallas kernels compiled for the chip against kernels/ref.py
# ---------------------------------------------------------------------------

def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels.ref import flash_attention_reference, ssd_reference

    t0 = phase("kernels")
    B, S, H, Hkv, hd = FLASH_SHAPE
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(kk, (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, hd), jnp.float32)
    lens = jnp.asarray(np.linspace(S, S // 8, B).astype(np.int32))
    wm = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.float32)

    def kernel_loss(q, k, v):
        o = ops.flash_attention(q, k, v, lens, causal=True)
        return ((o * wm[:, :, None, None]) ** 2).sum(), o

    def ref_loss(q, k, v):
        o = flash_attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True,
            kv_len=lens).transpose(0, 2, 1, 3)
        return ((o * wm[:, :, None, None]) ** 2).sum(), o

    grad_k = jax.grad(kernel_loss, argnums=(0, 1, 2), has_aux=True)
    text = compiled_text(grad_k, q, k, v)
    check(text.count("tpu_custom_call") >= 3,
          "flash fwd, dq and dk/dv kernels compiled as tpu_custom_call "
          f"({text.count('tpu_custom_call')} in the grad program)")
    (dq, dk, dv), o = jax.jit(grad_k)(q, k, v)
    with jax.default_matmul_precision("highest"):
        (rq, rk, rv), ro = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2),
                                            has_aux=True))(q, k, v)
    o, ro = np.asarray(o) * np.asarray(wm)[:, :, None, None], \
        np.asarray(ro) * np.asarray(wm)[:, :, None, None]
    errs = {"o": rel_err(o, ro), "dq": rel_err(dq, rq),
            "dk": rel_err(dk, rk), "dv": rel_err(dv, rv)}
    check(all(e <= KERNEL_REL_TOL for e in errs.values()),
          f"flash (B={B}, S={S}, H={H}, hd={hd}, ragged lengths "
          f"{np.asarray(lens).tolist()}) vs reference: max error / max "
          f"|ref| {({n: f'{e:.2e}' for n, e in errs.items()})} "
          f"<= {KERNEL_REL_TOL}")

    B, S, H, P, N, chunk = SSD_SHAPE
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    lens = jnp.asarray([S, S - 3 * chunk // 2], jnp.int32)[:B]

    def scan(x, dt, A, Bm, Cm, lens):
        return ops.ssd_scan(x, dt, A, Bm, Cm, lens, chunk=chunk)

    text = compiled_text(scan, x, dt, A, Bm, Cm, lens)
    check("tpu_custom_call" in text,
          "ssd_scan kernel compiled as tpu_custom_call")
    y = np.asarray(jax.jit(scan)(x, dt, A, Bm, Cm, lens))
    errs = []
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            L = int(lens[b])
            yr, _ = ssd_reference(x[b:b + 1, :L], dt[b:b + 1, :L], A,
                                  Bm[b:b + 1, :L], Cm[b:b + 1, :L])
            errs.append(rel_err(y[b:b + 1, :L], yr))
    check(max(errs) <= KERNEL_REL_TOL,
          f"ssd_scan (B={B}, S={S}, H={H}, P={P}, N={N}, chunk={chunk}, "
          f"lengths {np.asarray(lens).tolist()}) vs reference: max error "
          f"/ max |ref| {[f'{e:.2e}' for e in errs]} <= {KERNEL_REL_TOL}")
    print(f"  kernels phase {time.perf_counter() - t0:.1f}s (set-up + "
          "compile + run)")


# ---------------------------------------------------------------------------
# train: the paper's main path, launch/train.py at full width
# ---------------------------------------------------------------------------

def _planned_no_remat_peak(arch: str, batch_size: int, seq: int,
                           quantum: int) -> float:
    """The planner's own predicted peak of one bucket with nothing
    rematerialised: fixed bytes plus every unit's activation bytes."""
    import jax
    import numpy as np
    from repro.core import MimosePlanner
    from repro.models.lm import build_model
    from repro.models.registry import get_config

    lm = build_model(get_config(arch))
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    planner = MimosePlanner(lm, 1e18, quantum=quantum)
    ones = np.ones((batch_size, seq), np.int32)
    batch = {"tokens": ones, "labels": ones,
             "weights": ones.astype(np.float32),
             "lengths": np.full((batch_size,), seq, np.int32)}
    _, info = planner.plan(params, batch)
    assert info.plan.n_remat == 0
    return float(planner.fixed_bytes) + float(info.plan.est_activation_bytes)


def _cpu_step0_loss(arch: str, dataset: str, batch_size: int,
                    quantum: int, steps: int) -> float:
    """Step 0 of ``launch/train.py`` evaluated on the host CPU: the same
    seeded parameters and the same first batch of the same stream."""
    import jax
    import numpy as np
    from repro.data.pipeline import make_batches, pad_batch
    from repro.models.lm import build_model
    from repro.models.registry import get_config

    cfg = get_config(arch)
    lm = build_model(cfg)
    batch = next(iter(make_batches(dataset, batch_size=batch_size,
                                   vocab_size=cfg.vocab_size,
                                   num_batches=steps, quantum=quantum,
                                   seed=0)))
    batch = pad_batch(batch, quantum)
    batch["lengths"] = np.asarray(batch["lengths"], np.int32)
    with jax.default_device(jax.devices("cpu")[0]):
        params = lm.init(jax.random.PRNGKey(0))
        loss, _ = jax.jit(lm.loss)(params, batch)
        return float(loss)


def phase_train() -> None:
    import jax
    import numpy as np
    from repro.launch import train
    from repro.train.transfer import measure_pcie_gbps

    t0 = phase("train")
    arch, dataset = TRAIN_ARCH, TRAIN_DATASET
    quantum, batch_size = TRAIN_QUANTUM, TRAIN_BATCH
    from repro.data.pipeline import DISTRIBUTIONS, bucket_length
    top = bucket_length(DISTRIBUTIONS[dataset].hi, quantum)

    link = measure_pcie_gbps()
    print(f"  host link measured on this chip: {link}")
    check(link["pinned_host"] and link["pcie_gbps"] > 0,
          "pinned-host round trip measured")
    no_remat = _planned_no_remat_peak(arch, batch_size, top, quantum)
    budget_mb = BUDGET_SHARE * no_remat / 2**20
    print(f"  planner's no-remat peak for the {top} bucket: "
          f"{no_remat / 2**20:.1f} MiB; budget {budget_mb:.1f} MiB")

    ts = time.perf_counter()
    out = train.main(TRAIN_ARGS + ["--steps", str(TRAIN_STEPS),
                                   "--budget-mb", f"{budget_mb:.3f}",
                                   "--pcie-gbps", str(link["pcie_gbps"])])
    hist = out["history"]
    print(f"  train.main {time.perf_counter() - ts:.1f}s; per step: "
          + ", ".join(f"S={h['bucket'] // batch_size} "
                      f"{'compile+' if h['compile'] else ''}"
                      f"{h['step_time_s']:.2f}s remat={h['remat_units']}"
                      for h in hist))
    losses = [h["loss"] for h in hist]
    check(len(hist) == TRAIN_STEPS and all(map(math.isfinite, losses)),
          f"{len(hist)} steps, every loss finite: "
          f"{[round(x, 4) for x in losses]}")
    check(out["compiles"] == out["buckets"],
          f"compiles ({out['compiles']}) == distinct buckets "
          f"({out['buckets']})")
    check(any(h["remat_units"] > 0 for h in hist),
          f"a REMAT plan ran (max remat units "
          f"{max(h['remat_units'] for h in hist)})")
    ref = _cpu_step0_loss(arch, dataset, batch_size, quantum, TRAIN_STEPS)
    check(abs(losses[0] - ref) <= LOSS_RTOL * abs(ref),
          f"step 0 loss on the chip {losses[0]:.6f} vs CPU {ref:.6f} "
          f"(rel diff {abs(losses[0] - ref) / abs(ref):.2e} <= {LOSS_RTOL})")
    largest = max(h["bucket"] for h in hist)
    planned = max(h["planned_peak_bytes"] for h in hist
                  if h["bucket"] == largest)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"  device peak_bytes_in_use {peak / 2**20:.1f} MiB (whole "
          f"process so far) beside the planner's predicted peak for the "
          f"largest bucket (S={largest // batch_size}): "
          f"{planned / 2**20:.1f} MiB under its plan, "
          f"{no_remat / 2**20:.1f} MiB with nothing rematerialised")
    print(f"  train phase {time.perf_counter() - t0:.1f}s (set-up + "
          "compile + run)")


# ---------------------------------------------------------------------------
# offload: the pinned-host transfer lane and the host-offload remat policy
# ---------------------------------------------------------------------------

def phase_offload() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.ad_checkpoint import checkpoint_name
    from repro.kernels.ops import residual_dma_copy
    from repro.models.lm import (OFFLOAD_RESIDUAL_NAME, build_model,
                                 configure_offload, host_offload_policy)
    from repro.models.registry import get_config
    from repro.train.transfer import TransferLane, host_memory_supported

    t0 = phase("offload")
    check(host_memory_supported(), "pinned_host memory supported")
    lm = build_model(get_config(TRAIN_ARCH))
    check(configure_offload(lm, None) is False and lm.offload_exec,
          "configure_offload: OFFLOAD executes as host offload on one chip")

    x = jax.random.normal(jax.random.PRNGKey(3), (4096, 4096), jnp.float32)
    lane = TransferLane()
    try:
        h = lane.offload(x)
        host = lane.host_value(h)
        back = lane.fetch(h)
    finally:
        lane.close()
    check(host.sharding.memory_kind == "pinned_host",
          f"lane parked {x.nbytes >> 20} MiB in pinned_host")
    check(back.sharding.memory_kind == "device"
          and np.array_equal(np.asarray(back), np.asarray(x)),
          "lane round trip device -> pinned_host -> device is bitwise equal")
    check("tpu_custom_call" in compiled_text(residual_dma_copy, x),
          "DMA copy kernel compiled as tpu_custom_call")
    check(np.array_equal(np.asarray(residual_dma_copy(x)), np.asarray(x)),
          f"DMA copy kernel: {x.nbytes >> 20} MiB copied bitwise equal")

    B, S = TRAIN_BATCH, 512
    ones = np.ones((B, S), np.int32)
    params = lm.init(jax.random.PRNGKey(0))
    unit = lm.plan_units(params, {"tokens": ones, "labels": ones})[0]
    xs = jax.random.normal(jax.random.PRNGKey(4), (B, S, lm.cfg.d_model),
                           lm.dtype)

    def unit_loss(policy):
        def tagged(p, y):
            return unit.apply(p, checkpoint_name(y, OFFLOAD_RESIDUAL_NAME))
        f = jax.checkpoint(tagged, policy=policy)
        return jax.grad(lambda p, y: jnp.sum(f(p, jnp.sin(y)) ** 2),
                        argnums=(0, 1))

    g_off = unit_loss(host_offload_policy())
    text = compiled_text(g_off, unit.params, xs)
    check("S(5)" in text,
          "offload grad compiled with its residual in host memory (S(5))")
    got = jax.jit(g_off)(unit.params, xs)
    want = jax.jit(unit_loss(None))(unit.params, xs)
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want)))
    errs = [rel_err(a, b) for a, b in zip(jax.tree_util.tree_leaves(got),
                                          jax.tree_util.tree_leaves(want))]
    check(same or max(errs) <= 1e-6,
          f"unit grad under host_offload_policy == remat grad "
          f"({'bitwise' if same else f'max rel {max(errs):.1e}'})")
    print(f"  offload phase {time.perf_counter() - t0:.1f}s")


# ---------------------------------------------------------------------------
# serve: launch/serve.py at qwen3-1.7b width
# ---------------------------------------------------------------------------

def phase_serve() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.pipeline import bucket_length
    from repro.data.trace import gen_trace
    from repro.launch import serve
    from repro.models.lm import build_model
    from repro.models.registry import get_config
    from repro.train.serve import generate

    t0 = phase("serve")
    out = serve.main(["--arch", SERVE_ARCH,
                      "--num-requests", str(SERVE_REQUESTS),
                      "--max-new-tokens", str(SERVE_NEW_TOKENS),
                      "--hbm-gb", str(SERVE_HBM_GB),
                      "--quantum", str(SERVE_QUANTUM),
                      "--prefill-chunk", str(SERVE_PREFILL_CHUNK)])
    print(f"  serve.main {time.perf_counter() - t0:.1f}s; compiles "
          f"{out['compile_counts']}")
    check(out["completed"] == SERVE_REQUESTS and out["rejected"] == 0,
          f"{out['completed']}/{SERVE_REQUESTS} requests completed, "
          f"{out['rejected']} rejected")
    cfg = get_config(SERVE_ARCH)
    # the trace serve.main generated with its defaults
    req = gen_trace(num_requests=SERVE_REQUESTS, vocab_size=cfg.vocab_size,
                    max_new_tokens=SERVE_NEW_TOKENS)[0]
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    want = np.asarray(generate(
        lm, params, jnp.asarray(req.prompt[None]), SERVE_NEW_TOKENS,
        prefill_chunk=SERVE_PREFILL_CHUNK,
        cache_len=bucket_length(len(req.prompt) + SERVE_NEW_TOKENS,
                                SERVE_QUANTUM)))[0].tolist()
    got = out["outputs"][req.rid]
    check(got == want, f"request {req.rid} (prompt {len(req.prompt)} "
          f"tokens) == sequential generate(): {got}")
    print(f"  serve phase {time.perf_counter() - t0:.1f}s (set-up + "
          "compile + run)")


# ---------------------------------------------------------------------------
# four chips: the 2x2 mesh train path against one chip
# ---------------------------------------------------------------------------

def phase_mesh() -> None:
    import jax
    from repro.launch import train
    from repro.sharding.budget import MeshBudget

    t0 = phase("train on a 2x2 mesh")
    hbm_gb = 16.0
    out = train.main(TRAIN_ARGS + ["--steps", str(MESH_STEPS),
                                   "--mesh-shape", "2x2",
                                   "--hbm-gb", str(hbm_gb)])
    hist = out["history"]
    losses = [h["loss"] for h in hist]
    check(len(hist) == MESH_STEPS and all(map(math.isfinite, losses)),
          f"{len(hist)} mesh steps, every loss finite: "
          f"{[round(x, 4) for x in losses]}")
    budget = MeshBudget.from_shape((2, 2), hbm_gb * 2**30)
    planned = max(h["planned_peak_bytes"] for h in hist)
    for d in jax.devices()[:4]:
        peak = d.memory_stats()["peak_bytes_in_use"]
        print(f"  device {d.id}: peak_bytes_in_use {peak / 2**20:.1f} MiB; "
              f"planned per-device budget "
              f"{budget.hbm_per_device_bytes / 2**20:.1f} MiB, planner's "
              f"predicted per-device peak {planned / 2**20:.1f} MiB"
              + ("  ** over the planned budget **"
                 if peak > budget.hbm_per_device_bytes else ""))
    print(f"  mesh run {time.perf_counter() - t0:.1f}s")

    t1 = phase("train on one chip (step 0 reference)")
    one = train.main(TRAIN_ARGS + ["--steps", "1"])
    ref = one["history"][0]["loss"]
    check(abs(losses[0] - ref) <= LOSS_RTOL * abs(ref),
          f"step 0 loss on the 2x2 mesh {losses[0]:.6f} vs one chip "
          f"{ref:.6f} (rel diff {abs(losses[0] - ref) / abs(ref):.2e})")
    print(f"  one-chip run {time.perf_counter() - t1:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: every phase on one chip; 4: only the 2x2 "
                         "mesh train path and its one-chip comparison")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.roofline import device_peaks

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed as a package"
    peaks = device_peaks(dev)
    print(f"device {dev.device_kind} x{len(devices)} ({dev.platform}); "
          f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
          f"{libtpu}; peaks {peaks.flops / 1e12:.0f} TFLOP/s, "
          f"{peaks.hbm_bw / 1e9:.0f} GB/s ({peaks.source}); compile "
          f"cache {cache}", flush=True)

    if args.chips == 4:
        phase_mesh()
    else:
        phase_kernels()
        phase_train()
        phase_offload()
        phase_serve()
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
