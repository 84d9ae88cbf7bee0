"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Run from the root of a checkout that holds ``BENCHMARK.json``, this
``bench/`` directory and the program under ``src/``.  The cell's
configuration, traffic mix and per-layer metrics are found by the names
``BENCHMARK.json`` gives them: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``; the
plain reference by the name the configuration gives it,
``bench/reference/<reference>.py``.

With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a separate traced
run (a profiler trace of the window's last seconds, plus the program's
spans).  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and, last, ``checks``: every number
compared against the plain reference beside its limit, which also end
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

CLOCK0 = time.perf_counter()

import argparse
import importlib.util
import json
import pathlib
import sys
import types

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, bench_dir: pathlib.Path = HERE):
    """The workload entry, its configuration and traffic dicts, and the
    end-to-end and per-layer metric entries it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = json.loads((bench_dir / "configs" / f"{w['config']}.json")
                     .read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in names]
    return w, cfg, traffic, e2e, layer


def reader(name: str, bench_dir: pathlib.Path = HERE):
    """The ``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def run_cell(args, *, require_tpu: bool = True, control: bool = False,
             fault=None, spec=None, bench_dir: pathlib.Path = HERE,
             clock0: float = CLOCK0) -> dict:
    """Everything a run does, returned as its result dict.  The command
    line always asks for a TPU; ``require_tpu=False`` (tests) takes the
    default device instead; ``control`` runs the control the
    configuration names (``bench/lib/reference.py``), and ``fault``
    plants a fault in the timed path."""
    spec = spec if spec is not None else load_spec()
    w, cfg, traffic, e2e, layer = resolve(spec, args.workload, bench_dir)
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"no program under {ROOT / 'src'}")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from bench.lib import check, device as dev, reference
    import jax
    if require_tpu:
        dev.enable_compile_cache()
        devices = dev.require_chips(int(w["chips"]))
    else:
        devices = jax.devices()[:int(w["chips"])]
    counter = dev.CompileCounter()
    cell = types.SimpleNamespace(
        cfg=cfg, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, clock0=clock0,
        counter=counter, control=control, fault=fault,
        ref=reference.load(cfg, bench_dir),
        memory_peak=lambda: dev.memory_peak(devices))
    if cfg["kind"] == "train":
        from bench.lib import train_cell as driver
    else:
        from bench.lib import serve_cell as driver
    out = driver.run(cell)

    kind = devices[0].device_kind
    out["run"]["peak_flops"] = (dev.PEAKS[kind].flops if kind in dev.PEAKS
                                else None)
    numbers = dict(out["numbers"])
    where = numbers.pop("_where", {})
    correct, checks = check.verdict(numbers, cfg["limits"])
    device = dict(dev.describe(devices),
                  memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        metrics = {}
        for m in layer:
            v = reader(m["name"], bench_dir)(out["run"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        tr = out["run"].get("trace")
        if tr is not None:
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": out["end_to_end"][m["name"]],
                                         "unit": m["unit"]} for m in e2e}
    result["device"] = device
    result["checks"] = checks
    result["_where"] = where
    result["_compiles_in_window"] = dict(counter.counts)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args)
    except Exception as e:
        from bench.lib.device import NoChip
        if isinstance(e, (NoChip, FileNotFoundError, KeyError)):
            print(f"bench: {e}", file=sys.stderr)
            return 2
        raise
    where = result.pop("_where")
    compiles = result.pop("_compiles_in_window")
    print(f"compiles in the window: {sum(compiles.values())} {compiles}")
    from bench.lib.check import print_checks
    print(json.dumps(result), flush=True)
    print_checks(result["checks"], where)
    return 0


if __name__ == "__main__":
    sys.exit(main())
