"""Record the small device trace ``test_bench_trace.py`` reduces.

    python bench/tests/record_trace.py [OUT]     # on a machine with a TPU

Three rounds of: a 2048x2048 bf16 matmul chain inside
``bench:traced_window`` / ``bench:step``, then 20 ms of host sleep inside
``bench:feed`` while the chip idles.  Writes the ``.xplane.pb`` to OUT,
by default ``bench/tests/data/small_trace.xplane.pb``.
"""
import glob
import os
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from bench.lib import spans
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    f(x).block_until_ready()
    log = tempfile.mkdtemp()
    jax.profiler.start_trace(log, profiler_options=spans.profile_options())
    with spans.harness_span("traced_window"):
        for _ in range(3):
            with spans.harness_span("step"):
                y = x
                for _ in range(4):
                    y = f(y)
                y.block_until_ready()
            with spans.harness_span("feed"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        log, "plugins/profile/*/*.xplane.pb")))[-1]
    out = pathlib.Path(sys.argv[1]) if len(sys.argv) > 1 else \
        HERE / "data" / "small_trace.xplane.pb"
    out.parent.mkdir(exist_ok=True)
    shutil.copy(path, out)
    print(f"wrote {out} ({out.stat().st_size} bytes)")
    from bench.metrics import devtrace
    print(devtrace.reduce_trace(log))
    shutil.rmtree(log, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
