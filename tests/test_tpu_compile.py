"""Compile the Pallas kernels for a described TPU v5e chip.

Interpret mode on the CPU runs the kernel body with jnp semantics, so it
cannot see what the chip's compiler (Mosaic) refuses: block shapes that
do not tile as (8, 128), rank-1 blocks, scalars outside SMEM, or more
VMEM than a kernel may use.  These tests lower the kernels at published
widths for one chip of a ``v5e:2x2`` topology that is described, not
attached, and check that the compiled program holds the kernel
(``tpu_custom_call``).  Nothing runs; no chip is needed.

The topology is described inside a fixture: only the process that runs
these tests loads the TPU compiler library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import offload_dma as dma
from repro.kernels import ssd_scan as ssd

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


FLASH_WIDTHS = {
    # name: (B, S, H, Hkv, hd, dtype)
    "bert_base": (8, 512, 12, 12, 64, jnp.float32),
    "qwen3_1p7b": (1, 4096, 16, 8, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(FLASH_WIDTHS))
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, name):
    B, S, H, Hkv, hd, dtype = FLASH_WIDTHS[name]

    def loss(q, k, v, kv_len):
        o = fa.flash_attention(q, k, v, kv_len, True, 0, False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    args = (_spec((B, H, S, hd), dtype, one_chip),
            _spec((B, Hkv, S, hd), dtype, one_chip),
            _spec((B, Hkv, S, hd), dtype, one_chip),
            _spec((B,), jnp.int32, one_chip))
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    # forward, dq and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


def test_ssd_scan_compiles_for_v5e_at_mamba2_width(one_chip):
    B, S, H, P, N, chunk = 1, 2048, 64, 64, 128, 64    # mamba2-1.3b
    dtype = jnp.bfloat16

    def scan(x, dt, A, Bm, Cm, kv_len):
        return ssd.ssd_scan(x, dt, A, Bm, Cm, kv_len=kv_len, chunk=chunk)

    args = (_spec((B, S, H, P), dtype, one_chip),
            _spec((B, S, H), dtype, one_chip),
            _spec((H,), jnp.float32, one_chip),
            _spec((B, S, N), dtype, one_chip),
            _spec((B, S, N), dtype, one_chip),
            _spec((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in _compiled_text(scan, *args)


def test_dma_copy_compiles_for_v5e(one_chip):
    # one bert-base residual stream at B=8, S=512, with a padded tail
    x = _spec((8, 512, 768 + 1), jnp.float32, one_chip)
    fn = functools.partial(dma.dma_copy, chunk_elems=1 << 15)
    assert "tpu_custom_call" in _compiled_text(fn, x)
