"""What a run on the chip depends on before it computes anything: where
the compile cache goes, which peaks the device is priced at, and that a
mesh the host cannot build is an error rather than a one-device run."""
import pathlib
import types

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import train
from repro.launch.roofline import PEAKS, PLANNING_KIND, device_peaks

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Give the process back the cache setting it had: the tests that
    follow in this process must not start caching into the checkout."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_env_dir_wins_and_sets_nothing(monkeypatch, tmp_path,
                                                     cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      cache_dir_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # a second call (another entry point in the same process) agrees
    assert compile_cache.enable_compile_cache() == want


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("platform,kind", [("cpu", "cpu"),
                                           ("tpu", "TPU v5 lite")])
def test_device_peaks_plan_against_the_v5e_row(platform, kind):
    peaks = device_peaks(_device(platform, kind))
    assert peaks is PEAKS[PLANNING_KIND]
    assert peaks.flops == 197e12 and peaks.hbm_bw == 819e9
    assert "v5e" in peaks.source


def test_device_peaks_unknown_tpu_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v9x"):
        device_peaks(_device("tpu", "TPU v9x"))


def test_train_mesh_larger_than_visible_devices_raises(monkeypatch, tmp_path,
                                                       cache_dir_config):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    n = len(jax.devices())
    with pytest.raises(RuntimeError, match="devices"):
        train.main(["--reduced", "--steps", "1",
                    "--mesh-shape", f"{n + 1}x1"])
