"""The chip-facing guards: chip_smoke's device check, the device peak
table behind MFU, and where the persistent compilation cache goes."""
import pytest

import jax

import chip_smoke as CS
from repro.launch import compile_cache as CC
from repro.launch import telemetry as TL


def test_require_tpu_refuses_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        CS.require_tpu(1)


def test_device_peak_table():
    # the CPU has no published peak: MFU is reported as not measured
    assert TL.device_peak() is None
    assert TL.peak_flops_per_device() is None

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert TL.device_peak(Dev()).flops == 197e12
    assert TL.device_peak(Dev()).hbm_bw == 819e9
    Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(ValueError, match="no published peak"):
        TL.device_peak(Dev())


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_inside_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = CC.enable_compile_cache()
    assert path == str(CC.REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (CC.REPO_ROOT / "chip_smoke.py").exists()
    assert ".jax_cache/" in (CC.REPO_ROOT / ".gitignore").read_text()


def test_compile_cache_env_is_left_to_jax(monkeypatch, tmp_path,
                                          cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
