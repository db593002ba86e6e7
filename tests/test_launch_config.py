"""Launch-layer configuration: the persistent compile cache and the
per-chip peak table."""
import jax
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import CHIP_PEAKS, chip_peaks


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_fixed_path_in_checkout(
        monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == compile_cache.default_dir()
    assert path.endswith("artifacts/jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path           # stable across calls


def test_compile_cache_leaves_env_dir_in_charge(monkeypatch,
                                                cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_chip_peaks_known_kind_and_unknown_is_an_error():
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.ici_link_bw) == (197e12, 819e9,
                                                             50e9)
    assert set(CHIP_PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")
