"""Device helpers: no silent CPU fallback, one compile-cache location, and
the tune cache kept inside the checkout."""
import jax
import pytest

from repro import device
from repro.tune import cache as tcache


def test_on_tpu_does_not_swallow_a_backend_failure(monkeypatch):
    def broken():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        device.on_tpu()


def test_on_tpu_is_false_on_the_cpu():
    assert jax.devices()[0].platform == "cpu"
    assert device.on_tpu() is False


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = device.init_compile_cache()
    assert got == str(device.COMPILE_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert device.COMPILE_CACHE_DIR.is_relative_to(device.REPO_ROOT)
    # the same path every time: it is part of the cache key
    assert device.init_compile_cache() == got


def test_compile_cache_env_wins_and_nothing_else_is_set(
        monkeypatch, tmp_path, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.init_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_is_gitignored():
    ignored = (device.REPO_ROOT / ".gitignore").read_text().split()
    rel = device.CACHE_DIR.relative_to(device.REPO_ROOT)
    assert f"{rel}/" in ignored


def test_tune_cache_defaults_into_the_checkout(monkeypatch):
    monkeypatch.delenv(tcache.ENV_PATH, raising=False)
    path = tcache.default_path()
    assert path == str(device.CACHE_DIR / "tune_cache.json")
