"""The persistent compile cache is placed from outside or at one fixed
path inside the checkout (``repro.launch.compile_cache``)."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_places_the_cache(monkeypatch, tmp_path,
                                      cache_dir_restored):
    """With JAX_COMPILATION_CACHE_DIR set, that is the directory (jax
    reads it from the environment at import) and the helper writes no
    directory of its own into the config."""
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "jax")


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                   cache_dir_restored):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    repo = compile_cache.DEFAULT_DIR.parent
    assert (repo / "src" / "repro").is_dir()
    assert first == str(repo / ".jax_cache")
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
