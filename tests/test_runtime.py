"""``repro.utils.runtime``: the checkout root, and where JAX's persistent
compilation cache goes when an entry point turns it on."""
import jax

from repro.utils import runtime


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert (runtime.checkout_root() / "src" / "repro").is_dir()
    was = jax.config.jax_compilation_cache_dir
    try:
        path = runtime.enable_compile_cache()
        assert path == str(runtime.checkout_root() / ".cache" / "jax_compile")
        assert jax.config.jax_compilation_cache_dir == path
        assert runtime.enable_compile_cache() == path     # never moves
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_keeps_the_environment_directory(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    helper sets no directory of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
