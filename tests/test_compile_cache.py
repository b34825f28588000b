"""The persistent compilation cache's placement: JAX_COMPILATION_CACHE_DIR
when set (and then nothing else), else one fixed path in the checkout.
Nothing here turns the cache on: ``jax.config.update`` is captured."""
import jax

from repro.core import REPO_ROOT
from repro.core.compile_cache import enable_compile_cache


def _captured_updates(monkeypatch) -> list:
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _captured_updates(monkeypatch)
    assert enable_compile_cache() == tmp_path
    assert calls == []                      # JAX already reads the variable


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _captured_updates(monkeypatch)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == REPO_ROOT / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", str(first))] * 2
    assert "/.jax_cache/" in (REPO_ROOT / ".gitignore").read_text().split()
