"""The persistent compilation cache follows one rule at every entry point:
``JAX_COMPILATION_CACHE_DIR`` when it is set, else a fixed, git-ignored
directory of the checkout."""

from __future__ import annotations

from pathlib import Path

import jax

from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch) -> list:
    calls: list = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_cache_follows_env_and_sets_nothing(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_cache_defaults_to_fixed_ignored_checkout_dir(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache() == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
