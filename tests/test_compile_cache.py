"""The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` wins
and nothing else is set; otherwise one fixed path inside the checkout."""

import os

import jax

from repro.launch import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    first = cc.enable_compile_cache()
    assert first == cc.enable_compile_cache() == cc.compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)] * 2
