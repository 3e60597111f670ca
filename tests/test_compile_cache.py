"""The persistent compile cache is set by the entry points only."""
import os
import pathlib

import jax

import repro.server  # noqa: F401  (importing sets no cache)
from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins_and_code_sets_nothing(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    env = {compile_cache.ENV_VAR: "/somewhere/cache"}
    assert compile_cache.enable_compile_cache(env) == "/somewhere/cache"
    assert calls == []


def test_default_is_fixed_path_in_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache({}) == want
    assert calls == [("jax_compilation_cache_dir", want)]
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_import_sets_no_cache():
    assert (jax.config.jax_compilation_cache_dir
            == os.environ.get(compile_cache.ENV_VAR))
