"""The compile-cache helper: the environment's directory when set, else a
fixed directory inside the checkout."""
import pathlib

import jax

from repro.launch import compile_cache as cc

REPO = pathlib.Path(__file__).resolve().parents[2]


def test_cache_dir_honours_environment():
    env = {cc.ENV_VAR: "/some/where/cache"}
    assert cc.compile_cache_dir(env) == pathlib.Path("/some/where/cache")


def test_cache_dir_defaults_to_fixed_path_in_checkout():
    assert cc.compile_cache_dir({}) == REPO / ".jax_cache"
    assert cc.compile_cache_dir({cc.ENV_VAR: ""}) == REPO / ".jax_cache"
    # The same path on every call: the cache key includes the directory.
    assert cc.compile_cache_dir({}) == cc.compile_cache_dir({})


def test_enable_sets_nothing_when_environment_names_the_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV_VAR, "/some/where/cache")
    assert cc.enable_compile_cache() == pathlib.Path("/some/where/cache")
    assert jax.config.jax_compilation_cache_dir == before
