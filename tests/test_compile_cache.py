"""The persistent compilation cache lives where the environment says, else
at a fixed path inside the checkout — never a temporary or per-run name."""
import pathlib

import jax

from repro.utils import compile_cache as cc


def test_compile_cache_dir_follows_env_else_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV_VAR, "/placed/from/outside")
    assert cc.use_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == was   # nothing set here
    monkeypatch.delenv(cc.ENV_VAR)
    checkout = pathlib.Path(__file__).resolve().parent.parent
    try:
        assert cc.use_compile_cache() == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            checkout / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
