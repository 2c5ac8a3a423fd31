"""Entry points: the persistent compile-cache helper and the gnn_serve CLI."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    """Restore jax's compile-cache settings after a test changes them."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_compile_cache_writes_to_env_dir_only(tmp_path, monkeypatch,
                                              cache_config):
    """With $JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the in-checkout default stays untouched."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = (sorted(os.listdir(compile_cache.DEFAULT_DIR))
              if compile_cache.DEFAULT_DIR.exists() else None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    jax.jit(lambda x: jnp.cos(x) * 5 - 2)(jnp.ones(11)).block_until_ready()
    assert any(f.endswith("-cache") for f in os.listdir(tmp_path))
    after = (sorted(os.listdir(compile_cache.DEFAULT_DIR))
             if compile_cache.DEFAULT_DIR.exists() else None)
    assert after == before


def test_compile_cache_default_is_fixed_gitignored_dir(monkeypatch,
                                                       cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    repo = compile_cache.DEFAULT_DIR.parent
    assert path == str(repo / ".jax_cache") == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    with open(repo / ".gitignore") as f:
        assert ".jax_cache/" in f.read().split()


def test_gnn_serve_runs_pallas_on_the_device_model(tmp_path, monkeypatch,
                                                   cache_config):
    """The CLI always serves through the literal Pallas engine, planned on
    the (calibrated) model of the device in use — never VCK5000."""
    from repro.launch import gnn_serve
    from repro.core.perfmodel import runtime_fallback

    with pytest.raises(SystemExit):
        gnn_serve.main(["--literal"])
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    stats = gnn_serve.main(["--scale", "0.03", "--requests", "4",
                            "--max-batch", "2"])
    assert stats["hardware_model"].startswith(runtime_fallback().name
                                              + "+calib")
    assert stats["errors"] == 0 and stats["batches"] == 2
    assert stats["compiled_batches"] == 1
    assert stats["dispatch"]["compiled_batches"] > 0
