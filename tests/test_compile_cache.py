"""``repro.launch.compile_cache.enable``: one cache directory, placed from
outside when ``JAX_COMPILATION_CACHE_DIR`` is set, else a fixed path inside
the checkout that git ignores."""
import os

import jax
import pytest

from repro.launch import compile_cache as CC

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def restore_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_enable_compilation_cache", prev[1])
    cc.reset_cache()


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_enable_picks_one_directory(restore_config, monkeypatch, tmp_path,
                                    from_env):
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(CC.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(CC.ENV, raising=False)
    path = CC.enable()
    assert jax.config.jax_enable_compilation_cache
    if from_env:
        # JAX reads the variable itself; nothing here names another dir.
        assert path == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    else:
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
