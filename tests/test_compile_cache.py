"""utils/compile_cache.py: the one place that decides where XLA's persistent
compilation cache lives — the environment's directory wins and is left to
jax, otherwise the fixed ``<checkout>/.jax_cache``; never a temporary path.
"""

import os
import tempfile

import jax
import pytest

from deepfake_detection_tpu.utils.compile_cache import (DEFAULT_CACHE_DIR,
                                                        setup_compile_cache)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_KNOBS = ("jax_compilation_cache_dir",
          "jax_persistent_cache_min_entry_size_bytes",
          "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_config():
    """The suite's own cache settings (tests/conftest.py) put back after."""
    before = {k: getattr(jax.config, k) for k in _KNOBS}
    yield before
    for k, v in before.items():
        jax.config.update(k, v)


def test_default_is_the_fixed_checkout_path():
    assert DEFAULT_CACHE_DIR == os.path.join(_REPO, ".jax_cache")


@pytest.mark.parametrize("cli_dir", ["", "/somewhere/from/the/cli"])
def test_env_dir_is_left_to_jax(monkeypatch, cache_config, cli_dir):
    """Env set: no directory is set in code, and --compile-cache-dir loses."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert setup_compile_cache(cli_dir) == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == \
        cache_config["jax_compilation_cache_dir"]
    # only the two floors are touched
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


@pytest.mark.parametrize("cli_dir,want", [
    ("", DEFAULT_CACHE_DIR), ("rel/cache", os.path.abspath("rel/cache"))])
def test_env_unset_uses_cli_then_checkout(monkeypatch, cache_config,
                                          cli_dir, want):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert setup_compile_cache(cli_dir, min_compile_secs=0.5) == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5


@pytest.mark.parametrize("env", [None, "/some/dir"])
@pytest.mark.parametrize("cli_dir", ["", "/cli/dir"])
def test_never_a_temporary_directory(monkeypatch, cache_config, env,
                                     cli_dir):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    where = setup_compile_cache(cli_dir)
    assert where in (env, cli_dir, DEFAULT_CACHE_DIR)
    assert not where.startswith(tempfile.gettempdir() + os.sep)
    assert str(os.getpid()) not in where
