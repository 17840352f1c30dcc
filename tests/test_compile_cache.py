"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, else to the fixed ``<repo>/.jax_cache``."""

from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR, use_compile_cache


def test_compile_cache_dir_follows_env_else_repo(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(ENV_VAR, str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper must not move it
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv(ENV_VAR)
        assert use_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        repo = Path(__file__).resolve().parents[1]
        assert DEFAULT_DIR == repo / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
