"""utils/compile_cache.py: where the persistent XLA cache lives.

Rules pinned here: JAX_COMPILATION_CACHE_DIR, when set, is JAX's own
business and the code sets nothing; otherwise the cache is the one fixed
absolute path <repo root>/.bench_cache/xla_cache, whatever the cwd; a jax
that rejects the knob degrades to None instead of raising — the cache is
an optimization, never a dependency.
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from tpu_bfs.utils import compile_cache
from tpu_bfs.utils.compile_cache import DEFAULT_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_resolution():
    # Resolution is once-per-process; every test here varies the env, so
    # each starts unresolved.
    compile_cache.reset_resolution()
    yield
    compile_cache.reset_resolution()


@pytest.fixture
def _restore_jax_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.fixture
def _record_updates(monkeypatch):
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda *a: (updates.append(a), real_update(*a))
    )
    return updates


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, _record_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ext"))
    msgs = []
    assert enable_compile_cache(log=msgs.append) == str(tmp_path / "ext")
    # Nothing set in code: no config update, no directory made by us.
    assert _record_updates == []
    assert not os.path.exists(tmp_path / "ext")
    assert any("JAX_COMPILATION_CACHE_DIR" in m for m in msgs)


def test_default_is_fixed_path_in_checkout(monkeypatch,
                                           _restore_jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_DIR == os.path.join(REPO, ".bench_cache", "xla_cache")
    assert os.path.isabs(DEFAULT_DIR)
    assert enable_compile_cache() == DEFAULT_DIR
    assert os.path.isdir(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR


def test_default_ignores_cwd(monkeypatch, tmp_path,
                             _restore_jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    assert enable_compile_cache() == DEFAULT_DIR
    assert not os.path.exists(tmp_path / ".bench_cache")


def test_repo_specific_knob_is_gone(monkeypatch, tmp_path,
                                    _restore_jax_cache_config):
    # The old TPU_BFS_BENCH_XLA_CACHE / TPU_BFS_BENCH_CACHE routing no
    # longer moves the cache: one knob, JAX's own.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("TPU_BFS_BENCH_XLA_CACHE", str(tmp_path / "old"))
    monkeypatch.setenv("TPU_BFS_BENCH_CACHE", str(tmp_path / "bc"))
    assert enable_compile_cache() == DEFAULT_DIR
    assert not os.path.exists(tmp_path / "old")


def test_degrades_when_jax_config_update_raises(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def boom(name, value):
        raise AttributeError(f"no such config: {name}")

    monkeypatch.setattr(jax.config, "update", boom)
    msgs = []
    assert enable_compile_cache(log=msgs.append) is None
    assert any("compile cache unavailable" in m for m in msgs)


def test_degrade_logs_nothing_without_logger(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(
        jax.config, "update",
        lambda *a: (_ for _ in ()).throw(RuntimeError("nope")),
    )
    assert enable_compile_cache() is None


def test_idempotent_resolution(monkeypatch, tmp_path, _record_updates,
                               _restore_jax_cache_config):
    """Second call returns the first outcome WITHOUT re-running
    jax.config.update or re-logging — every EngineRegistry() and entry
    point calls this, and a preheat run constructs several registries."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    msgs = []
    first = enable_compile_cache(log=msgs.append)
    assert first == DEFAULT_DIR and len(_record_updates) == 1
    # A later call — even with the env now set — returns the resolved
    # path silently: one cache per process, logged once.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "late"))
    assert enable_compile_cache(log=msgs.append) == first
    assert len(_record_updates) == 1 and len(msgs) == 1
    # force=True re-resolves (the escape hatch this file's fixture uses).
    assert enable_compile_cache(force=True) == str(tmp_path / "late")
    assert len(_record_updates) == 1


def test_entries_land_in_env_dir(tmp_path):
    """End to end in a fresh process: with JAX_COMPILATION_CACHE_DIR set,
    a compile writes its entry there and nothing lands in the default."""
    cache = tmp_path / "xla"
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        from tpu_bfs.utils.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
        """
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(cache), str(cache)]
    assert os.listdir(cache), "no cache entry written"
