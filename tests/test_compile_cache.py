"""Persistent XLA compile cache placement (utils/compile_cache.py)."""

import importlib
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env_extra, code):
    # Re-enable explicitly: conftest pins TPP_COMPILE_CACHE=0 for the rest
    # of the suite, and subprocesses inherit that.
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPP_COMPILE_CACHE": "1",
           **env_extra}
    for name in ("TPP_AOT_CACHE", "TPP_AUTOTUNE_CACHE"):
        env.pop(name, None)
    if "JAX_COMPILATION_CACHE_DIR" not in env_extra:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env, cwd=REPO,
    )


CODE = """
import jax
from tpu_pipelines.ops import autotune
from tpu_pipelines.serving import aot
from tpu_pipelines.utils.compile_cache import maybe_enable_compile_cache
print("enabled:", maybe_enable_compile_cache())
print("dir:", jax.config.jax_compilation_cache_dir)
print("aot:", aot.cache_dir())
print("autotune:", autotune.cache_dir())
"""


def test_cache_defaults_to_fixed_dir_inside_checkout():
    """No variable set: one root inside the checkout (git-ignored), so a
    sealed machine's cache comes back with the checkout and the path —
    part of the cache key — never moves."""
    proc = _run({}, CODE)
    assert proc.returncode == 0, proc.stderr[-500:]
    root = os.path.join(REPO, ".cache")
    assert "enabled: True" in proc.stdout
    assert f"dir: {os.path.join(root, 'xla')}\n" in proc.stdout
    assert f"aot: {os.path.join(root, 'aot')}\n" in proc.stdout
    assert f"autotune: {os.path.join(root, 'autotune')}\n" in proc.stdout
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".cache/" in f.read().split()


def test_cache_disable_knob(tmp_path):
    proc = _run(
        {"TPP_COMPILE_CACHE": "0",
         "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xc")}, CODE,
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    assert "enabled: False" in proc.stdout
    assert not (tmp_path / "xc").exists()


def test_outside_cache_dir_is_the_only_root(tmp_path):
    """JAX_COMPILATION_CACHE_DIR from outside: JAX itself reads it, the
    code sets no directory, and the AOT and autotune caches move under the
    same root — nothing is written to any other cache path."""
    theirs = str(tmp_path / "theirs")
    proc = _run({"JAX_COMPILATION_CACHE_DIR": theirs}, CODE)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert "enabled: True" in proc.stdout
    assert f"dir: {theirs}\n" in proc.stdout
    assert f"aot: {os.path.join(theirs, 'aot')}\n" in proc.stdout
    assert f"autotune: {os.path.join(theirs, 'autotune')}\n" in proc.stdout


def test_idempotent_in_process(tmp_path, monkeypatch):
    import jax

    from tpu_pipelines.utils import compile_cache

    monkeypatch.setenv("TPP_COMPILE_CACHE", "1")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    # Another test (or an earlier runner construction) may have set the
    # config already; clear it so this test exercises the enable path.
    jax.config.update("jax_compilation_cache_dir", None)
    importlib.reload(compile_cache)
    # Sandbox: never point the live test process's jax config at the real
    # checkout cache (later slow compiles would persist there).
    monkeypatch.setattr(compile_cache, "_CHECKOUT", str(tmp_path))
    try:
        first = compile_cache.maybe_enable_compile_cache()
        assert compile_cache.maybe_enable_compile_cache() == first
        assert first is True
        assert jax.config.jax_compilation_cache_dir == str(
            tmp_path / ".cache" / "xla"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        importlib.reload(compile_cache)


def test_user_configured_cache_dir_is_respected(tmp_path, monkeypatch):
    """A cache dir the embedding program set via jax.config must never be
    repointed."""
    import jax

    from tpu_pipelines.utils import compile_cache

    monkeypatch.setenv("TPP_COMPILE_CACHE", "1")
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "theirs"))
    importlib.reload(compile_cache)
    monkeypatch.setattr(compile_cache, "_CHECKOUT", str(tmp_path / "ours"))
    try:
        assert compile_cache.maybe_enable_compile_cache() is True
        assert jax.config.jax_compilation_cache_dir == str(
            tmp_path / "theirs"
        )
        assert not (tmp_path / "ours").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        importlib.reload(compile_cache)
