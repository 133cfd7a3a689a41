"""Direct coverage for the persistent-XLA-cache switch (conflux_tpu/cache.py)
and the plan cache's `clear_plans()` — previously exercised only
indirectly through the serve tests (ISSUE 2 satellite).

The module-level `_ENABLED_AT` latch is monkeypatched around each test so
ordering against the serve tests (which enable the real cache) does not
matter, and the live jax config is restored afterwards.
"""

import os
import subprocess
import sys
import time

import pytest

import jax
import jax.numpy as jnp

from conflux_tpu import cache, serve


@pytest.fixture
def fresh_cache(monkeypatch, tmp_path):
    """Un-latch the enable switch, point the fixed repo path at a temp
    dir, and restore the jax cache config."""
    monkeypatch.setattr(cache, "_ENABLED_AT", None)
    monkeypatch.setattr(cache, "REPO_CACHE_DIR", str(tmp_path / "repo"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", before)


def _dir_in_fresh_process(env_dir):
    """default_cache_dir() as another process (other pid, later clock)
    resolves it."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c",
         "from conflux_tpu import cache; print(cache.default_cache_dir())"],
        env=env, capture_output=True, text=True, check=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return out.stdout.strip()


@pytest.mark.parametrize("case", [
    "env_dir_wins", "fixed_repo_dir", "enable_uses_env_dir",
    "enable_uses_repo_dir", "same_in_every_process"])
def test_cache_dir_rule(case, fresh_cache, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR when set (and then no directory is set
    in code), else the fixed <repo>/.jax_cache — never a path that
    moves with the process, the clock or the home directory."""
    env_dir = str(fresh_cache / "from-env")
    before = jax.config.jax_compilation_cache_dir
    if case == "env_dir_wins":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert cache.default_cache_dir() == env_dir
    elif case == "fixed_repo_dir":
        assert cache.default_cache_dir() == cache.REPO_CACHE_DIR
    elif case == "enable_uses_env_dir":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert cache.enable_persistent_cache() == env_dir
        # jax reads the variable itself: the code must not re-point it
        assert jax.config.jax_compilation_cache_dir == before
    elif case == "enable_uses_repo_dir":
        got = cache.enable_persistent_cache()
        assert got == cache.REPO_CACHE_DIR
        assert os.path.isdir(got), "cache dir must be created on demand"
        assert jax.config.jax_compilation_cache_dir == got
    else:
        real = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(cache.__file__))), ".jax_cache")
        assert _dir_in_fresh_process(None) == real
        time.sleep(0.01)
        assert _dir_in_fresh_process(None) == real
        assert _dir_in_fresh_process(env_dir) == env_dir


def test_enable_sets_admission_filters(fresh_cache):
    cache.enable_persistent_cache()
    # min-entry-size filter zeroed: admission is time-thresholded only
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    assert cache.cache_enabled()


def test_enable_is_idempotent_first_call_wins(fresh_cache, monkeypatch):
    first = cache.enable_persistent_cache()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(fresh_cache / "later"))
    second = cache.enable_persistent_cache()
    assert second == first, "a live cache must not be re-pointed"
    assert jax.config.jax_compilation_cache_dir == first


def test_enable_failure_raises(fresh_cache, monkeypatch):
    """A cache that cannot be configured is an error, not a silent
    no-op: it would cost minutes of compile per process unseen."""
    def boom(*a, **k):
        raise RuntimeError("unsupported")

    # context-scoped: the patch must be gone before fixture teardown
    # restores the real jax config
    with monkeypatch.context() as m:
        m.setattr(jax.config, "update", boom)
        with pytest.raises(RuntimeError, match="unsupported"):
            cache.enable_persistent_cache()
    assert not cache.cache_enabled()


def test_clear_plans_drops_cached_plans():
    serve.clear_plans()
    plan = serve.FactorPlan.create((16, 16), jnp.float32, v=16,
                                   persistent_cache=False)
    assert serve.FactorPlan.create((16, 16), jnp.float32, v=16,
                                   persistent_cache=False) is plan
    serve.clear_plans()
    fresh = serve.FactorPlan.create((16, 16), jnp.float32, v=16,
                                    persistent_cache=False)
    assert fresh is not plan, "clear_plans left a stale plan behind"
    serve.clear_plans()
