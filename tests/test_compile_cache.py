"""The persistent compilation cache rule every entry point follows:
``$JAX_COMPILATION_CACHE_DIR`` when set (no directory set in code), else
the fixed, gitignored ``<repo>/.jax_cache``; every program is stored."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache, obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_CONFIG = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_dir_config():
    prev = {name: getattr(jax.config, name) for name in _CONFIG}
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    for name, value in prev.items():
        jax.config.update(name, value)


def _stores_every_program():
    return (jax.config.jax_persistent_cache_min_compile_time_secs == 0
            and jax.config.jax_persistent_cache_min_entry_size_bytes == -1)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None   # nothing set
    assert _stores_every_program()


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path                 # stable per call
    assert _stores_every_program()
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_stats_count_jax_cache_events():
    before = compile_cache.stats()
    for event in ("/jax/compilation_cache/compile_requests_use_cache",) * 3 \
            + ("/jax/compilation_cache/cache_hits",
               "/jax/compilation_cache/cache_misses",
               "/jax/compilation_cache/tasks_using_cache"):
        compile_cache._on_event(event)
    after = compile_cache.stats()
    assert after["hits"] - before["hits"] == 1
    assert after["misses"] - before["misses"] == 2
    assert after["writes"] - before["writes"] == 1
    assert obs.counter("compile_cache.requests").value >= 3


_RUN = """
import json
import jax, jax.numpy as jnp
from repro import compile_cache
compile_cache.enable()
jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps(compile_cache.stats()))
"""


def test_second_process_hits_what_the_first_compiled(tmp_path):
    """A program that compiles in milliseconds is stored, and a second
    process loads it instead of compiling it."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    runs = [json.loads(subprocess.run(
        [sys.executable, "-c", _RUN], env=env, capture_output=True,
        text=True, check=True, timeout=300).stdout.strip().splitlines()[-1])
        for _ in range(2)]
    assert runs[0]["writes"] >= 1
    assert runs[1]["hits"] >= 1 and runs[1]["writes"] == 0
