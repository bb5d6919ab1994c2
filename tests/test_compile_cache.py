"""paddle_tpu.utils.compile_cache: the one place the persistent compile
cache is placed. JAX_COMPILATION_CACHE_DIR set -> jax reads it, nothing
is configured here; unset -> one fixed path inside the checkout (the
path is part of jax's cache key, so it must never move)."""
import os
import subprocess
import sys

import jax

from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_placement_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == [], "the env var owns the placement"


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_updates(monkeypatch)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert calls == [(compile_cache.CACHE_OPTION, want)] * 2


def test_default_is_the_same_in_another_process():
    # a second process, started from a different directory
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    code = ("from paddle_tpu.utils import compile_cache as c\nimport jax\n"
            "print(c.enable_compile_cache())\n"
            "print(getattr(jax.config, c.CACHE_OPTION))\n")
    r = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.join(REPO, "tests"), env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.stdout.split() == [compile_cache.REPO_CACHE_DIR] * 2, \
        r.stderr[-2000:]
    assert compile_cache.REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
