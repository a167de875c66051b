"""The persistent compile cache is placed from outside.

``JAX_COMPILATION_CACHE_DIR`` set: that directory is used and nothing —
not the JAX config, not ``os.environ`` — is pointed anywhere else.
Unset: ``<checkout>/.geomx_compile_cache``, resolved from the package's
own location, so every cwd and every process agrees (the directory is
part of JAX's cache key: a cache that moves never hits).  See
utils/compile_cache.py."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_env_dir_is_used_and_left_alone(tmp_path, monkeypatch,
                                        restore_cache_config):
    from geomx_tpu.utils import enable_compile_cache

    want = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    monkeypatch.delenv("GEOMX_COMPILE_CACHE", raising=False)
    before_env = dict(os.environ)
    before_cfg = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == want
    assert dict(os.environ) == before_env          # nothing exported
    assert jax.config.jax_compilation_cache_dir == before_cfg  # nothing set


def test_default_dir_is_in_the_checkout_from_any_cwd(tmp_path, monkeypatch,
                                                     restore_cache_config):
    from geomx_tpu.utils import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("GEOMX_COMPILE_CACHE", raising=False)
    monkeypatch.chdir(tmp_path)
    want = os.path.join(REPO, ".geomx_compile_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_two_processes_in_two_cwds_agree(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "GEOMX_COMPILE_CACHE")}
    env["PYTHONPATH"] = REPO
    code = ("from geomx_tpu.utils import enable_compile_cache; "
            "print(enable_compile_cache())")
    dirs = []
    for cwd in (tmp_path, REPO):
        out = subprocess.run([sys.executable, "-c", code], cwd=str(cwd),
                             env=env, capture_output=True, text=True,
                             timeout=120, check=True)
        dirs.append(out.stdout.strip().splitlines()[-1])
    assert dirs[0] == dirs[1] == os.path.join(REPO, ".geomx_compile_cache")


def test_zero_is_the_off_switch(monkeypatch, restore_cache_config):
    from geomx_tpu.utils import enable_compile_cache

    monkeypatch.setenv("GEOMX_COMPILE_CACHE", "0")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


_SCOPED_AFTER_UNSCOPED = """
import os, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from geomx_tpu.utils.profiler import profile_scope

def program(scoped):
    def f(x):
        if scoped:
            with profile_scope("step/optimizer"):
                return jnp.tanh(x) * 2.0
        return jnp.tanh(x) * 2.0
    return jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()

assert "step/optimizer" not in program(False)
filled = len(os.listdir(sys.argv[1]))
assert filled >= 1
stale = program(True)
print("TRAP", "step/optimizer" not in stale, len(os.listdir(sys.argv[1])) == filled)
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
cured = program(True)
print("CURE", "step/optimizer" in cured, len(os.listdir(sys.argv[1])) > filled)
"""


def test_scope_names_and_the_cache_key(tmp_path):
    """What utils/compile_cache.py says of scope names: JAX strips
    locations before it hashes a program, so a kernel-less program under a
    new scope loads the entry the unscoped build wrote and carries no
    names; with the metadata in the key (as tests/conftest.py sets it) it
    compiles anew and carries them."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCOPED_AFTER_UNSCOPED, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-2:] == ["TRAP True True",
                                                    "CURE True True"]
