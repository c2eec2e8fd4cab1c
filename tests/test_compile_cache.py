"""Compile-cache placement (utils/backend.arm_compile_cache): the cache
is placed from outside where ``JAX_COMPILATION_CACHE_DIR`` is set — the
code then sets no directory — and otherwise sits at one fixed in-checkout
path. The path is part of the cache key, so it must never move."""

import os
import tempfile

import jax
import pytest

from sda_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_tpu(monkeypatch):
    """arm_compile_cache as a chip host sees it; config restored after."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_means_no_directory_set_in_code(monkeypatch, on_tpu):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    calls = []
    real = jax.config.update
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: (calls.append(k), real(k, v))[1])
    assert backend.arm_compile_cache() == "/placed/from/outside"
    assert "jax_compilation_cache_dir" not in calls


def test_env_var_unset_means_fixed_in_checkout_path(monkeypatch, on_tpu):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = backend.arm_compile_cache()
    assert got == os.path.join(REPO, ".jax_compile_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same path on every call: never a temp, pid or time component
    assert backend.arm_compile_cache() == got
    assert not got.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in got
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_cpu_backend_nobody_placed_a_cache_for_stays_uncached(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert backend.arm_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_arming_installs_the_cache_counters():
    from sda_tpu.obs import devprof

    backend.arm_compile_cache()
    assert set(devprof.compile_totals()["cache"]) == {"hit", "miss"}
    assert devprof.install_monitoring() is True
