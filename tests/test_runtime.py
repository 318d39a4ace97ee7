"""Process set-up of the launch drivers: compile-cache placement and the
CPU-only --host-devices mode."""
import sys

import jax
import pytest

from repro.launch import runtime


@pytest.fixture
def cache_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.use_compile_cache()
    assert path == str(runtime.CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (runtime.CHECKOUT / "chip_smoke.py").exists()


def test_compile_cache_env_wins_and_code_sets_nothing(monkeypatch,
                                                      cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


class _Exec(Exception):
    pass


def _fake_execv(path, args):
    raise _Exec(path, args)


def test_host_devices_sets_cpu_and_appends_xla_flags(monkeypatch):
    monkeypatch.setattr(runtime.os, "execv", _fake_execv)
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/x "
                       "--xla_force_host_platform_device_count=8")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(_Exec) as e:
        runtime.force_host_devices(4, "repro.launch.train", ["--steps", "2"])
    assert e.value.args == (sys.executable, [
        sys.executable, "-m", "repro.launch.train", "--steps", "2"])
    assert runtime.os.environ["JAX_PLATFORMS"] == "cpu"
    assert runtime.os.environ["XLA_FLAGS"] == (
        "--xla_dump_to=/x --xla_force_host_platform_device_count=4")
    # the re-exec'd child is already in that mode: no second exec
    runtime.force_host_devices(4, "repro.launch.train", ["--steps", "2"])
