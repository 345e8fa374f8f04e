"""What the executable plane takes from the device it runs on: the
hardware spec, device errors, the compile cache, one process per chip."""

import multiprocessing

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    GPU_H800,
    TPU_V5E,
    LocalBackend,
    ProcBackend,
    ProcConfig,
    ServingSystem,
    Supervisor,
    compile_cache_dir,
    hardware_for,
    use_compile_cache,
)
from repro.core import compile_cache


# ------------------------------------------------------------- hardware
class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_hardware_table_keys_on_device_kind():
    assert hardware_for(_Dev("TPU v5 lite")) is TPU_V5E
    assert hardware_for(jax.devices()[0]) is GPU_H800     # host CPU
    with pytest.raises(ValueError, match="TPU v9"):
        hardware_for(_Dev("TPU v9"))


def test_serving_system_prices_its_plane():
    assert ServingSystem(n_executors=1).profiles.hw is GPU_H800
    local = ServingSystem(n_executors=1, backend=LocalBackend())
    assert local.profiles.hw is hardware_for(jax.devices()[0])
    assert ServingSystem(n_executors=1, hw=TPU_V5E).profiles.hw is TPU_V5E


# --------------------------------------------------------- device errors
def test_block_propagates_device_errors(monkeypatch):
    def fail(x):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(jax, "block_until_ready", fail)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        LocalBackend._block({"latents": jnp.zeros(3)})


def test_block_skips_plain_payloads():
    LocalBackend._block({"image": "<toy>", "n": 3, "arr": jnp.ones(2)})


# ---------------------------------------------------------- compile cache
@pytest.fixture
def cache_config():
    """Restore this process's cache settings after the helper sets them."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in prev.items():
        jax.config.update(n, v)


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == str(tmp_path)
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert path.endswith(".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


class _FakeProcess:
    """Records the spawn arguments and refuses to start."""

    spawned = []

    def __init__(self, target, args, daemon):
        self.spawned.append(args)

    def start(self):
        raise OSError("not spawned in this test")

    def join(self, timeout=None):
        pass

    def is_alive(self):
        return False


class _FakeContext:
    Process = _FakeProcess


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_supervisor_hands_workers_the_cache(monkeypatch, tmp_path, env_dir):
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(multiprocessing, "get_context",
                        lambda method: _FakeContext())
    _FakeProcess.spawned.clear()
    sup = Supervisor(ProcConfig())
    try:
        with pytest.raises(OSError, match="not spawned"):
            sup.spawn(0)
    finally:
        sup.shutdown()
    (args,) = _FakeProcess.spawned
    assert args[-1] == sup.jax_cache_dir == compile_cache_dir()


# ------------------------------------------------------ one process per chip
def test_proc_plane_refuses_a_tpu_parent(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU-only"):
        ProcBackend()
