"""Entry-point device discipline (core/platform.py): a measurement script
refuses a non-TPU platform unless ``JAX_PLATFORMS`` pins ``cpu``, stamps
the device it ran on, and the compile cache is placed from outside when
``JAX_COMPILATION_CACHE_DIR`` is set."""

import os

import jax
import pytest

from nnstreamer_tpu.core import platform


def test_require_tpu_refuses_unpinned_cpu(monkeypatch):
    # a host with no chip and no JAX_PLATFORMS resolves to CPU silently
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as e:
        platform.require_tpu("chip_smoke.py")
    assert "needs a TPU" in str(e.value) and "'cpu'" in str(e.value)


def test_require_tpu_refuses_cpu_listed_second(monkeypatch):
    # "tpu,cpu" asks for a TPU; landing on the CPU is not what was asked
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(SystemExit):
        platform.require_tpu("chip_smoke.py")


def test_require_tpu_allows_pinned_cpu_and_stamps_device(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    dev = platform.require_tpu("chip_smoke.py")
    assert dev == {"platform": "cpu", "device_kind": "cpu",
                   "device_count": len(jax.devices())}


@pytest.fixture
def cache_config():
    """Restore jax's cache directory whatever the test set."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_placed_from_outside_sets_nothing(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert platform.enable_compilation_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".xla_cache")
    assert platform.enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)


def test_cache_stays_off_on_cpu(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert platform.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == before

