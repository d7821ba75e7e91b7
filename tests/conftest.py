"""Test environment: hermetic CPU backend with 8 virtual devices.

SURVEY §4 translation: multi-chip tests run on a simulated local mesh
(``--xla_force_host_platform_device_count=8``) instead of the reference's
localhost-socket multi-process rigs.  Must be set before jax initializes.
"""

import os

# Force CPU: tests are hermetic by design (SURVEY §4 translation) — an
# environment that exports another JAX_PLATFORMS (or a host that holds a
# real chip) must not move the suite onto it, where the 8-device mesh tests
# would fail.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    import jax

    assert jax.devices()[0].platform == "cpu", (
        "test suite must run on the virtual CPU mesh, got "
        f"{jax.devices()[0]}"
    )
    assert len(jax.devices()) >= 8, (
        f"expected 8 virtual CPU devices, got {len(jax.devices())} — "
        "XLA_FLAGS was applied too late (backend already initialized?)"
    )


@pytest.fixture
def rng():
    return np.random.default_rng(42)
