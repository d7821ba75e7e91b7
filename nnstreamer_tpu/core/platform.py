"""Backend/platform helpers for SCRIPT entry points (chip_smoke.py,
benchmark/run.py's like) that own their process — the library
itself never mutates global jax config on import, so a user's deliberate
programmatic settings survive ``import nnstreamer_tpu``.  Importing THIS
module does not import jax.
"""

from __future__ import annotations

import os
from typing import Optional

#: The checkout root: the fixed default home of the compile cache (the
#: directory is part of the cache key, so a path that moves never hits).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache() -> Optional[str]:
    """Place jax's persistent compilation cache and return its directory
    (None = this run compiles uncached).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
    outside: jax reads that variable itself and this function sets NO
    directory in code.  Where it is not, the cache is
    ``<checkout>/.xla_cache`` (git-ignored).  CPU runs stay uncached
    unless the environment says otherwise: CPU AOT cache hits warn about
    machine-feature mismatches ("could lead to SIGILL").  Asking which
    backend is live initializes it — scripts calling this at start-up are
    about to do that anyway — and a backend that cannot initialize raises
    here instead of leaving a device run silently uncached.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    # Ask the backend, not JAX_PLATFORMS: the variable may be unset (jax
    # then resolves to whatever it finds) or list cpu as a second entry
    # ("tpu,cpu" — what the chip machine exports).
    if jax.default_backend() == "cpu":
        return None
    path = os.path.join(_CHECKOUT, ".xla_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu(what: str) -> dict:
    """Initialize the backend and return the device stamp every measured
    row carries (``platform``, ``device_kind``, ``device_count``).  A
    measurement path that finds no TPU fails instead of printing CPU
    numbers under a device metric's name; ``JAX_PLATFORMS`` naming ``cpu``
    FIRST (the default backend) is the one explicit way to ask for a CPU
    (functional dry) run."""
    import jax

    d = jax.devices()[0]
    dev = {"platform": d.platform, "device_kind": d.device_kind,
           "device_count": len(jax.devices())}
    pinned = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if d.platform != "tpu" and pinned != "cpu":
        raise SystemExit(
            f"{what}: needs a TPU, found platform {d.platform!r} "
            f"({d.device_kind}); set JAX_PLATFORMS=cpu for a functional "
            "CPU run")
    return dev
