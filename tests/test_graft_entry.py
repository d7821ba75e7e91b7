"""Driver-entry contract tests (``__graft_entry__.py``).

These tests pin the PUBLIC ``dryrun_multichip`` entry, not just the body:
it must complete inside a wall-clock bound whatever platform the
environment advertises, because it never touches the caller's backend at
all — the body runs in a child pinned to the virtual CPU mesh.
"""

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    import jax

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    out.block_until_ready()
    assert out.shape == (8, 1001)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
def test_dryrun_multichip_public_entry(monkeypatch):
    # A JAX_PLATFORMS value naming a backend that does not exist here: the
    # entry must neither probe it nor pass it through to the child (the
    # child's environment pins cpu).
    monkeypatch.setenv("JAX_PLATFORMS", "nonexistent_backend,cpu")
    t0 = time.monotonic()
    graft.dryrun_multichip(8)
    elapsed = time.monotonic() - t0
    # Body measured ~30s on the 8-device CPU mesh; generous margin for cold
    # compile, but far below the driver's timeout (the failure mode that
    # shipped twice was an unbounded hang, not slowness).
    assert elapsed < 240, f"dryrun_multichip took {elapsed:.0f}s"
