"""Device-mesh construction: the TPU replacement for nnstreamer-edge topology.

Reference analog: the reference distributes work by *naming hosts* —
``tensor_query_client host=H port=P`` over TCP (SURVEY §2.7/§5.8).  On TPU
the unit of distribution is the **ICI-connected device mesh**: we name
logical axes and let XLA place collectives on ICI links.

Axis conventions used across the framework:

* ``data``   — batch (DP): streams/frames sharded across chips.
* ``model``  — tensor parallel (TP): weight matrices split over channels/heads.
* ``seq``    — sequence/context parallel (SP): ring attention over tokens.
* ``expert`` — expert parallel (EP) for MoE models.
* ``pipe``   — pipeline stages (inter-stage, software-pipelined).

Any axis of size 1 is legal and free, so a single ``make_mesh`` call serves
1-chip dev runs and v5e-8 pods alike.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

AXES = ("data", "model", "seq", "expert", "pipe")


def make_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    *,
    devices=None,
    data: int = 0,
    model: int = 1,
    seq: int = 1,
    expert: int = 1,
    pipe: int = 1,
):
    """Build a ``jax.sharding.Mesh`` with the framework's canonical axes.

    ``data=0`` (default) means "absorb all remaining devices".  Example::

        mesh = make_mesh(model=2)          # on 8 devices -> data=4, model=2
        mesh = make_mesh({"data": 2, "seq": 4})
    """
    import jax
    import numpy as np

    sizes = {"data": data, "model": model, "seq": seq, "expert": expert, "pipe": pipe}
    if axis_sizes:
        unknown = set(axis_sizes) - set(AXES)
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}; valid: {AXES}")
        sizes.update(axis_sizes)

    # Validate the plan BEFORE touching numpy: a zero/negative axis used
    # to surface as an opaque numpy reshape error ("cannot reshape array
    # of size 8 into shape (8,0,...)").  Only ``data`` may be 0 (= auto:
    # absorb every device the fixed axes don't claim).
    for name in AXES:
        v = sizes[name]
        if name == "data" and (v is None or v == 0):
            continue  # auto-absorb; resolved below
        if not isinstance(v, int) or isinstance(v, bool):
            raise ValueError(
                f"mesh axis {name!r} size must be an int, got {v!r}")
        if v < 1:
            raise ValueError(
                f"mesh axis {name!r} must be >= 1, got {v} "
                "(only 'data' supports 0/None = auto-absorb)")

    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    fixed = 1
    for name in AXES:
        if name != "data" and sizes[name] > 1:
            fixed *= sizes[name]
    requested = {a: sizes[a] for a in AXES if sizes[a] not in (0, 1, None)}
    if sizes["data"] in (0, None):
        if n % fixed:
            # name the axis whose size breaks divisibility, not just the
            # product — the caller needs to know WHICH knob to fix
            bad = next((a for a in AXES
                        if a != "data" and sizes[a] > 1 and n % sizes[a]),
                       None)
            detail = (f"axis {bad!r} = {sizes[bad]} does not divide the "
                      f"device count" if bad else
                      f"the fixed axes {requested} multiply to {fixed}, "
                      "which does not divide the device count")
            raise ValueError(
                f"cannot auto-size the 'data' axis over {n} device(s): "
                f"{detail} (requested {requested or '{}'}, "
                f"{n} device(s) available)")
        sizes["data"] = n // fixed
    total = sizes["data"] * fixed
    if total != n:
        bad = next((a for a in AXES if sizes[a] > 1 and n % sizes[a]), None)
        hint = (f"; axis {bad!r} = {sizes[bad]} does not divide "
                f"{n}" if bad else "")
        raise ValueError(
            f"mesh plan {requested or dict(sizes)} needs {total} device(s), "
            f"have {n}{hint}: axis sizes must multiply to the device count")

    shape = tuple(sizes[a] for a in AXES)
    arr = np.asarray(devs).reshape(shape)
    return jax.sharding.Mesh(arr, AXES)


def single_device_mesh(device=None):
    """A 1-device mesh (every axis size 1) — lets mesh-aware code run anywhere."""
    import jax

    dev = device if device is not None else jax.devices()[0]
    return make_mesh(data=1, devices=[dev])


def mesh_axis_size(mesh, name: str) -> int:
    return int(mesh.shape.get(name, 1))


def device_coords(mesh) -> Dict[int, Tuple[int, int]]:
    """Map ``device.id`` -> its ``(data, model)`` coordinate in the mesh —
    how per-replica counters and trace spans name a chip's position in a
    2-D placement (docs/BATCHING.md "2-D sharded dispatch")."""
    import numpy as np

    coords: Dict[int, Tuple[int, int]] = {}
    arr = np.asarray(mesh.devices)
    di_axis = AXES.index("data")
    mi_axis = AXES.index("model")
    for idx in np.ndindex(arr.shape):
        coords[arr[idx].id] = (int(idx[di_axis]), int(idx[mi_axis]))
    return coords


def local_batch(mesh, global_batch: int) -> int:
    d = mesh_axis_size(mesh, "data")
    if global_batch % d:
        raise ValueError(f"global batch {global_batch} not divisible by data={d}")
    return global_batch // d
