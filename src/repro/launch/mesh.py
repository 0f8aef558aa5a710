"""Production meshes (assignment §MULTI-POD DRY-RUN).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state — device counts are locked on first jax init, and only
``dryrun.py`` (which sets XLA_FLAGS before any import) should ever see 512
host devices.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (tests / CPU smoke runs)."""
    n = jax.device_count()
    data = n // model_axis
    return _make_mesh((data, model_axis), ("data", "model"))


SWEEP_AXIS = "grid"


def make_sweep_mesh(num_devices: int | None = None):
    """1-D mesh over the flattened sweep-run axis (DESIGN.md §2).

    The sweep engine shards its flattened grid axis over this mesh's
    ``"grid"`` axis via ``shard_map`` — pure batch parallelism, no
    collectives.  ``num_devices`` restricts to a prefix of the available
    devices (the device-scaling benchmark sweeps it); default is all.
    """
    import numpy as np

    devs = jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"asked for {num_devices} devices, only {len(devs)} present")
        devs = devs[:num_devices]
    return jax.sharding.Mesh(np.asarray(devs), (SWEEP_AXIS,))


def federation_axis(mesh) -> str:
    """The paper's agent axis: cross-pod when present, else data (DESIGN §4)."""
    return "pod" if "pod" in mesh.axis_names else "data"
