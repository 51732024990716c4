"""Production mesh construction (prompt-fixed topology).

Single pod:  (16, 16)    axes ('data', 'model')      — 256 chips
Multi-pod:   (2, 16, 16) axes ('pod', 'data', 'model') — 512 chips

Defined as functions so importing this module never touches jax device
state (device count is locked at first backend init — dryrun.py must set
XLA_FLAGS before importing anything).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, *, devices=None):
    """Arbitrary mesh (tests use small ones, e.g. (2, 2)).

    Every axis is ``Auto``: the model code places data with sharding
    constraints and lets the partitioner propagate them, so activations
    carry no sharding in their types.
    """
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (CPU smoke: 1 device)."""
    n = len(jax.devices())
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes that carry the global batch (pod included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
