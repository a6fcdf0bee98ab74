"""Production mesh construction (functions only — importing this module
never touches jax device state).

Production target: TPU v5e pods, 256 chips each (16 x 16).  The multi-pod
mesh adds a leading "pod" axis (2 pods = 512 chips): DP spans
("pod", "data"), TP/EP stays intra-pod on "model" (ICI-only; the pod axis
crosses DCN, which only sees data-parallel gradient reduction — the
standard multi-pod layout).

Asyncval deployment note (DESIGN.md §2.1): training and validation are
*disaggregated* — ``make_disaggregated_meshes`` splits the device set so
pod 0 trains while pod 1 validates; the checkpoint directory is the only
coupling between them.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_validator_mesh(n_devices: int | None = None, *, model_axis: int = 1):
    """Elastic validator mesh: any device count (corpus encoding is purely
    data-parallel, so the validator defaults to model=1)."""
    devices = jax.devices()
    n = n_devices or len(devices)
    assert n % model_axis == 0
    return jax.sharding.Mesh(
        __import__("numpy").asarray(devices[:n]).reshape(n // model_axis,
                                                         model_axis),
        ("data", "model"))


def make_disaggregated_meshes():
    """(train_mesh, validator_mesh) over disjoint halves of the device set —
    the Asyncval deployment: pod 0 trains, pod 1 validates."""
    devices = jax.devices()
    n = len(devices)
    assert n >= 2, "disaggregation needs >= 2 devices"
    half = n // 2
    import numpy as np
    train = jax.sharding.Mesh(np.asarray(devices[:half]).reshape(half, 1),
                              ("data", "model"))
    val = jax.sharding.Mesh(np.asarray(devices[half:2 * half]).reshape(half, 1),
                            ("data", "model"))
    return train, val
