"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches JAX device state — the dry-run driver
must set ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before*
the first JAX initialization, and any import-time device access would lock
the device count first.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh.

    Single pod: (data=16, model=16) — one v5e pod of 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis is
    the DCN dimension (batch-parallel only; no weight shards cross pods).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_for(devices_or_count, model_axis: int = 1,
                  axis_names: Sequence[str] = ("data", "model")):
    """Best-effort mesh over an arbitrary device set (elastic re-mesh path).

    Used by the elastic resume logic: given however many devices survive,
    build a (data, model) mesh with the requested TP degree (clamped to what
    divides the device count).
    """
    import numpy as np
    if isinstance(devices_or_count, int):
        devices = jax.devices()[:devices_or_count]
    else:
        devices = list(devices_or_count)
    n = len(devices)
    tp = model_axis
    while n % tp:
        tp -= 1
    arr = np.array(devices).reshape(n // tp, tp)
    return jax.sharding.Mesh(arr, axis_names)
