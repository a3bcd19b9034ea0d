"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model) — the 'pod' axis
carries cross-pod DP (or FSDP for the pod-FSDP configs); 'data' carries
in-pod DP/FSDP; 'model' carries TP/EP.
"""

from __future__ import annotations

import jax


def _mk_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return _mk_mesh((data, model), ("data", "model"))
