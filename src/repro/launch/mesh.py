"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.  The production target is a TPU
v5e pod of 16x16 = 256 chips; multi-pod doubles it with a leading "pod"
axis (DP across pods, whose ICI/DCN links are the scarce resource —
see distributed.collectives for the compressed cross-pod reduction).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(n_data: int = None, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    n_data = n_data or (n // n_model)
    return _mesh((n_data, n_model), ("data", "model"))
