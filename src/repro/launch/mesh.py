"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — jax locks the device count on first use, and
only ``dryrun.py`` is allowed to request 512 host-platform devices.
"""
from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: 256 chips (16x16, data x model).
    Multi-pod: 2 pods x 256 chips; the ``pod`` axis crosses the DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_test_mesh(n_data: int = 2, n_model: int = 2,
                   pod: int = 0) -> jax.sharding.Mesh:
    """Small mesh over the visible devices: fake host devices in CPU
    multi-device tests, or the chips of one host (``(2, 2)`` on a v5e 2x2)."""
    if pod:
        return jax.make_mesh((pod, n_data, n_model), ("pod", "data", "model"),
                             axis_types=(_AUTO,) * 3)
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(_AUTO,) * 2)
