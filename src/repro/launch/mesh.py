"""Production meshes.  Defined as functions (never module-level constants)
so importing this module never touches jax device state."""
from __future__ import annotations

import jax


def _auto(n):
    """Every mesh axis in Auto mode (GSPMD propagates the shardings)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ('data','model'); 2x16x16 = 512 chips
    multi-pod ('pod','data','model')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever local devices exist (tests / examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
