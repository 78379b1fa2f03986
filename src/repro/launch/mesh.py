"""Production mesh definitions.

All constructors are FUNCTIONS (importing this module never touches jax
device state).  The production target is a TPU v5e pod of 16 x 16 = 256
chips; the multi-pod configuration stacks 2 pods = 512 chips with a pure
data-parallel 'pod' axis (DESIGN.md §6).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n):
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[: data * model],
                         axis_types=_auto(2))


HW_V5E = {
    "peak_flops_bf16": 197e12,      # per chip
    "hbm_bw": 819e9,                # bytes/s per chip
    "ici_bw": 50e9,                 # bytes/s per link direction
    "hbm_bytes": 16e9,              # HBM capacity per chip
}
