"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization, while unit tests import the
same code under a single real device.
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis Auto (implicit sharding)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh_compat((data, model), ("data", "model"))


# TPU v5e hardware model used by the roofline (per chip).
HW = {
    "peak_bf16_flops": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link (~4 links/chip on v5e)
}
