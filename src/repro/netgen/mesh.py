"""The device mesh that netgen's stacked dispatch shards over.

`use_mesh(mesh)` makes a mesh active for the current thread; inside it,
`NetServer` shards a stacked slot block over the mesh's `data` axis
(`active_mesh()` is what it reads). Outside any `use_mesh` context
`active_mesh()` is None and dispatch stays on one device.

The mesh builders are functions, never module-level constants, so
importing this module never touches jax device state (a caller may set
XLA_FLAGS for faked host devices before jax initialises).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator

import jax
from jax.sharding import Mesh

__all__ = ["use_mesh", "active_mesh", "make_host_mesh", "make_mesh_compat"]

_ctx = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None) -> Iterator[None]:
    """Activate `mesh` for this thread; the previous one is restored on exit."""
    prev = active_mesh()
    _ctx.mesh = mesh
    try:
        yield
    finally:
        _ctx.mesh = prev


def active_mesh() -> Mesh | None:
    """The mesh of the innermost `use_mesh` on this thread, else None."""
    return getattr(_ctx, "mesh", None)


def make_mesh_compat(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """`jax.make_mesh` with every axis Auto (implicit sharding)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A (data, model) mesh over whatever devices exist."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh_compat((data, model), ("data", "model"))
