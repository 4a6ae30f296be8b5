"""Pallas kernels of the netgen datapaths (and the LM substrate)."""
from __future__ import annotations


def resolve_interpret(interpret: bool | None) -> bool:
    """The one rule for Pallas interpret mode: an explicit flag wins;
    otherwise kernels run interpreted only when JAX's default backend is
    the CPU, and lower through Mosaic everywhere else. Called at trace
    time, so a TPU process never takes the interpreter by default."""
    if interpret is not None:
        return bool(interpret)
    import jax

    return jax.default_backend() == "cpu"
