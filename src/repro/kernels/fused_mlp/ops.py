"""Public op for the fused whole-network MLP kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.fused_mlp import fused_mlp as _k


def fused_mlp_predict(
    x_uint8: jnp.ndarray, w1: jnp.ndarray, w2: jnp.ndarray, *, threshold: int = 128, **kw
) -> jnp.ndarray:
    return _k.fused_mlp_predict(x_uint8, w1, w2, threshold=threshold, **kw)
