"""Public ops for the W8A8 quantized matmul kernel.

`qlinear` is the end-to-end op: quantize the activation on the fly
(per-tensor symmetric), run the int8 kernel against pre-quantized
weights, dequantize in the fused epilogue. No netgen datapath calls it;
`tests/test_kernels_quant.py` holds it to its reference.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.quant_matmul import quant_matmul as _k
from repro.kernels.quant_matmul import ref as _ref

_INTERPRET = True


def quant_matmul(x_q, w_q, sx, sw, **kw) -> jnp.ndarray:
    kw.setdefault("interpret", _INTERPRET)
    return _k.quant_matmul(x_q, w_q, sx, sw, **kw)


def quantize_act(x: jnp.ndarray):
    return _ref.quantize_act_ref(x)


def quantize_weight(w: jnp.ndarray):
    return _ref.quantize_weight_ref(w)


def qlinear(x: jnp.ndarray, w_q: jnp.ndarray, sw: jnp.ndarray, **kw) -> jnp.ndarray:
    """fp activation in, fp out; weights already int8 + per-channel scales."""
    x_q, sx = _ref.quantize_act_ref(x)
    y = quant_matmul(x_q, w_q, sx, sw, **kw)
    return y.astype(x.dtype)
