"""Pallas TPU kernel: the whole paper network in ONE kernel launch.

The paper's FPGA artifact is a clockless combinational circuit: the entire
784-500-10 network evaluates with no intermediate storage, latency equal to
gate propagation delay. The TPU analogue is whole-network fusion: a single
`pallas_call` whose grid tiles only the batch; both weight matrices are
pinned in VMEM, and the binarize -> layer1 -> step -> layer2 -> argmax
chain executes without any HBM round-trip for intermediates.

VMEM budget (paper-sized net): w1 784x512 int32 = 1.6 MB, w2 512x16 int32
= 32 KB, one batch tile 256x784 int8 = 0.2 MB — comfortably inside the
~16 MB VMEM of a TPU core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.binary_matvec.binary_matvec import argmax_lanes

# Each byte dot sums at most 255 * K, exact in float32 below 2^24.
_MAX_FAN_IN = (1 << 24) // 255


def _binary_dot(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """{0,1} (bm, K) @ int32 (K, N) -> int32, exact on the MXU. Mosaic
    has no integer dot, so w is split into its four unsigned bytes, each
    exact as a bf16 operand; every byte dot is exact in float32, and the
    int32 recombination wraps exactly as an int32 matmul does."""
    xb = x.astype(jnp.float32).astype(jnp.bfloat16)
    acc = jnp.zeros((x.shape[0], w.shape[1]), jnp.int32)
    for i in range(4):
        byte = ((w >> (8 * i)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        d = jnp.dot(xb, byte, preferred_element_type=jnp.float32)
        acc = acc + (d.astype(jnp.int32) << (8 * i))
    return acc


def _fused_mlp_kernel(x_ref, w1_ref, w2_ref, o_ref, *, threshold: int):
    x = x_ref[...].astype(jnp.int32) > threshold                      # (bm, K)
    hi = _binary_dot(x, w1_ref[...])                                  # (bm, H)
    fi = _binary_dot(hi > 0, w2_ref[...])                             # MSB step
    o_ref[...] = argmax_lanes(fi)                                     # (bm, 1)


@functools.partial(jax.jit, static_argnames=("threshold", "bm", "interpret"))
def fused_mlp_predict(
    x_uint8: jnp.ndarray,
    w1: jnp.ndarray,
    w2: jnp.ndarray,
    *,
    threshold: int = 128,
    bm: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Predictions for a batch, whole net in one launch. Returns int32 (B,)."""
    B, K = x_uint8.shape
    K2, H = w1.shape
    H2, O = w2.shape
    assert K == K2 and H == H2, (x_uint8.shape, w1.shape, w2.shape)
    assert max(K, H) <= _MAX_FAN_IN, (K, H)
    bm = min(bm, max(8, B))
    Bp = ((B + bm - 1) // bm) * bm
    xp = jnp.zeros((Bp, K), jnp.uint8).at[:B].set(x_uint8.astype(jnp.uint8))

    out = pl.pallas_call(
        functools.partial(_fused_mlp_kernel, threshold=threshold),
        grid=(Bp // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((K, H), lambda i: (0, 0)),   # whole w1 resident
            pl.BlockSpec((H, O), lambda i: (0, 0)),   # whole w2 resident
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(xp, w1.astype(jnp.int32), w2.astype(jnp.int32))
    return out[:B, 0]
