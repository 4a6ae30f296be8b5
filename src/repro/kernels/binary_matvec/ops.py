"""Public ops for the binary (multiplication-free) matmul kernel.

Every kernel op takes `interpret=`; left unset, kernels run in Pallas
interpret mode when JAX's backend is the CPU and compile through Mosaic
on a TPU (`repro.kernels.resolve_interpret`)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.binary_matvec import binary_matvec as _k
from repro.kernels.binary_matvec import ref as _ref


def binary_matmul(x: jnp.ndarray, w: jnp.ndarray, **kw) -> jnp.ndarray:
    """y = x @ w, x in {0,1} (int8), w int — adds-only Pallas kernel."""
    return _k.binary_matmul(x, w, **kw)


def binary_matmul_packed(xp: jnp.ndarray, w: jnp.ndarray, **kw) -> jnp.ndarray:
    """y = unpack(xp) @ w for bitpacked activations (uint32 words)."""
    return _k.binary_matmul_packed(xp, w, **kw)


def binary_matmul_planes(xp: jnp.ndarray, pos: jnp.ndarray,
                         neg: jnp.ndarray, **kw) -> jnp.ndarray:
    """y = unpack(xp) @ w for w decomposed into packed signed bit-planes
    (pos/neg uint32 (P, KW, N)) — the fully bit-packed popcount kernel."""
    return _k.binary_matmul_planes(xp, pos, neg, **kw)


def binary_forward_planes(x: jnp.ndarray, *planes: jnp.ndarray,
                          **kw) -> jnp.ndarray:
    """Whole-net megakernel: raw uint8 (B, K) / (M, B, K) through every
    layer's resident bit-planes in ONE Pallas launch (binarize+pack,
    popcount accumulate, in-register step+repack, fused argmax). Plane
    arrays come from `ExecutionPlan.megakernel_view()`."""
    return _k.binary_forward_planes(x, *planes, **kw)


def pack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """Pack binary activations 32-per-uint32 (pads K up to a /32 multiple)."""
    b, k = x.shape
    kp = ((k + 31) // 32) * 32
    if kp != k:
        x = jnp.zeros((b, kp), x.dtype).at[:, :k].set(x)
    return _ref.pack_bits_ref(x)


def step_pack(acc: jnp.ndarray, *, words: int) -> jnp.ndarray:
    """Fused strict step + repack: int32 accumulators (B, N) -> uint32
    activation words (B, words). The layer-to-layer hop of the packed
    and bit-plane datapaths: no int8 activation ever materializes."""
    return _ref.step_pack_ref(acc, words)


def binarize_pack(x_uint8: jnp.ndarray, *, threshold: int,
                  words: int) -> jnp.ndarray:
    """Binarize raw uint8 inputs against `threshold` straight into packed
    uint32 words (B, words) — the packed chains' entry point."""
    return _ref.pack_bool_ref(x_uint8.astype(jnp.int32) > threshold, words)
