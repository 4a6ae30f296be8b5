"""The paper's optimization ladder (§III): inference-time simplifications.

Stages (cumulative, exactly as the paper applies them):

  L0  baseline       — sigmoid activations, scaled float inputs, fp32 weights
  L1  step act       — hidden sigmoid -> step(x > 0); output argmax unchanged
                       (paper §III.A: 98% -> 95%)
  L2  binary input   — raw pixel > 128 -> {0,1} instead of float scaling
                       (paper §III.B: 95% -> 94%)
  L3  integer weights— weights cast to small integers
                       (paper §III.C: 94% -> 92%)

L4 (zero pruning) and L5 (multiplication-free addend form) are *exact
rewrites* of the L3 network — they change resources, not accuracy — and
live in `repro.netgen` (compat shim: `repro.core.netgen`).

The ladder generalizes past the paper's 784-500-10 topology: every
predictor accepts a params dict with any number of weight matrices
("w1".."wN", see `param_weights`), applying the step activation between
all layers and argmax at the output, and `QuantizedNet` holds the full
integer stack. The 2-layer construction (`w1=`/`w2=`) keeps working.

A note on L3 faithfulness: the paper's Verilog comments bound weights as
-10 < w < 10, i.e. the float weights are affinely scaled into a small
integer range before casting (raw trained weights have |w| << 1 and a
direct cast would zero the network). Positive per-layer scaling commutes
with both the step threshold at 0 and the final argmax, so the scaled cast
is mathematically the paper's transform.
"""
from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mlp as mlp_lib

INPUT_THRESHOLD = 128  # paper: pixel cutoff value
WEIGHT_BOUND = 9       # paper: -10 < weights < 10


def step(x: jnp.ndarray) -> jnp.ndarray:
    """Paper's activation: comparator at 0. On hardware this is the MSB
    (sign bit) of the signed accumulator; here a VPU compare."""
    return (x > 0).astype(jnp.int32)


def binarize_input(x_uint8: jnp.ndarray, threshold: int = INPUT_THRESHOLD) -> jnp.ndarray:
    """Paper §III.B: raw pixel in [0,255] -> {0,1} at cutoff 128."""
    return (x_uint8.astype(jnp.int32) > threshold).astype(jnp.int32)


def int_cast_weights(w: np.ndarray, bound: int = WEIGHT_BOUND) -> np.ndarray:
    """Paper §III.C: cast weights to integers, scaled into (-10, 10).

    Scale is per-matrix (a single positive scalar), preserving the sign of
    every pre-activation and the argmax of the output layer.
    """
    w = np.asarray(w, dtype=np.float64)
    s = bound / max(np.abs(w).max(), 1e-12)
    return np.round(w * s).astype(np.int32)


def weights_digest(weights, input_threshold: int = INPUT_THRESHOLD) -> str:
    """Stable content digest of a quantized stack (the compile-cache key).

    Covers the integer weight *values*, shapes, layer order, and the input
    threshold — nothing else. Values are canonicalized to int64 before
    hashing, so the digest is identical across storage dtypes (an int8
    and an int32 copy of the same matrix hash equal) and across processes
    and machines (sha256 over little-endian bytes, no Python `hash`).
    A `repro.core.convnet.ConvNet` digests itself (`ConvNet.digest`:
    layer kinds, shapes, weights, thresholds and input mode).
    """
    from repro.core.convnet import ConvNet

    if isinstance(weights, ConvNet):
        return weights.digest()
    h = hashlib.sha256()
    weights = list(weights)
    h.update(f"netgen-v1:thr={int(input_threshold)}:depth={len(weights)}"
             .encode())
    for w in weights:
        w = np.asarray(w)
        if not np.issubdtype(w.dtype, np.integer):
            raise TypeError(
                f"weights_digest hashes *quantized* stacks; got dtype {w.dtype}")
        w = np.ascontiguousarray(w.astype("<i8"))
        h.update(f":{w.shape}:".encode())
        h.update(w.tobytes())
    return h.hexdigest()


def param_weights(params: dict) -> list:
    """Ordered weight matrices of a params dict: keys "w1".."wN"."""
    keys = mlp_lib._weight_keys(params)
    if not keys:
        raise ValueError(f"no w<i> keys in params: {sorted(params)}")
    return [params[k] for k in keys]


# ---------------------------------------------------------------------------
# Ladder predictors. Each returns a jitted fn: uint8 images -> int predictions.
# ---------------------------------------------------------------------------

def _step_chain(x, ws, dtype):
    """Shared ladder arithmetic: step between layers, argmax at the end."""
    for w in ws[:-1]:
        x = step(x @ w).astype(dtype)
    return jnp.argmax(x @ ws[-1], axis=-1)


def predict_l1(params: dict):
    """L1: step hidden activations, float weights, scaled float input."""
    ws = [jnp.asarray(w, jnp.float32) for w in param_weights(params)]

    @jax.jit
    def f(x_uint8):
        return _step_chain(mlp_lib.scale_inputs(x_uint8), ws, jnp.float32)

    return f


def predict_l2(params: dict):
    """L2: + binary inputs (pixel > 128)."""
    ws = [jnp.asarray(w, jnp.float32) for w in param_weights(params)]

    @jax.jit
    def f(x_uint8):
        return _step_chain(binarize_input(x_uint8).astype(jnp.float32), ws,
                           jnp.float32)

    return f


def predict_l3(params: dict):
    """L3: + integer weights. The whole network is now integer arithmetic:
    binary inputs, int weights, int accumulators, sign-bit activations —
    exactly the arithmetic the paper's Verilog implements."""
    ws = [jnp.asarray(int_cast_weights(w), jnp.int32)
          for w in param_weights(params)]

    @jax.jit
    def f(x_uint8):
        return _step_chain(binarize_input(x_uint8), ws, jnp.int32)

    return f


@dataclasses.dataclass(frozen=True, init=False)
class QuantizedNet:
    """Frozen integer network produced by the ladder (input to netgen).

    Holds any number of layers in `weights`; the original 2-layer
    construction `QuantizedNet(w1=..., w2=...)` and the `.w1`/`.w2`
    accessors keep working (and `.w2` means *the second of two* — it
    raises on deeper stacks rather than silently aliasing a layer).
    """
    weights: tuple            # int32 matrices, (fan_in, fan_out) each
    input_threshold: int

    def __init__(self, w1=None, w2=None, *, weights=None,
                 input_threshold: int = INPUT_THRESHOLD):
        if weights is None:
            if w1 is None or w2 is None:
                raise TypeError("pass w1= and w2=, or weights=[...]")
            weights = (w1, w2)
        elif w1 is not None or w2 is not None:
            raise TypeError("pass either w1/w2 or weights=, not both")
        object.__setattr__(
            self, "weights", tuple(np.asarray(w) for w in weights))
        object.__setattr__(self, "input_threshold", int(input_threshold))

    @property
    def depth(self) -> int:
        return len(self.weights)

    def _pair(self) -> tuple:
        if self.depth != 2:
            raise AttributeError(
                f".w1/.w2 are 2-layer accessors; this net has depth "
                f"{self.depth} — use .weights")
        return self.weights

    @property
    def w1(self) -> np.ndarray:
        return self._pair()[0]

    @property
    def w2(self) -> np.ndarray:
        return self._pair()[1]

    @property
    def shapes(self) -> tuple:
        return tuple(w.shape for w in self.weights)

    def digest(self) -> str:
        """Content digest of this net (see `weights_digest`)."""
        return weights_digest(self.weights, self.input_threshold)


def quantize(params: dict) -> QuantizedNet:
    """Cast a trained float stack (any depth) to the frozen integer net."""
    return QuantizedNet(
        weights=[int_cast_weights(w) for w in param_weights(params)])


def predict_quantized(net: QuantizedNet):
    """Reference L3 arithmetic for an already-quantized net: the dense
    (matmul-based) path the compiled netgen backends must match bit-exactly."""
    ws = [jnp.asarray(w, jnp.int32) for w in net.weights]
    thr = net.input_threshold

    @jax.jit
    def f(x_uint8):
        return _step_chain(binarize_input(x_uint8, thr), ws, jnp.int32)

    return f
