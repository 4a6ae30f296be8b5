"""Binarized convolutional nets: the description netgen compiles, the
fold from a bipolar (FINN-style) model, and a plain float reference.

A `ConvNet` is what `Session.compile`, `NetServer.register` and
`ServingEngine.register` take beside a `QuantizedNet`:

  input   — an (H, W, C) image read HWC row-major from one uint8 request
            row of H*W*C bytes, in one of two modes: "compare" (every
            pixel binarized by `pixel > input_threshold`, 1 bit) or
            "pixels" (the 8-bit values feed the first layer as they are);
  layers  — `ConvLayer` (a valid kh x kw convolution, integer weights
            (kh, kw, c_in, c_out), one integer threshold per channel:
            `acc > t` -> {0, 1}), `PoolLayer` (a 2x2 max-pool over the
            {0, 1} map, i.e. an OR) and `DenseLayer` (integer weights
            (k, n) over the HWC-flattened input, one threshold per unit).
            The last layer is a `DenseLayer` whose thresholds are
            subtracted from its accumulators before the argmax.

Values between layers are {0, 1}. FINN's binarized nets compute with
bipolar values (+1 / -1) and batch-norm thresholds instead; the fold
between the two (`ConvNet.from_bipolar`) is exact, see its docstring.

`bipolar_logits` is the plain float32 `jax.numpy` forward of the bipolar
net (`lax.conv_general_dilated`, `reduce_window` max, +-1 activations),
the architecture's reference. It shares no code with netgen's lowering;
`repro.netgen.graph.evaluate` is the integer arbiter of the folded net.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Sequence, Union

import numpy as np

__all__ = ["ConvLayer", "ConvNet", "DenseLayer", "INPUT_MODES", "PoolLayer",
           "bipolar_logits"]

INPUT_MODES = ("compare", "pixels")


def _int_array(a, name: str, ndim: int) -> np.ndarray:
    a = np.asarray(a)
    if a.ndim != ndim:
        raise ValueError(f"{name} must be {ndim}-D, got shape {a.shape}")
    if not np.issubdtype(a.dtype, np.integer):
        raise ValueError(f"{name} must be integer, got dtype {a.dtype}")
    a = a.astype(np.int64)
    a.flags.writeable = False
    return a


@dataclasses.dataclass(frozen=True, eq=False)
class ConvLayer:
    """Valid (unpadded) convolution, stride 1: weights (kh, kw, c_in,
    c_out), thresholds (c_out,)."""
    weights: np.ndarray
    thresholds: np.ndarray

    def __post_init__(self):
        w = _int_array(self.weights, "conv weights", 4)
        t = _int_array(self.thresholds, "conv thresholds", 1)
        if t.shape != (w.shape[3],):
            raise ValueError(f"conv thresholds {t.shape} for {w.shape[3]} channels")
        object.__setattr__(self, "weights", w)
        object.__setattr__(self, "thresholds", t)

    kind = "conv"


@dataclasses.dataclass(frozen=True, eq=False)
class PoolLayer:
    """Max-pool over non-overlapping size x size windows (size 2)."""
    size: int = 2

    def __post_init__(self):
        if self.size != 2:
            raise ValueError(f"only a 2x2 max-pool is supported, got {self.size}")

    kind = "pool"


@dataclasses.dataclass(frozen=True, eq=False)
class DenseLayer:
    """Dense layer over the HWC-flattened input: weights (k, n),
    thresholds (n,)."""
    weights: np.ndarray
    thresholds: np.ndarray

    def __post_init__(self):
        w = _int_array(self.weights, "dense weights", 2)
        t = _int_array(self.thresholds, "dense thresholds", 1)
        if t.shape != (w.shape[1],):
            raise ValueError(f"dense thresholds {t.shape} for {w.shape[1]} units")
        object.__setattr__(self, "weights", w)
        object.__setattr__(self, "thresholds", t)

    kind = "dense"


Layer = Union[ConvLayer, PoolLayer, DenseLayer]


@dataclasses.dataclass(frozen=True, eq=False)
class ConvNet:
    """A binarized conv net as netgen compiles it (see module doc)."""
    input_shape: tuple
    layers: tuple
    input_mode: str = "pixels"
    input_threshold: int = 128

    def __post_init__(self):
        shape = tuple(int(d) for d in self.input_shape)
        if len(shape) != 3 or min(shape) < 1:
            raise ValueError(f"input_shape must be (H, W, C), got {self.input_shape}")
        if self.input_mode not in INPUT_MODES:
            raise ValueError(f"input_mode {self.input_mode!r} not in {INPUT_MODES}")
        if not 0 <= int(self.input_threshold) < 255:
            raise ValueError(f"input_threshold {self.input_threshold} outside [0, 255)")
        object.__setattr__(self, "input_shape", shape)
        object.__setattr__(self, "input_threshold", int(self.input_threshold))
        object.__setattr__(self, "layers", tuple(self.layers))
        self.shapes()                      # validates the chain

    @property
    def n_inputs(self) -> int:
        h, w, c = self.input_shape
        return h * w * c

    @property
    def n_classes(self) -> int:
        return int(self.layers[-1].weights.shape[1])

    def shapes(self) -> list:
        """The output shape of every layer: (H, W, C) maps, then (n,)
        after a dense layer. Raises ValueError on a chain that does not
        fit (a kernel larger than its map, a pool of an odd map, a pool
        not after a conv, a conv after a dense layer, a dense fan-in that
        is not the flattened input, or a last layer that is not dense)."""
        if not self.layers:
            raise ValueError("a ConvNet needs at least one layer")
        if not isinstance(self.layers[-1], DenseLayer):
            raise ValueError("the last layer must be a DenseLayer (the argmax layer)")
        cur: tuple = self.input_shape
        out = []
        prev = None
        for i, layer in enumerate(self.layers):
            if isinstance(layer, ConvLayer):
                if len(cur) != 3:
                    raise ValueError(f"layer {i}: conv after a dense layer")
                kh, kw, cin, cout = layer.weights.shape
                if cin != cur[2] or kh > cur[0] or kw > cur[1]:
                    raise ValueError(f"layer {i}: conv {layer.weights.shape} "
                                     f"does not fit a {cur} map")
                cur = (cur[0] - kh + 1, cur[1] - kw + 1, cout)
            elif isinstance(layer, PoolLayer):
                if not isinstance(prev, ConvLayer):
                    raise ValueError(f"layer {i}: a pool must follow a conv layer")
                if cur[0] % layer.size or cur[1] % layer.size:
                    raise ValueError(f"layer {i}: {layer.size}x{layer.size} pool "
                                     f"of an odd {cur} map")
                cur = (cur[0] // layer.size, cur[1] // layer.size, cur[2])
            elif isinstance(layer, DenseLayer):
                k = int(np.prod(cur))
                if layer.weights.shape[0] != k:
                    raise ValueError(f"layer {i}: dense fan-in {layer.weights.shape[0]} "
                                     f"!= {k} inputs")
                cur = (layer.weights.shape[1],)
            else:
                raise TypeError(f"layer {i}: not a ConvNet layer: {layer!r}")
            out.append(cur)
            prev = layer
        return out

    def digest(self) -> str:
        """Content digest (the compile-cache key): input shape, mode and
        threshold, and every layer's kind, shape, weights and thresholds
        (int64 little-endian, so storage dtype does not matter)."""
        h = hashlib.sha256()
        h.update(f"netgen-convnet-v1:{self.input_shape}:{self.input_mode}:"
                 f"{self.input_threshold if self.input_mode == 'compare' else '-'}:"
                 f"{len(self.layers)}".encode())
        for layer in self.layers:
            h.update(f":{layer.kind}".encode())
            if isinstance(layer, PoolLayer):
                h.update(f":{layer.size}".encode())
                continue
            for a in (layer.weights, layer.thresholds):
                a = np.ascontiguousarray(a.astype("<i8"))
                h.update(f":{a.shape}:".encode())
                h.update(a.tobytes())
        return h.hexdigest()

    @classmethod
    def from_bipolar(cls, input_shape, layers: Sequence, *,
                     input_mode: str = "pixels",
                     input_threshold: int = 128) -> "ConvNet":
        """Fold a bipolar net into netgen's {0, 1} datapath, exactly.

        `layers` holds the same layer types with bipolar meaning: weights
        +-1 (any integers work), activations +1 / -1, and each
        conv/dense layer's `thresholds` tau a batch-norm folded to
        `y >= tau` (FINN's form). The last layer's thresholds are not
        read: its bipolar scores feed the argmax. With a = (b + 1) / 2 the
        {0, 1} form of a bipolar activation b and s = sum(w * a):

          * a layer that reads bipolar values: y = 2 s - sum(w) per unit,
            and y >= tau <=> s > ceil((tau + sum(w)) / 2) - 1; sum(w) is
            fixed per channel because the conv has no padding;
          * the first layer in "pixels" mode reads the image itself:
            y = s, so the threshold becomes tau - 1 (`acc > t`); in
            "compare" mode the binarized input is bipolar like the rest;
          * the last layer: argmax of 2 s - sum(w) = argmax of
            s - sum(w) / 2 when every sum(w) is even (every fan-in even,
            as for +-1 weights), else of 2 s - sum(w) with doubled
            weights;
          * a max-pool of bipolar values is the OR of the {0, 1} ones.
        """
        out = []
        bipolar_input = input_mode == "compare"
        for i, layer in enumerate(layers):
            if isinstance(layer, PoolLayer):
                out.append(layer)
                continue
            w = layer.weights
            axes = (0, 1, 2) if isinstance(layer, ConvLayer) else (0,)
            total = w.sum(axis=axes)
            if i == len(layers) - 1:
                if np.all(total % 2 == 0):
                    out.append(DenseLayer(w, total // 2))
                else:
                    out.append(DenseLayer(2 * w, total))
                continue
            tau = layer.thresholds
            if i == 0 and not bipolar_input:
                t = tau - 1
            else:
                t = -((-(tau + total)) // 2) - 1          # ceil((tau + sum w)/2) - 1
            out.append(type(layer)(w, t))
        return cls(input_shape=input_shape, layers=tuple(out), input_mode=input_mode,
                   input_threshold=input_threshold)


def bipolar_logits(input_shape, layers: Sequence, x_uint8, *,
                   input_mode: str = "pixels", input_threshold: int = 128):
    """Float32 logits of the bipolar net (see `ConvNet.from_bipolar` for
    its meaning) on uint8 rows (B, H*W*C), in plain `jax.numpy` at the
    highest matmul precision. Every value is an integer below 2**24 in
    magnitude for nets of +-1 weights up to tens of thousands of
    fan-in, so float32 is exact there."""
    import jax
    import jax.numpy as jnp

    h, w, c = (int(d) for d in input_shape)
    x = jnp.asarray(x_uint8).reshape(-1, h, w, c).astype(jnp.float32)
    if input_mode == "compare":
        a = jnp.where(x > input_threshold, 1.0, -1.0)
    else:
        a = x
    with jax.default_matmul_precision("highest"):
        for i, layer in enumerate(layers):
            last = i == len(layers) - 1
            if isinstance(layer, PoolLayer):
                k = layer.size
                a = jax.lax.reduce_window(a, -jnp.inf, jax.lax.max,
                                          (1, k, k, 1), (1, k, k, 1), "VALID")
                continue
            wf = jnp.asarray(layer.weights, jnp.float32)
            if isinstance(layer, ConvLayer):
                y = jax.lax.conv_general_dilated(
                    a, wf, window_strides=(1, 1), padding="VALID",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
            else:
                y = a.reshape(a.shape[0], -1) @ wf
            if last:
                return y
            tau = jnp.asarray(layer.thresholds, jnp.float32)
            a = jnp.where(y >= tau, 1.0, -1.0)
    raise ValueError("no layers")
