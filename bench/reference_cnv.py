"""Plain reference for a bipolar binarized conv net (FINN's CNV), and the
comparison.

The net, from its description alone: the image (H, W, C), HWC row-major
in a uint8 request row, feeds the first layer as 8-bit values; then each
layer in turn: a valid stride-1 conv or a dense layer over the
HWC-flattened input gives sums y, and a hidden layer's output is +1
where y >= tau (its channel's threshold) and -1 elsewhere; a 2x2
max-pool takes the max of +-1 values; the last layer's sums are the
logits, and the class is their argmax. Written in numpy from that
description; it shares no code or data with the program.

Convolutions run as im2col matrix products in row blocks, in float32.
Every operand and partial sum is an integer below 2**24 in magnitude
(asserted), so each is exact.
"""
from __future__ import annotations

import numpy as np

from bench.reference import widest_gap  # noqa: F401  (the comparison)

EXACT = 2 ** 24
ROW_BLOCK = 32


def _check_exact(weights, input_max: int = 255) -> None:
    """Every sum of a layer is at most sum(|w|) times its largest input
    (255 for the pixel layer, 1 after it) in magnitude."""
    for i, layer in enumerate(weights):
        if layer["kind"] == "pool":
            continue
        w = np.abs(np.asarray(layer["weights"], np.int64))
        worst = int(w.reshape(-1, w.shape[-1]).sum(axis=0).max()) * (input_max if i == 0 else 1)
        if worst >= EXACT:
            raise ValueError(f"layer {i}: a sum reaches {worst} >= 2**24: float32 would round")


def conv(a: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Valid stride-1 conv of (B, H, W, C) by (kh, kw, C, C_out), by im2col."""
    kh, kw, c, cout = w.shape
    b, h, wd, _ = a.shape
    ho, wo = h - kh + 1, wd - kw + 1
    cols = np.lib.stride_tricks.sliding_window_view(a, (kh, kw), axis=(1, 2))
    cols = cols.transpose(0, 1, 2, 4, 5, 3).reshape(b * ho * wo, kh * kw * c)
    return (cols @ w.reshape(kh * kw * c, cout).astype(np.float32)).reshape(b, ho, wo, cout)


def maxpool(a: np.ndarray, k: int) -> np.ndarray:
    b, h, w, c = a.shape
    return a[:, :h // k * k, :w // k * k].reshape(b, h // k, k, w // k, k, c).max(axis=(2, 4))


def layer_sums(layer: dict, a: np.ndarray) -> np.ndarray:
    """The sums y of one conv or dense layer over its input `a`."""
    w = np.asarray(layer["weights"])
    if layer["kind"] == "conv":
        return conv(a, w)
    return a.reshape(a.shape[0], -1) @ w.astype(np.float32)


def image(config: dict, x_uint8: np.ndarray, input_shift: int = 0) -> np.ndarray:
    """The first layer's input: pixels as float32 (B, H, W, C); with
    `input_shift` > 0 held in 8 - shift bits first (the control)."""
    x = x_uint8
    if input_shift:
        x = (x >> input_shift) << input_shift
    return x.reshape(x.shape[0], *config["input_shape"]).astype(np.float32)


def forward(weights: list, a: np.ndarray) -> np.ndarray:
    """Logits of a block of first-layer inputs through every layer."""
    for i, layer in enumerate(weights):
        if layer["kind"] == "pool":
            a = maxpool(a, int(layer["size"]))
            continue
        y = layer_sums(layer, a)
        if i == len(weights) - 1:
            return y
        a = np.where(y >= np.asarray(layer["thresholds"], np.float32), 1.0, -1.0
                     ).astype(np.float32)
    raise ValueError("the last layer must be dense")


def logits(weights: list, config: dict, x_uint8: np.ndarray,
           input_shift: int = 0) -> np.ndarray:
    """Bipolar integer logits (rows, n_classes) as float32, in row blocks,
    of the net whose layers (`weights`, a weight scheme's output) are
    given; `input_shift` as in `image`."""
    _check_exact(weights)
    out = [forward(weights, image(config, x_uint8[i:i + ROW_BLOCK], input_shift))
           for i in range(0, x_uint8.shape[0], ROW_BLOCK)]
    n_classes = np.asarray(weights[-1]["weights"]).shape[-1]
    return np.concatenate(out) if out else np.zeros((0, n_classes), np.float32)
