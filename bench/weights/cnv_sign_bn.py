"""Binary conv-net weights with batch-norm thresholds: +1 / -1 weights,
the signs of N(0, 1) draws, and one threshold tau per channel of every
hidden layer (`y >= tau` on its bipolar sums, FINN's folded batch-norm).

In place of trained statistics, each channel's tau is set on a
calibration batch of `calibration_images` uniform uint8 images drawn
from the same stream as the weights: the channel's sums over the batch
are ranked and tau is the sum at a share drawn uniform in
[1 - fire_high, 1 - fire_low], so the channel fires on about a share in
[fire_low, fire_high] of images like the benchmark's. The sums come from
the reference's own layer arithmetic (`bench/reference_cnv.py`). The
last layer has no threshold.

Returns the layers in order as dicts: {"kind": "conv", "weights":
(kh, kw, c_in, c_out) int8, "thresholds": (c_out,) int64},
{"kind": "pool", "size": 2}, {"kind": "dense", "weights": (k, n) int8,
"thresholds": (n,) int64, zeros for the last layer}.
"""
from __future__ import annotations

import numpy as np

from bench import reference_cnv as ref


def make(rng: np.random.Generator, config: dict, params: dict) -> list:
    h, w, c = config["input_shape"]
    x = rng.integers(0, 256, (int(params["calibration_images"]), h * w * c), dtype=np.uint8)
    a = ref.image(config, x)
    shape = (h, w, c)
    layers: list = []
    specs = config["layers"]
    for i, spec in enumerate(specs):
        if spec["kind"] == "pool":
            layers.append({"kind": "pool", "size": int(spec["size"])})
            a = ref.maxpool(a, int(spec["size"]))
            shape = a.shape[1:]
            continue
        if spec["kind"] == "conv":
            k = int(spec["kernel"])
            wshape = (k, k, shape[-1], int(spec["channels"]))
        else:
            wshape = (int(np.prod(shape)), int(spec["units"]))
        weights = np.where(rng.standard_normal(wshape) >= 0, 1, -1).astype(np.int8)
        layer = {"kind": spec["kind"], "weights": weights}
        y = ref.layer_sums(layer, a)
        flat = y.reshape(-1, wshape[-1])
        if i == len(specs) - 1:
            layer["thresholds"] = np.zeros(wshape[-1], np.int64)
        else:
            share = 1.0 - rng.uniform(params["fire_low"], params["fire_high"], wshape[-1])
            tau = np.array([np.quantile(flat[:, j], share[j], method="inverted_cdf")
                            for j in range(wshape[-1])])
            layer["thresholds"] = tau.astype(np.int64)
            a = np.where(y >= tau.astype(np.float32), 1.0, -1.0).astype(np.float32)
            shape = a.shape[1:]
        layers.append(layer)
    return layers
