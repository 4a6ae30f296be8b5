"""The net of a configuration with `"net": "finn_cnv"`: a binarized conv
net (FINN's CNV) from its `layers` list, over an `input_shape` image in
one `input_length`-byte uint8 row, bipolar weights and activations with
one threshold per channel, served by netgen as a `ConvNet`.

The work of one row is the net's own multiply-accumulates: a conv layer
takes H_out * W_out * kh * kw * C_in * C_out, a dense one K * N. A call
moves its uint8 rows in, an int32 class out, and every weight once at a
byte. `conv_min_seconds` prices the conv layers alone (their operations;
the input rows and the conv weights as bytes), for `conv_roofline`.
"""
from __future__ import annotations

from bench import work


def _layers(config: dict) -> list:
    """[(kind, macs a row, weights)] of every layer, from the shapes."""
    h, w, c = config["input_shape"]
    shape = [h, w, c]
    out = []
    for spec in config["layers"]:
        if spec["kind"] == "conv":
            k, cout = int(spec["kernel"]), int(spec["channels"])
            ho, wo = shape[0] - k + 1, shape[1] - k + 1
            n_w = k * k * shape[2] * cout
            out.append(("conv", ho * wo * n_w, n_w))
            shape = [ho, wo, cout]
        elif spec["kind"] == "pool":
            s = int(spec["size"])
            shape = [shape[0] // s, shape[1] // s, shape[2]]
        else:
            k = 1
            for d in shape:
                k *= d
            n = int(spec["units"])
            out.append(("dense", k * n, k * n))
            shape = [n]
    return out


def row_length(config: dict) -> int:
    return int(config["input_length"])


def macs(config: dict, kinds=("conv", "dense")) -> int:
    """Multiply-accumulates of one row through the layers of `kinds`."""
    return sum(m for kind, m, _ in _layers(config) if kind in kinds)


def weight_count(config: dict, kinds=("conv", "dense")) -> int:
    return sum(n for kind, _, n in _layers(config) if kind in kinds)


def build(config: dict, weights: list):
    """The program's `ConvNet`, folded from the bipolar layers."""
    import numpy as np

    from repro.core.convnet import ConvLayer, ConvNet, DenseLayer, PoolLayer

    layers = []
    for layer in weights:
        if layer["kind"] == "pool":
            layers.append(PoolLayer(int(layer["size"])))
        else:
            cls = ConvLayer if layer["kind"] == "conv" else DenseLayer
            layers.append(cls(np.asarray(layer["weights"]), np.asarray(layer["thresholds"])))
    return ConvNet.from_bipolar(tuple(config["input_shape"]), layers,
                                input_mode=config["input_mode"])


def ops(config: dict, rows: int, versions: int = 1) -> int:
    return 2 * versions * int(rows) * macs(config)


def bytes_moved(config: dict, rows: int, versions: int = 1) -> int:
    return versions * (int(rows) * (row_length(config) + 4) + weight_count(config))


def min_seconds(config: dict, rows: int, versions: int, peak: dict) -> tuple[float, str]:
    return work.least_seconds(ops(config, rows, versions),
                              bytes_moved(config, rows, versions), peak)


def conv_min_seconds(config: dict, rows: int, versions: int,
                     peak: dict) -> tuple[float, str]:
    """Least time of the conv layers alone for a call of `rows` rows."""
    n_ops = 2 * versions * int(rows) * macs(config, ("conv",))
    n_bytes = versions * (int(rows) * row_length(config) + weight_count(config, ("conv",)))
    return work.least_seconds(n_ops, n_bytes, peak)
