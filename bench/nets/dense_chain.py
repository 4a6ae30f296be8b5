"""The net of a configuration with no `net` key: a dense chain of integer
matrices at `widths`, uint8 pixels binarized by `x > input_threshold`, a step
at 0 after every layer but the last, and an argmax.

A net module gives the harness what is particular to one shape of net:

- `row_length(config)`: bytes in one uint8 request row;
- `build(config, weights)`: what `NetServer.register` takes for one
  version's weights (a weight scheme's `make` output);
- `ops`, `bytes_moved` and `min_seconds` of `(config, rows, versions)`: the
  work of one call of `rows` rows for each of `versions` versions, and the
  least time the chip could take for it, for `step_mfu_pct` and the kernels'
  roofline readers. Here they are `bench/work.py` at the config's widths.
"""
from __future__ import annotations

from bench import work


def row_length(config: dict) -> int:
    return int(config["widths"][0])


def build(config: dict, weights: list):
    from repro.core.quantize import QuantizedNet

    return QuantizedNet(weights=weights, input_threshold=int(config["input_threshold"]))


def ops(config: dict, rows: int, versions: int = 1) -> int:
    return work.ops(config["widths"], rows, versions)


def bytes_moved(config: dict, rows: int, versions: int = 1) -> int:
    return work.bytes_moved(config["widths"], rows, versions)


def min_seconds(config: dict, rows: int, versions: int, peak: dict) -> tuple[float, str]:
    return work.min_seconds(config["widths"], rows, versions, peak)
