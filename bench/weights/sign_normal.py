"""Binary weights: the signs of N(0, 1) draws, as +1 / -1 (one plane)."""
from __future__ import annotations

import numpy as np


def make(rng: np.random.Generator, config: dict, params: dict) -> list:
    widths = config["widths"]
    return [np.where(rng.standard_normal((k, n)) >= 0, 1, -1).astype(np.int32)
            for k, n in zip(widths[:-1], widths[1:])]
