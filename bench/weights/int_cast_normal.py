"""Weights drawn N(0, 1) and cast by the paper's scaled integer cast.

Each matrix is scaled by one positive factor so that its largest magnitude
becomes `bound`, then rounded (Adiletta & Flanagan 2020, section III.C;
the same arithmetic as the program's `int_cast_weights`)."""
from __future__ import annotations

import numpy as np


def make(rng: np.random.Generator, config: dict, params: dict) -> list:
    widths, bound = config["widths"], int(params["bound"])
    out = []
    for k, n in zip(widths[:-1], widths[1:]):
        w = rng.standard_normal((k, n))
        out.append(np.round(w * (bound / np.abs(w).max())).astype(np.int32))
    return out
