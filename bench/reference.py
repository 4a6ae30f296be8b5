"""Plain reference for a dense step-activation classifier, and the comparison.

The net: pixels binarized by `x > input_threshold`, then for every layer but
the last `a = step(a @ W)` with `step(v) = v > 0`, and the class is the argmax
of the last layer's integer logits. Written in numpy from that description;
it shares no code or data with the program.

The matrix products run in float32. Every operand and partial sum is an
integer below 2**24 in magnitude (asserted), so each is exact.
"""
from __future__ import annotations

import numpy as np

EXACT = 2 ** 24
ROW_BLOCK = 4096


def _check_exact(weights) -> None:
    for w in weights:
        worst = int(np.abs(w).sum(axis=0).max())
        if worst >= EXACT:
            raise ValueError(f"a column sums to {worst} >= 2**24: float32 would round")


def logits(weights, config: dict, x_uint8: np.ndarray,
           input_shift: int = 0) -> np.ndarray:
    """Integer logits (rows, n_classes) as float32, computed in row blocks,
    of the net whose weights are given and whose `input_threshold` the
    configuration states.

    `input_shift` > 0 holds the pixels in 8 - shift bits first (the control):
    the comparator then sees `x >> shift` against `threshold >> shift`."""
    _check_exact(weights)
    ws = [np.asarray(w, np.float32) for w in weights]
    thr = int(config["input_threshold"]) >> input_shift
    out = []
    for i in range(0, x_uint8.shape[0], ROW_BLOCK):
        x = x_uint8[i:i + ROW_BLOCK]
        if input_shift:
            x = x >> input_shift
        a = (x > thr).astype(np.float32)
        for w in ws[:-1]:
            a = (a @ w > 0).astype(np.float32)
        out.append(a @ ws[-1])
    return np.concatenate(out) if out else np.zeros((0, ws[-1].shape[1]), np.float32)


def widest_gap(ref_logits: np.ndarray, served: np.ndarray) -> float:
    """Widest gap by which a served class's reference logit lies below the
    reference's best logit of that row. 0 means every answer is a best class.
    A class outside the logits' range reads as infinitely wrong."""
    if served.size == 0:
        return 0.0
    served = np.asarray(served).astype(np.int64)
    n_classes = ref_logits.shape[1]
    bad = (served < 0) | (served >= n_classes)
    if bad.any():
        return float("inf")
    best = ref_logits.max(axis=1)
    got = np.take_along_axis(ref_logits, served[:, None], axis=1)[:, 0]
    return float((best - got).max())
