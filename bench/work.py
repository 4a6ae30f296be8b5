"""Work of one forward pass of a dense step-activation net, from its widths.

The count is the net's own multiply-accumulates at its published widths,
whatever datapath computes them (popcount planes, MXU or dense), so a later
datapath is judged on the same work:

    ops   = 2 * rows * sum(K_l * N_l)              integer operations
    bytes = rows * (n_in + 4) + sum(K_l * N_l)     uint8 rows in, int32 out,
                                                   weights once at one byte

A stacked call of M versions is M times both.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def macs(widths) -> int:
    """Multiply-accumulates of one row through layers widths[0] -> ... -> widths[-1]."""
    return sum(int(k) * int(n) for k, n in zip(widths[:-1], widths[1:]))


def ops(widths, rows: int, versions: int = 1) -> int:
    return 2 * versions * int(rows) * macs(widths)


def bytes_moved(widths, rows: int, versions: int = 1) -> int:
    return versions * (int(rows) * (int(widths[0]) + 4) + macs(widths))


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(n_ops: int, n_bytes: int, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for `n_ops` int8 operations over
    `n_bytes` of HBM traffic, and which bound sets it."""
    t_ops = n_ops / peak["int8_ops_per_s"]
    t_mem = n_bytes / peak["hbm_bytes_per_s"]
    return (t_ops, "int8") if t_ops >= t_mem else (t_mem, "hbm")


def min_seconds(widths, rows: int, versions: int, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for one call, and which bound sets it."""
    return least_seconds(ops(widths, rows, versions), bytes_moved(widths, rows, versions), peak)
