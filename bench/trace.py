"""Reduce a `jax.profiler` trace (`.xplane.pb`) to device busy time, kernel
events and idle gaps, on the trace's own clock (nanoseconds).

The window is the host annotation `bench.window` that the harness wraps
around its timed loop. Device activity is the union of the intervals of the
events on each TPU plane's `XLA Ops` line, clipped to the window; busy
seconds are averaged over the chips that ran something. Ops are named by
their HLO instruction name (`binary_forward_planes.1`).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    window: tuple            # (start_ns, end_ns) of `bench.window`
    ops: list                # chip 0's device ops: (name, start_ns, dur_ns)
    busy: list               # chip 0's merged busy intervals [(start, end)]
    busy_s: float            # busy seconds, averaged over chips with ops
    host: list               # harness annotations: (name, start_ns, dur_ns, thread)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def merge(intervals) -> list:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def covered(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _clip(events, lo, hi) -> list:
    out = []
    for name, s, d, *rest in events:
        e = s + d
        if e <= lo or s >= hi:
            continue
        s2, e2 = max(s, lo), min(e, hi)
        out.append((name, s2, e2 - s2, *rest))
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event: the trace names an op
    by its whole HLO text, `%binary_forward_planes.1 = s32[256,1] custom-call(...)`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path) -> Trace:
    """Reduce one trace file. Raises if it holds no `bench.window`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host, chips = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [(op_name(ev.name), ev.start_ns, ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            chips.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns, ev.duration_ns, line.name)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith("bench.")]
    windows = [(s, s + d) for name, s, d, _ in host if name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW} annotation")
    lo, hi = windows[0]
    chips.sort(key=lambda c: int(c[0][len(DEVICE_PREFIX):].split(" ")[0] or 0))
    per_chip = []
    for _, ops in chips:
        ops = _clip(ops, lo, hi)
        per_chip.append((ops, merge((s, s + d) for _, s, d in ops)))
    active = [busy for ops, busy in per_chip if ops]
    busy_s = (sum(covered(b) for b in active) / len(active) * 1e-9) if active else 0.0
    ops0, busy0 = per_chip[0] if per_chip else ([], [])
    return Trace(window=(lo, hi), ops=ops0, busy=busy0, busy_s=busy_s,
                 host=_clip(host, lo, hi))


def kernel_events(trace: Trace, name: str) -> list:
    """Chip 0's device events whose name is `name` or starts with `name.`."""
    return [ev for ev in trace.ops if ev[0] == name or ev[0].startswith(name + ".")]


def top_ops(trace: Trace, k: int = 10) -> list:
    """The k device ops that took the most time: [[name, seconds], ...]."""
    tot: dict = {}
    for name, _, d in trace.ops:
        tot[name] = tot.get(name, 0) + d
    return [[n, t * 1e-9] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace) -> list:
    """Chip 0's idle intervals inside the window: [(start_ns, end_ns)]."""
    gaps, t = [], trace.window[0]
    for s, e in trace.busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if trace.window[1] > t:
        gaps.append((t, trace.window[1]))
    return gaps


def label_gaps(gaps, spans, k: int = 10) -> list:
    """The k longest gaps, each named by what the host was doing at its
    middle: per thread, the innermost span (name, start_ns, end_ns, thread)
    that covers it, joined by `|`. [[name, seconds], ...]"""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        inner: dict = {}
        for sp in spans:
            if sp[0] != WINDOW and sp[1] <= mid <= sp[2]:
                cur = inner.get(sp[3])
                if cur is None or sp[2] - sp[1] < cur[2] - cur[1]:
                    inner[sp[3]] = sp
        name = "|".join(sorted(sp[0] for sp in inner.values())) or "no span"
        out.append([name, (e - s) * 1e-9])
    return out
