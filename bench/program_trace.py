"""The program's own spans, read from the profiler's trace.

While the program's telemetry is on, each `netgen.*` span is also a
`jax.profiler.TraceAnnotation`, so the traced run's `.xplane.pb` holds it as
a host event on the profiler's clock, beside the device's ops. `bench/trace.py`
keeps only the harness's `bench.*` events; this module reads the `netgen.*`
ones from the trace file whose `bench.window` is the run's, and reduces them
for the per-layer readers. A program whose spans are not annotations leaves
no such event, and the readers then return None.
"""
from __future__ import annotations

import bisect
from pathlib import Path

from bench import trace as xtrace

TRACE_DIR = ".bench/trace"   # where bench/run.py has the profiler write
PREFIX = "netgen."


def _scan(path) -> tuple:
    """(window, events) of one trace file: the first `bench.window`, as
    `bench/trace.py` takes it, and every host event named `netgen.*` as
    (name, start_ns, dur_ns, thread)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    window, events = None, []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    events.append((ev.name, ev.start_ns, ev.duration_ns, line.name))
                elif window is None and ev.name == xtrace.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return window, events


def events(run, root) -> list:
    """The program's host events that lie wholly inside the run's traced
    window, (name, start_ns, dur_ns, thread); [] where no
    trace file under `root` holds that window. Kept on `run`, so the
    readers of one run read the file once."""
    if run.trace is None:
        return []
    kept = getattr(run, "program_events", None)
    if kept is None:
        kept = []
        for path in sorted(Path(root, TRACE_DIR).rglob("*.xplane.pb")):
            window, evs = _scan(path)
            if window == tuple(run.trace.window):
                lo, hi = window
                kept = [ev for ev in evs if lo <= ev[1] and ev[1] + ev[2] <= hi]
                break
        run.program_events = kept
    return kept


def named(evs, name: str) -> list:
    return [ev for ev in evs if ev[0] == name]


def mean_us(evs, name: str) -> float | None:
    """Mean duration of the events called `name`, in microseconds."""
    durs = [d for _, _, d, *_ in named(evs, name)]
    return sum(durs) / len(durs) * 1e-3 if durs else None


def _inside(busy, starts, start, end) -> float:
    """Nanoseconds of the sorted, disjoint `busy` intervals (whose starts
    are `starts`) inside [start, end)."""
    i = max(bisect.bisect_right(starts, start) - 1, 0)
    total = 0.0
    while i < len(busy) and busy[i][0] < end:
        total += max(0.0, min(end, busy[i][1]) - max(start, busy[i][0]))
        i += 1
    return total


def waits_us(evs, name: str, busy) -> float | None:
    """Mean over the events called `name` of their duration less the
    device's busy time inside them, in microseconds: the host waiting
    beyond the device's work. `busy` is sorted and disjoint (`Trace.busy`)."""
    spans = named(evs, name)
    if not spans:
        return None
    starts = [s for s, _ in busy]
    waits = [d - _inside(busy, starts, s, s + d) for _, s, d, *_ in spans]
    return sum(waits) / len(waits) * 1e-3
