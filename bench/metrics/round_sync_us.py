"""Mean host wait beyond the kernel in fetching one slot round's result, from
the trace: each `netgen.round.fetch` span's duration less the device's busy
time inside it (the union of `XLA Ops` clipped to the span), averaged over
fetches. BENCHMARK.json splits it by cell kind (`.online`, `.offline`), since
each kind moves its own metric."""
from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    if run.trace is None:
        return None
    return program_trace.waits_us(program_trace.events(run, ROOT), "netgen.round.fetch",
                                  run.trace.busy)
