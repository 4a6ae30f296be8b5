"""Mean host time of staging one slot round, from the trace: the duration of
the program's `netgen.round.stage` span (padding rows into the slot block).
BENCHMARK.json splits it by cell kind (`.online`, `.offline`), since each kind
moves its own metric."""
from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    return program_trace.mean_us(program_trace.events(run, ROOT), "netgen.round.stage")
