"""Mean host time of launching one slot round, from the trace: the duration of
the program's `netgen.round.launch` span (the predictor call until it returns:
argument transfer and enqueue). BENCHMARK.json splits it by cell kind
(`.online`, `.offline`), since each kind moves its own metric."""
from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    return program_trace.mean_us(program_trace.events(run, ROOT), "netgen.round.launch")
