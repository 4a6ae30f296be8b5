"""Mean host time of resolving one engine batch's futures, from the trace: the
duration of the program's `netgen.engine.resolve` span on the batcher thread."""
from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    return program_trace.mean_us(program_trace.events(run, ROOT), "netgen.engine.resolve")
