"""The reader of `rounds_per_launch`: slot rounds over kernel launches in the
window, and nothing for a program without the slot-round counter."""
from pathlib import Path

import pytest

from bench.run import RunData, load_file
from bench.tests.conftest import dense_chain

READER = Path(__file__).resolve().parents[1] / "metrics" / "rounds_per_launch.py"
LAUNCHES = ("counter", "netgen_kernel_launches_total", (("form", "fusednet"),))
ROUNDS = ("counter", "netgen_slot_rounds_total", (("server", "server-1"),))


def _run(before, after):
    return RunData("offline", 2.0, None, before, after, [], None, 8192 * 10,
                   *dense_chain([784, 1024, 1024, 1024, 10]), 1, None)


@pytest.mark.parametrize("before, after, value", [
    # ten calls of 32 rounds in one launch each, after a warm-up call
    ({LAUNCHES: 1, ROUNDS: 32}, {LAUNCHES: 11, ROUNDS: 352}, 32.0),
    # a program without the counter: one launch a round, nothing to read
    ({LAUNCHES: 1}, {LAUNCHES: 321}, None),
    # a target that counts no kernel launches
    ({ROUNDS: 32}, {ROUNDS: 352}, None),
], ids=["multi-round", "no-counter", "no-launches"])
def test_rounds_per_launch_reads_rounds_over_launches(before, after, value):
    reader = load_file(READER, "t_rounds_per_launch")
    assert reader.read(_run(before, after)) == value
