"""The reader of `launches_ahead_share`: launches dispatched ahead over kernel
launches in the window, and nothing without launches or without the counter."""
from pathlib import Path

import pytest

from bench.run import RunData, load_file
from bench.tests.conftest import dense_chain

READER = Path(__file__).resolve().parents[1] / "metrics" / "launches_ahead_share.py"
LAUNCHES = ("counter", "netgen_kernel_launches_total", (("form", "fusednet"),))
AHEAD = ("counter", "netgen_launches_ahead_total", (("server", "server-1"),))


def _run(before, after):
    return RunData("offline", 2.0, None, before, after, [], None, 8192,
                   *dense_chain([784, 1024, 1024, 1024, 10]), 1, None)


@pytest.mark.parametrize("before, after, value", [
    # one call of four launches, three of them dispatched ahead
    ({LAUNCHES: 4, AHEAD: 3}, {LAUNCHES: 8, AHEAD: 6}, 0.75),
    # the counter is there, but no launch was counted
    ({LAUNCHES: 4, AHEAD: 3}, {LAUNCHES: 4, AHEAD: 3}, None),
    # one-launch calls: the counter stays at 0
    ({LAUNCHES: 1, AHEAD: 0}, {LAUNCHES: 11, AHEAD: 0}, 0.0),
    # a program without the counter
    ({LAUNCHES: 1}, {LAUNCHES: 11}, None),
], ids=["four-launch-call", "no-launches", "one-launch-calls", "no-counter"])
def test_launches_ahead_share_reads_ahead_over_launches(before, after, value):
    reader = load_file(READER, "t_launches_ahead_share")
    assert reader.read(_run(before, after)) == value
