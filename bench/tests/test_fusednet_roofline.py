"""The reader of `fusednet_roofline` prices each launch by the rows it ran:
slot rounds a launch (the change of `netgen_slot_rounds_total` over the
change of `netgen_kernel_launches_total`) times `slot_capacity`, and one
round a launch where the rounds counter did not move. On the recorded trace
of eleven megakernel launches."""
from pathlib import Path

import pytest

from bench import trace, work
from bench.run import RunData, load_file
from bench.tests.conftest import dense_chain

DATA = Path(__file__).resolve().parent / "data"
READER = Path(__file__).resolve().parents[1] / "metrics" / "fusednet_roofline.py"
V5E = work.peaks("TPU v5 lite")
LAUNCHES = ("counter", "netgen_kernel_launches_total", (("form", "fusednet"),))
ROUNDS = ("counter", "netgen_slot_rounds_total", (("server", "server-1"),))
PAPER = [784, 500, 10]
# wide enough, with a short input row, that operations bound one round of 256
# rows as well as 32: the least time is then linear in the rows
WIDE = [64, 4096, 4096, 10]


@pytest.fixture(scope="module")
def recorded():
    return trace.read(DATA / "paper-predict-many.xplane.pb")


def _read(recorded, widths, versions, before, after):
    run = RunData("offline", 2.0, recorded, before, after, [], None, 1_000_000,
                  *dense_chain(widths), versions, V5E)
    return load_file(READER, "t_fusednet_roofline").read(run)


def _device_s(recorded):
    return sum(d for _, _, d in trace.kernel_events(recorded, "binary_forward_planes")) * 1e-9


@pytest.mark.parametrize("widths, versions", [(PAPER, 1), (PAPER, 2), (WIDE, 1)],
                         ids=["paper", "paper-stacked", "wide"])
def test_a_launch_is_priced_at_the_rows_it_ran(recorded, widths, versions):
    old = _read(recorded, widths, versions, {}, {})   # no rounds counter: a round a launch
    one = _read(recorded, widths, versions, {LAUNCHES: 1, ROUNDS: 1},
                {LAUNCHES: 12, ROUNDS: 12})
    many = _read(recorded, widths, versions, {LAUNCHES: 1, ROUNDS: 32},
                 {LAUNCHES: 12, ROUNDS: 384})
    least = {rows: work.min_seconds(widths, rows, versions, V5E)[0] for rows in (256, 8192)}
    assert old == one == pytest.approx(100 * 11 * least[256] / _device_s(recorded), rel=1e-12)
    assert many == pytest.approx(100 * 11 * least[8192] / _device_s(recorded), rel=1e-12)
    assert many / one == pytest.approx(least[8192] / least[256], rel=1e-12)


def test_32_rounds_a_launch_read_32_times_one_round_where_operations_bound_both(recorded):
    assert work.min_seconds(WIDE, 256, 1, V5E)[1] == "int8"
    one = _read(recorded, WIDE, 1, {}, {LAUNCHES: 11, ROUNDS: 11})
    many = _read(recorded, WIDE, 1, {}, {LAUNCHES: 11, ROUNDS: 352})
    assert many == pytest.approx(32 * one, rel=1e-12)


def test_weights_read_once_a_launch_make_the_paper_net_read_less_than_32_times(recorded):
    # one round of the paper net is bound by its bytes, 397,000 of them
    # weights; 32 rounds in one launch read them once, so 32 rounds cost
    # 22.6 times one round, not 32 times
    assert work.min_seconds(PAPER, 256, 2, V5E)[1] == "hbm"
    assert work.min_seconds(PAPER, 8192, 2, V5E)[1] == "int8"
    one = _read(recorded, PAPER, 2, {}, {LAUNCHES: 11, ROUNDS: 11})
    many = _read(recorded, PAPER, 2, {}, {LAUNCHES: 11, ROUNDS: 352})
    assert many / one == pytest.approx(2 * 2 * 8192 * 397_000 / 393e12
                                       / (2 * (256 * 788 + 397_000) / 819e9), rel=1e-12)
    assert 22 < many / one < 23
