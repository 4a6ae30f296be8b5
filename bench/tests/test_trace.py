"""The trace reduction, the work count and the roofline and MFU arithmetic,
on a small trace recorded on a TPU v5e (three `predict_many` calls of the
paper net, 1024 rows each, under a `bench.window` annotation)."""
from pathlib import Path

import pytest

from bench import trace, work
from bench.run import RunData
from bench.tests.conftest import dense_chain

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "paper-predict-many.xplane.pb"
PAPER = [784, 500, 10]
LFC = [784, 1024, 1024, 1024, 10]
V5E = work.peaks("TPU v5 lite")


def test_merge_is_the_union_of_intervals():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)]) == [(0, 3), (5, 9), (10, 11)]
    assert trace.covered(trace.merge([(0, 4), (2, 6), (8, 9)])) == 7


def test_idle_gaps_and_idle_share_on_a_hand_made_trace():
    tr = trace.Trace(window=(0, 100), ops=[], busy=[(10, 30), (50, 60)], busy_s=30e-9, host=[])
    assert trace.idle_gaps(tr) == [(0, 10), (30, 50), (60, 100)]
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.idle_share == pytest.approx(0.7)


def test_op_name_is_the_hlo_instruction_name():
    ev = "%binary_forward_planes.1 = s32[256,1]{1,0} custom-call(u8[256,784] %copy.1)"
    assert trace.op_name(ev) == "binary_forward_planes.1"


@pytest.fixture(scope="module")
def recorded():
    return trace.read(RECORDED)


def test_recorded_trace_window_and_busy_union(recorded):
    assert recorded.window_s == pytest.approx(0.016411409)
    assert recorded.busy == trace.merge(recorded.busy)
    assert recorded.busy_s == pytest.approx(trace.covered(recorded.busy) * 1e-9)
    assert recorded.busy_s == pytest.approx(0.000548308)
    assert recorded.idle_share == pytest.approx(1 - 0.000548308 / 0.016411409)


def test_recorded_trace_kernel_events(recorded):
    events = trace.kernel_events(recorded, "binary_forward_planes")
    assert len(events) == 11
    assert all(name == "binary_forward_planes.1" for name, *_ in events)
    assert trace.kernel_events(recorded, "binary_forward") == []
    assert trace.top_ops(recorded, 1)[0][0] == "binary_forward_planes.1"


def test_recorded_trace_gaps_are_labelled_by_the_covering_span(recorded):
    gaps = trace.idle_gaps(recorded)
    assert sum(e - s for s, e in gaps) * 1e-9 == pytest.approx(
        recorded.window_s - recorded.busy_s)
    lo = recorded.window[0]
    spans = [("netgen.dispatch", lo, lo + 1e9, "engine"), ("bench.submit", lo, lo + 1e9, "main")]
    labels = trace.label_gaps(gaps, spans, k=3)
    assert [name for name, _ in labels] == ["bench.submit|netgen.dispatch"] * 3
    assert labels[0][1] >= labels[1][1] >= labels[2][1]


def test_work_at_published_widths():
    assert work.macs(PAPER) == 784 * 500 + 500 * 10 == 397_000
    assert work.macs(LFC) == 784 * 1024 + 2 * 1024 * 1024 + 1024 * 10 == 2_910_208
    assert work.ops(PAPER, 256) == 2 * 256 * 397_000
    assert work.bytes_moved(PAPER, 256) == 256 * 788 + 397_000
    assert work.ops(LFC, 256, versions=2) == 2 * 2 * 256 * 2_910_208
    assert work.bytes_moved(LFC, 256, versions=2) == 2 * (256 * 788 + 2_910_208)


def test_roofline_and_mfu_against_hand_computed_values(recorded):
    # paper net, 256 rows a launch: 203,264,000 ops (0.517 us at 393 TOP/s)
    # and 598,728 bytes (0.731 us at 819 GB/s): the bytes bound it
    least, bound = work.min_seconds(PAPER, 256, 1, V5E)
    assert bound == "hbm"
    assert least == pytest.approx(598_728 / 819e9)
    run = RunData("offline", 2.0, recorded, {}, {}, [], None, 1_000_000, *dense_chain(PAPER),
                  1, V5E)
    from bench.run import load_file

    metrics = Path(__file__).resolve().parents[1] / "metrics"
    roof = load_file(metrics / "fusednet_roofline.py", "t_roof").read(run)
    device_s = sum(d for _, _, d in trace.kernel_events(recorded, "binary_forward_planes")) * 1e-9
    assert roof == pytest.approx(100 * 11 * 598_728 / 819e9 / device_s)
    assert 0 < roof < 100
    mfu = load_file(metrics / "step_mfu_pct.py", "t_mfu").read(run)
    assert mfu == pytest.approx(100 * 2 * 397_000 * 1_000_000 / 2.0 / 393e12)
    us = load_file(metrics / "kernel_us_per_launch.py", "t_us").read(run)
    assert us == pytest.approx(device_s / 11 * 1e6)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")
