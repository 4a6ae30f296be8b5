"""The readers of the program's own spans on the profiler's clock
(`bench/program_trace.py` and the `round_*_us` and `engine_resolve_us`
metrics): the arithmetic on hand-made events, a program without such spans,
and the metrics of traced runs at a test size on the CPU."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import program_trace, trace, work
from bench.run import RunData, load_file
from bench.tests.conftest import (TINY_OFFLINE, TINY_ONLINE, TINY_STACKED, dense_chain,
                                  run_tiny)

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "paper-predict-many.xplane.pb"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
NEW = ("round_stage_us", "round_launch_us", "round_sync_us", "engine_resolve_us")
V5E = work.peaks("TPU v5 lite")


def _reader(stem):
    return load_file(METRICS / f"{stem}.py", "t_" + stem)


def test_sync_subtracts_exactly_the_busy_time_inside_each_fetch():
    tr = trace.Trace(window=(0, 1000), ops=[], busy=[(10, 30), (95, 140), (150, 160), (400, 500)],
                     busy_s=175e-9, host=[])
    evs = [("netgen.round.fetch", 100, 100, "main"),   # 40 + 10 busy inside: waits 50
           ("netgen.round.launch", 0, 100, "main"),
           ("netgen.round.fetch", 300, 50, "main"),    # no busy inside: waits 50
           ("netgen.round.fetch", 450, 100, "main")]   # 50 busy inside: waits 50
    assert program_trace.waits_us(evs, "netgen.round.fetch", tr.busy) == pytest.approx(0.05)
    assert program_trace.waits_us(evs, "netgen.round.fetch", []) == pytest.approx(250 / 3 * 1e-3)
    assert program_trace.waits_us(evs, "netgen.engine.resolve", tr.busy) is None


def test_mean_duration_of_the_named_events():
    evs = [("netgen.round.stage", 0, 2000, "a"), ("netgen.round.stage", 5000, 4000, "a"),
           ("netgen.kernel", 0, 9000, "a")]
    assert program_trace.mean_us(evs, "netgen.round.stage") == pytest.approx(3.0)
    assert program_trace.mean_us(evs, "netgen.round.launch") is None


def test_program_without_annotated_spans_reads_nothing(tmp_path):
    # the committed trace predates the annotations: it holds no netgen.* event
    window, evs = program_trace._scan(RECORDED)
    recorded = trace.read(RECORDED)
    assert window == recorded.window and evs == []
    run = RunData("offline", 2.0, recorded, {}, {}, [], None, 1, *dense_chain([784, 500, 10]),
                  1, V5E)
    assert program_trace.events(run, tmp_path) == []
    untraced = RunData("offline", 2.0, None, {}, {}, [], None, 1, *dense_chain([784, 500, 10]),
                       1, V5E)
    for stem in NEW:
        assert _reader(stem).read(run) is None
        assert _reader(stem).read(untraced) is None


@pytest.mark.parametrize("stem, value", [
    ("device_idle_pct", 96.65898278447634),
    ("kernel_us_per_launch", 48.05045454545454),
    ("fusednet_roofline", 1.5214166566438327),
    ("step_mfu_pct", 0.1010178117048346),
])
def test_existing_readers_keep_their_values_on_the_recorded_trace(stem, value):
    run = RunData("offline", 2.0, trace.read(RECORDED), {}, {}, [], None, 1_000_000,
                  *dense_chain([784, 500, 10]), 1, V5E)
    assert _reader(stem).read(run) == pytest.approx(value, rel=1e-12)


def test_server_host_reader_keeps_its_value_on_the_recorded_trace():
    # three single-version dispatches of 5 ms holding 12 launches, the registry's way
    after = {("counter", "netgen_dispatch_total", (("path", "single"),)): 3,
             ("counter", "netgen_kernel_launches_total", (("form", "fusednet"),)): 12}
    spans = [SimpleNamespace(name="netgen.dispatch", duration_s=0.005)] * 3
    run = RunData("offline", 2.0, trace.read(RECORDED), {}, after, spans, None, 1_000_000,
                  *dense_chain([784, 500, 10]), 1, V5E)
    got = _reader("server_host_us_per_round").read(run)
    assert got == pytest.approx((0.015 - 0.000548308) / 12 * 1e6, rel=1e-9)


@pytest.mark.parametrize("cell, kind", [(TINY_OFFLINE, "offline"), (TINY_STACKED, "offline"),
                                        (TINY_ONLINE, "online")])
def test_traced_runs_report_the_split_of_the_round(tiny_root, cell, kind):
    res = run_tiny(tiny_root, cell, seconds=0.5, trace=1)
    assert res["correct"]
    names = {f"{stem}.{kind}" for stem in NEW[:3]}
    if kind == "online":
        names.add("engine_resolve_us")
    assert names <= set(res["metrics"])
    for name in names:
        assert res["metrics"][name]["unit"] == "us"
        assert res["metrics"][name]["value"] > 0
    if kind == "offline":
        assert "engine_resolve_us" not in res["metrics"]
