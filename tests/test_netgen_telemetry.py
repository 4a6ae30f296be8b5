"""Telemetry subsystem tests (ISSUE 6): metric primitives (exact
percentiles, atomic counters under thread hammer), span nesting and
JSONL export, the instrumented compile/store/serve lifecycle, the
concurrent-serving histogram/occupancy/parentage invariants, Session
executor lifecycle (finalizer + context manager), and the
`benchmarks/check_trace.py` CI gate functions."""
import gc
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro import netgen
from repro.netgen import telemetry

from _netgen_helpers import images, random_net

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "benchmarks"))
from check_trace import (  # noqa: E402
    check_metrics, check_rounds, check_spans, check_trace_dir,
    parse_prometheus,
)

SIZES = (12, 9, 4)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts with zeroed metrics and no retained spans, and
    leaves tracing disabled for the rest of the suite."""
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _net(seed: int):
    return random_net(seed, SIZES, lo=-5, hi=5)


def _x(seed: int, b: int) -> np.ndarray:
    return images(seed, b, SIZES[0], salt=77)


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------

def test_histogram_exact_percentiles():
    h = telemetry.Histogram("h", {})
    for v in range(1, 101):                  # 1..100, shuffled in
        h.observe(((v * 37) % 100) + 1)
    assert h.count == 100
    assert h.p50 == 50
    assert h.p95 == 95
    assert h.p99 == 99
    assert h.percentile(1.0) == 100
    assert h.mean == pytest.approx(50.5)
    empty = telemetry.Histogram("e", {})
    assert empty.p50 == 0.0 and empty.count == 0
    with pytest.raises(ValueError):
        h.percentile(0.0)


def test_histogram_window_bounds_memory():
    h = telemetry.Histogram("h", {}, window=8)
    for v in range(100):
        h.observe(v)
    assert h.count == 100                    # all-time
    assert h.sum == sum(range(100))
    assert h.percentile(1.0) == 99           # window keeps the newest 8
    assert h.p50 == 95                       # nearest-rank over 92..99


def test_counter_thread_hammer():
    c = telemetry.Counter("c", {})
    n_threads, per_thread = 8, 5000

    def work():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread


def test_registry_get_or_create_and_labels():
    reg = telemetry.Registry()
    a = reg.counter("x_total", k="1")
    b = reg.counter("x_total", k="1")
    c = reg.counter("x_total", k="2")
    assert a is b and a is not c
    a.inc(3)
    assert reg.counter("x_total", k="1").value == 3
    # reset zeroes in place: live handles stay valid
    reg.reset()
    assert a.value == 0
    a.inc()
    assert reg.counter("x_total", k="1").value == 1


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_spans_disabled_are_noop():
    reg = telemetry.Registry()
    with reg.span("outer", a=1) as sp:
        sp.set_attr("b", 2)
    assert reg.spans() == []


def test_span_nesting_and_jsonl_export(tmp_path):
    reg = telemetry.Registry()
    reg.enabled = True
    with reg.span("outer", kind="test"):
        with reg.span("inner"):
            pass
        with reg.span("inner"):
            pass
    spans = reg.spans()
    assert [s.name for s in spans] == ["inner", "inner", "outer"]
    outer = spans[-1]
    assert outer.parent_id is None
    for inner in spans[:2]:
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id
        assert inner.duration_s >= 0
    path = tmp_path / "t.jsonl"
    n = reg.export_jsonl(path)
    assert n == 3
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert {rec["name"] for rec in lines} == {"outer", "inner"}
    assert check_spans(lines, require=("outer", "inner")) == []


def test_span_records_error_type():
    reg = telemetry.Registry()
    reg.enabled = True
    with pytest.raises(RuntimeError):
        with reg.span("boom"):
            raise RuntimeError("x")
    (rec,) = reg.spans()
    assert rec.error == "RuntimeError"


def test_threads_root_their_own_traces():
    reg = telemetry.Registry()
    reg.enabled = True
    def worker():
        with reg.span("worker"):
            pass

    with reg.span("main-root"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    worker_rec = next(r for r in reg.spans() if r.name == "worker")
    assert worker_rec.parent_id is None      # not adopted by main's stack


# ---------------------------------------------------------------------------
# Instrumented lifecycle: compile -> store -> serve
# ---------------------------------------------------------------------------

def test_compile_trace_nests_pipeline_and_passes():
    telemetry.enable()
    netgen.Session(capacity=4).compile(_net(0), target="jnp")
    spans = {r.span_id: r for r in telemetry.get_registry().spans()}
    by_name = {}
    for r in spans.values():
        by_name.setdefault(r.name, []).append(r)
    compile_span = by_name["netgen.compile"][0]
    assert compile_span.attrs["target"] == "jnp"
    for child in ("netgen.lower", "netgen.pipeline", "netgen.backend"):
        (rec,) = by_name[child]
        assert rec.parent_id == compile_span.span_id
    pipeline_span = by_name["netgen.pipeline"][0]
    passes = by_name["netgen.pass"]
    assert len(passes) == 2                  # default pipeline: zeros,prune
    for p in passes:
        assert p.parent_id == pipeline_span.span_id
        assert p.attrs["terms_after"] <= p.attrs["terms_before"]


def test_store_and_cache_counters_route_through_registry(tmp_path):
    store = netgen.ArtifactStore(tmp_path / "store")
    s1 = netgen.Session(store=store, capacity=4)
    s1.compile(_net(1), target="jnp")
    assert store.stats.saves == 1
    s2 = netgen.Session(store=store, capacity=4)   # fresh memory tier
    s2.compile(_net(1), target="jnp")
    st = s2.stats()
    assert (st.compiles, st.store_hits) == (0, 1)
    assert store.stats.loads == 1
    assert store.stats.load_seconds > 0
    # the prometheus exposition carries the same counters
    prom = telemetry.prometheus()
    assert "netgen_store_saves_total" in prom
    assert "netgen_cache_store_hits_total" in prom
    assert check_metrics(parse_prometheus(prom)) == []


def test_compile_cache_concurrent_hammer():
    """Satellite 2: identical concurrent compiles race safely — counters
    add up exactly and only one compile happens."""
    cache = netgen.CompileCache(capacity=8)
    net = _net(2)
    n_threads, per_thread = 8, 10
    errors = []

    def work():
        try:
            for _ in range(per_thread):
                cache.get_or_compile(net)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    st = cache.stats()
    assert st.hits + st.misses == n_threads * per_thread
    assert st.compiles == 1
    assert st.misses == st.compiles + st.store_hits


def test_tuner_stats_snapshot_and_search_span():
    telemetry.enable()
    tuner = netgen.KernelTuner()
    calls = []

    def measure(params):
        calls.append(dict(params))
        return 0.001 * (1 + params["bm"])

    key_fields = {"target": "t", "device_kind": "cpu", "shape": [4, 4]}
    best = tuner.get_or_tune(key_fields, [{"bm": 0}, {"bm": 1}], measure)
    assert best == {"bm": 0}
    st = tuner.stats
    assert (st.tunes, st.measurements, st.hits) == (1, 2, 0)
    assert st.measure_seconds > 0
    best2 = tuner.get_or_tune(key_fields, [{"bm": 0}, {"bm": 1}], measure)
    assert best2 == best and tuner.stats.hits == 1
    (rec,) = [r for r in telemetry.get_registry().spans()
              if r.name == "netgen.tune.search"]
    assert rec.attrs["candidates"] == 2
    assert rec.attrs["winner"] == {"bm": 0}


# ---------------------------------------------------------------------------
# Concurrent serving invariants (satellite 3)
# ---------------------------------------------------------------------------

def _server_with(nets, **kw):
    server = netgen.NetServer(cache=netgen.CompileCache(capacity=8),
                              slot_capacity=8, warmup=False, **kw)
    for i, net in enumerate(nets):
        server.register(f"v{i}", net)
    return server


def _hammer_predict_many(server, reqs, n_threads, per_thread):
    errors = []

    def work():
        try:
            for _ in range(per_thread):
                server.predict_many(reqs)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors


def _assert_serving_invariants(server, versions, n_requests):
    reg = telemetry.get_registry()
    for v in versions:
        h = reg.histogram("netgen_predict_latency_seconds",
                          server=server._scope, version=v)
        assert h.count == n_requests, (v, h.count)
        assert h.p50 <= h.p99
    occ = reg.histogram("netgen_slot_occupancy", server=server._scope)
    assert occ.count > 0
    assert 0.0 < occ.percentile(1.0) <= 1.0
    assert 0.0 < occ.percentile(0.01) <= 1.0
    # span parentage: every netgen.kernel has a netgen.dispatch parent
    spans = {r.span_id: r for r in reg.spans()}
    kernels = [r for r in spans.values() if r.name == "netgen.kernel"]
    dispatches = [r for r in spans.values() if r.name == "netgen.dispatch"]
    assert kernels and dispatches
    for k in kernels:
        parent = spans.get(k.parent_id)
        assert parent is not None, "orphan kernel span"
        assert parent.name == "netgen.dispatch"
    assert check_spans(
        [r.as_dict() for r in spans.values()],
        require=("netgen.dispatch", "netgen.kernel")) == []


def test_concurrent_predict_many_stacked():
    telemetry.enable()
    server = _server_with([_net(3), _net(4)])
    reqs = {"v0": _x(0, 13), "v1": _x(1, 13)}
    n_threads, per_thread = 8, 5
    _hammer_predict_many(server, reqs, n_threads, per_thread)
    n = n_threads * per_thread
    assert server.dispatch_counts["stacked"] == n
    _assert_serving_invariants(server, ("v0", "v1"), n)


def test_concurrent_predict_many_fallback():
    telemetry.enable()
    # different topology -> stack-incompatible -> fallback dispatch
    deep = random_net(5, (12, 10, 6, 4), lo=-5, hi=5)
    server = _server_with([_net(3)])
    server.register("deep", deep)
    reqs = {"v0": _x(0, 13), "deep": _x(2, 13)}
    n_threads, per_thread = 8, 5
    _hammer_predict_many(server, reqs, n_threads, per_thread)
    n = n_threads * per_thread
    assert server.dispatch_counts["fallback"] == n
    assert server.dispatch_counts["stacked"] == 0
    _assert_serving_invariants(server, ("v0", "deep"), n)


# ---------------------------------------------------------------------------
# Spans on the profiler's clock: the slot round and the engine's cycle
# ---------------------------------------------------------------------------

ROUND_PHASES = ("netgen.round.stage", "netgen.round.launch",
                "netgen.round.fetch")


def _profiled_events(log_dir, work) -> list:
    """Run `work()` under `jax.profiler` and return the trace's host
    events named `netgen.*` as (name, start_ns, end_ns), in start order."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = log_dir.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return sorted(
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("netgen."))


def _names(events, name) -> list:
    return [ev for ev in events if ev[0] == name]


def _serve_rounds(case: str):
    """(work, launches): one predict_many served in `launches` launches
    of 2^k whole slot rounds."""
    if case == "single":
        server = _server_with([_net(3)])
        reqs = {"v0": _x(0, 20)}          # rounds 8 + 8 + 4: launches 2 + 1
        launches = 2
    else:
        server = _server_with([_net(3), _net(4)])
        reqs = {"v0": _x(0, 13), "v1": _x(1, 13)}  # 8 + 5 each: one launch
        launches = 1
    server.predict_many(reqs)                    # compile outside the trace
    return (lambda: server.predict_many(reqs)), launches


@pytest.mark.parametrize("case", ["single", "stacked"])
def test_slot_round_phases_are_profiler_events(tmp_path, case):
    """Each launch (one or more slot rounds) puts one stage, launch and
    fetch event in the profiler's trace; launch and fetch lie inside
    their launch's `netgen.kernel` event, stage before it."""
    work, launches = _serve_rounds(case)
    telemetry.enable()
    events = _profiled_events(tmp_path, work)
    kernels = _names(events, "netgen.kernel")
    assert len(kernels) == launches
    for name in ROUND_PHASES:
        assert len(_names(events, name)) == launches, name
    stages = _names(events, "netgen.round.stage")
    launches = _names(events, "netgen.round.launch")
    fetches = _names(events, "netgen.round.fetch")
    for stage, kernel, launch, fetch in zip(stages, kernels, launches,
                                            fetches):
        assert stage[2] <= kernel[1]
        assert kernel[1] <= launch[1] <= launch[2] <= fetch[1]
        assert fetch[2] <= kernel[2]
    assert len(_names(events, "netgen.dispatch")) == 1
    spans = [r.as_dict() for r in telemetry.get_registry().spans()]
    assert check_rounds(spans) == []
    assert check_spans(spans, require=("netgen.dispatch", "netgen.kernel")
                       + ROUND_PHASES) == []


def test_engine_batch_cycle_is_profiler_events(tmp_path):
    """Each engine batch puts one `netgen.engine.form`, one
    `netgen.engine.admit` and one `netgen.engine.resolve` event in the
    trace, beside its batch."""
    server = _server_with([_net(3)])
    server.predict_many({"v0": _x(0, 1)})
    telemetry.enable()
    reg = telemetry.get_registry()
    engine = netgen.ServingEngine(server, max_batch_delay=0.005)

    def work():
        for seed in range(3):
            futures = [engine.submit("v0", row) for row in _x(seed, 4)]
            assert all(f.result(timeout=30) >= 0 for f in futures)
        # the batcher records a resolve span after setting the futures
        deadline = time.time() + 10
        while time.time() < deadline and sum(
                r.name == "netgen.engine.resolve"
                for r in reg.spans()) < engine.stats().batches:
            time.sleep(0.001)

    try:
        events = _profiled_events(tmp_path, work)
    finally:
        engine.shutdown()
    batches = engine.stats().batches
    assert batches >= 3
    assert len(_names(events, "netgen.engine.admit")) == batches
    assert len(_names(events, "netgen.engine.batch")) == batches
    assert len(_names(events, "netgen.engine.resolve")) == batches
    assert len(_names(events, "netgen.engine.form")) == batches
    spans = [r.as_dict() for r in reg.spans()]
    assert check_spans(spans, require=("netgen.engine.form",
                                       "netgen.engine.admit",
                                       "netgen.engine.batch",
                                       "netgen.engine.resolve")) == []
    assert check_rounds(spans) == []


def test_tracing_off_puts_no_program_event_in_the_profile(tmp_path):
    work, _ = _serve_rounds("single")
    telemetry.disable()
    assert telemetry.span("netgen.round.stage") is telemetry._NULL_SPAN
    assert _profiled_events(tmp_path, work) == []
    assert telemetry.get_registry().spans() == []


def test_spans_record_without_jax_profiler(monkeypatch):
    """Where jax cannot be imported, `enable()` resolves a no-op
    annotation and spans still record."""
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    telemetry.enable()
    with telemetry.span("netgen.round.stage"):
        pass
    (rec,) = telemetry.get_registry().spans()
    assert rec.name == "netgen.round.stage" and rec.error is None


# ---------------------------------------------------------------------------
# Session executor lifecycle (satellite 1)
# ---------------------------------------------------------------------------

def _compile_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("netgen-compile")]


def test_session_context_manager_joins_executor():
    with netgen.Session(capacity=4) as session:
        art = session.compile_async(_net(6), target="jnp").result()
        assert art.kind == "callable"
        assert _compile_threads()
    assert not _compile_threads()
    session.shutdown()                       # idempotent


def test_dropped_session_leaks_no_threads():
    session = netgen.Session(capacity=4)
    session.compile_async(_net(7), target="jnp").result()
    assert _compile_threads()
    del session
    gc.collect()
    deadline = time.time() + 5.0
    while _compile_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert not _compile_threads(), "executor threads leaked after GC"


# ---------------------------------------------------------------------------
# Exporters + the acceptance lifecycle + the CI gate
# ---------------------------------------------------------------------------

def test_report_renders_metrics_and_spans():
    telemetry.enable()
    telemetry.counter("demo_total", kind="x").inc(2)
    with telemetry.span("demo.span"):
        telemetry.histogram("demo_seconds").observe(0.25)
    text = telemetry.report()
    assert 'demo_total{kind="x"}: 2' in text
    assert "histogram demo_seconds" in text
    assert "span      demo.span: n=1" in text


def test_prometheus_exposition_shape():
    telemetry.counter("demo_total", a="b").inc()
    telemetry.histogram("demo_seconds").observe(0.5)
    prom = telemetry.prometheus()
    assert "# TYPE demo_total counter" in prom
    assert '# TYPE demo_seconds summary' in prom
    assert 'demo_seconds{quantile="0.5"} 0.5' in prom
    assert "demo_seconds_count 1" in prom
    # label values are escaped
    telemetry.gauge("g", v='say "hi"\n').set(1)
    assert r'say \"hi\"\n' in telemetry.prometheus()


def test_acceptance_full_lifecycle(tmp_path):
    """ISSUE 6 acceptance: one compile + one predict_many round yields a
    JSONL trace nesting pipeline->passes and dispatch->kernel, a
    Prometheus exposition with compile/store-hit counters and a
    per-version latency histogram with p50/p99, and a report() with
    non-zero occupancy — and the CI gate passes on the directory."""
    telemetry.enable()
    store = netgen.ArtifactStore(tmp_path / "store")
    with netgen.Session(store=store, capacity=4) as session:
        server = netgen.NetServer(session=session, slot_capacity=8,
                                  warmup=False)
        server.register("v0", _net(8))
        server.register("v1", _net(9))
        out = server.predict_many({"v0": _x(3, 11), "v1": _x(4, 11)})
    assert set(out) == {"v0", "v1"}
    assert all(len(p) == 11 for p in out.values())

    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    n = telemetry.export_jsonl(trace_dir / "trace.jsonl")
    assert n > 0
    (trace_dir / "metrics.prom").write_text(telemetry.prometheus())

    spans = [json.loads(line) for line in
             (trace_dir / "trace.jsonl").read_text().splitlines()]
    by_id = {s["span_id"]: s for s in spans}
    pass_spans = [s for s in spans if s["name"] == "netgen.pass"]
    assert pass_spans
    for p in pass_spans:
        assert by_id[p["parent_id"]]["name"] == "netgen.pipeline"
    kernel_spans = [s for s in spans if s["name"] == "netgen.kernel"]
    assert kernel_spans
    for k in kernel_spans:
        assert by_id[k["parent_id"]]["name"] == "netgen.dispatch"

    prom = (trace_dir / "metrics.prom").read_text()
    assert "netgen_cache_compiles_total" in prom
    assert "netgen_cache_store_hits_total" in prom
    assert 'netgen_predict_latency_seconds{quantile="0.5"' in prom \
        or 'version="v0"' in prom
    samples = parse_prometheus(prom)
    latency_quantiles = [
        (labels, v) for name, labels, v in samples
        if name == "netgen_predict_latency_seconds"
        and "quantile" in labels and labels.get("server") == server._scope]
    assert {l["quantile"] for l, _ in latency_quantiles} >= {"0.5", "0.99"}
    assert {l["version"] for l, _ in latency_quantiles} == {"v0", "v1"}

    report = telemetry.report()
    occ_line = next(line for line in report.splitlines()
                    if "netgen_slot_occupancy" in line
                    and server._scope in line)
    assert "count=0" not in occ_line
    assert "p50=0 " not in occ_line          # non-zero occupancy rendered

    assert check_trace_dir(trace_dir) == []


def test_check_trace_gate_warm_run(tmp_path):
    """A process that warm-starts every artifact from the store never
    compiles, so its trace has no compile/pipeline/pass spans — the
    gate must accept store-load + dispatch + kernel instead (this is
    exactly CI's cached-store tier-1 run)."""
    telemetry.enable()
    store = netgen.ArtifactStore(tmp_path / "store")
    net = _net(8)
    with netgen.Session(store=store) as s0:      # cold: populate store
        s0.compile(net, target="jnp")
    telemetry.reset()
    with netgen.Session(store=store, capacity=4) as session:  # warm
        server = netgen.NetServer(session=session, slot_capacity=8,
                                  warmup=False)
        server.register("v0", net)
        server.predict_many({"v0": _x(3, 11)})
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    telemetry.export_jsonl(trace_dir / "trace.jsonl")
    (trace_dir / "metrics.prom").write_text(telemetry.prometheus())
    names = {json.loads(line)["name"] for line in
             (trace_dir / "trace.jsonl").read_text().splitlines()}
    assert "netgen.compile" not in names          # genuinely warm
    assert "netgen.store.load" in names
    assert check_trace_dir(trace_dir) == []


def test_check_trace_gate_catches_violations(tmp_path):
    good = [
        {"trace_id": 1, "span_id": 1, "parent_id": None,
         "name": "netgen.compile", "start_unix": 1.0, "duration_s": 0.5,
         "attrs": {}, "thread": "t"},
    ]
    assert check_spans(good, require=("netgen.compile",)) == []
    # orphan parent
    bad = good + [{"trace_id": 1, "span_id": 2, "parent_id": 99,
                   "name": "netgen.pass", "start_unix": 1.0,
                   "duration_s": 0.1, "attrs": {}, "thread": "t"}]
    assert any("orphan" in e for e in check_spans(bad, require=()))
    # compile budget
    slow = [dict(good[0], duration_s=1e4)]
    assert any("over budget" in e
               for e in check_spans(slow, require=(), compile_budget_s=300))
    # duplicate ids
    assert any("duplicate" in e
               for e in check_spans(good + good, require=()))
    # counter identity breakage via metrics
    broken = parse_prometheus(
        'netgen_cache_misses_total{cache="c"} 3\n'
        'netgen_cache_compiles_total{cache="c"} 1\n'
        'netgen_cache_store_hits_total{cache="c"} 1\n')
    assert any("misses" in e for e in check_metrics(broken))
    # occupancy domain (only gated for scopes with observations)
    occ = parse_prometheus(
        'netgen_slot_occupancy{server="s",quantile="0.5"} 1.5\n'
        'netgen_slot_occupancy_count{server="s"} 4\n')
    assert any("occupancy" in e for e in check_metrics(occ))
    idle = parse_prometheus(
        'netgen_slot_occupancy{server="s",quantile="0.5"} 0.0\n'
        'netgen_slot_occupancy_count{server="s"} 0\n')
    assert check_metrics(idle) == []
    # slot rounds: each kernel span parents one launch and one fetch
    def rec(sid, name, parent=None):
        return {"trace_id": 1, "span_id": sid, "parent_id": parent,
                "name": name, "start_unix": 1.0, "duration_s": 0.1,
                "attrs": {}, "thread": "t"}
    round_ok = [rec(1, "netgen.dispatch"), rec(2, "netgen.round.stage", 1),
                rec(3, "netgen.kernel", 1),
                rec(4, "netgen.round.launch", 3),
                rec(5, "netgen.round.fetch", 3)]
    assert check_rounds(round_ok) == []
    assert check_spans(round_ok, require=()) == []
    no_fetch = round_ok[:4]
    assert any("0 netgen.round.fetch" in e for e in check_rounds(no_fetch))
    two_launches = round_ok + [rec(6, "netgen.round.launch", 3)]
    assert any("2 netgen.round.launch" in e
               for e in check_rounds(two_launches))
    misparented = round_ok[:4] + [rec(5, "netgen.round.fetch", 1)]
    assert any("0 netgen.round.fetch" in e
               for e in check_rounds(misparented))
    # missing files
    errors = check_trace_dir(tmp_path)
    assert any("trace.jsonl missing" in e for e in errors)
