"""Chip benchmark of netgen serving: one cell of BENCHMARK.json per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from BENCHMARK.json: the cell's configuration
(`configs[].file`, whose `net` names the module of its shape of net,
`bench/nets/<net>.py`, `dense_chain` where it names none; whose versions name
a weight scheme in `bench/weights/<scheme>.py`; and whose `reference` names
the plain reference `bench/<reference>.py`), its traffic mix
(`bench/traffic/<traffic>.json`, read by `bench/generator.py`) and each
per-layer metric's reader (`bench/metrics/<metric>.py`, or `<stem>.py` for a
metric `<stem>.<split>`: a `read(run)` that returns a number or None). Weight
schemes and references are given the configuration as it is run.

A run: checks for the chips the cell asks for (none: exit 2, no result);
makes the configuration's weights and the seed's inputs; registers the
versions with a `NetServer` over the configured target, through netgen's
artifact store and JAX's compilation cache inside the checkout, and warms
up the cell's shapes (set-up); runs the traffic for `--seconds`; then
compares every answer of the window with the plain reference. With
`--trace 1` the window runs under `jax.profiler` with the program's spans
on, and the line carries the per-layer metrics in place of the end-to-end
ones.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` `breakdown`,
`window_compiles` (traces and compiles inside the window, which should be
0), and last `checks`, each compared number beside its limit. The same
checks are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from concurrent.futures import wait  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# import this directory as the package `bench`: its trace.py must not
# shadow the standard library's module of that name
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import generator, work  # noqa: E402
from bench import trace as xtrace  # noqa: E402

CACHE_DIR = ".bench/jax_cache"     # JAX's persistent compilation cache
STORE_DIR = ".bench/netgen_store"  # netgen's ArtifactStore: the nets' built IR
TRACE_DIR = ".bench/trace"         # profiler output, read and removed
LIMITS = {"max_gap": 0.0, "unanswered": 0.0, "errored": 0.0}
STREAM_WEIGHTS = 1
DEFAULT_NET = "dense_chain"   # the net of a configuration that names none
OVER_LIMIT = 1e9   # printed in place of an infinite gap (a class out of range)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class LayoutError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_file(path: Path, name: str):
    """Import one file by path as module `name`."""
    if not path.is_file():
        raise LayoutError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _listed(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_net(root: Path, config: dict):
    """The module of the configuration's net: `bench/nets/<net>.py`."""
    name = config.get("net", DEFAULT_NET)
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_-]*", name):
        raise LayoutError(f"net {name!r} of {config.get('name')!r} is not a module name")
    return load_file(root / "bench" / "nets" / f"{name}.py", "bench_net_" + name)


def load_cell(root: Path, name: str) -> dict:
    """The cell `name` of `root`/BENCHMARK.json with its configuration, the
    module of its net, its traffic mix, and the metrics it reports."""
    layout = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in layout["workloads"]}
    if name not in cells:
        raise LayoutError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in layout["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic_file = root / "bench" / "traffic" / f"{cell['traffic']}.json"
    if not traffic_file.is_file():
        raise LayoutError(f"missing {traffic_file}")
    per_layer = [(m["name"], m["unit"], load_metric(root, m["name"]))
                 for m in layout["per_layer"] if _listed(m, name)]
    return {"cell": cell, "config": config, "net": load_net(root, config),
            "traffic": json.loads(traffic_file.read_text()),
            "end_to_end": [(m["name"], m["unit"]) for m in layout["end_to_end"]
                           if _listed(m, name)],
            "per_layer": per_layer}


def load_metric(root: Path, name: str):
    """The reader of metric `name`: `bench/metrics/<name>.py`, or for a
    metric split by cell kind (`<stem>.online`) the stem's `<stem>.py`."""
    metrics_dir = root / "bench" / "metrics"
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        path = metrics_dir / f"{name.split('.', 1)[0]}.py"
    return load_file(path, "bench_metric_" + name)


def make_versions(root: Path, config: dict, n: int) -> list:
    """[(name, weights)] of the config's first `n` versions, each as its
    scheme makes it from the configuration (for a dense chain, int32 matrices).

    The weights are the configuration's, drawn from its `weight_seed`, not
    from `--seed`: the program compiles weights into its XLA program as
    constants, so weights that followed the run's seed would compile anew
    in every run and no run would find its program in the cache. Every
    version draws from the same stream, so versions differ only by scheme."""
    if n > len(config["versions"]):
        raise LayoutError(f"traffic asks for {n} versions; {config['name']} has "
                          f"{len(config['versions'])}")
    out = []
    for v in config["versions"][:n]:
        scheme = v["weights"]["scheme"]
        mod = load_file(root / "bench" / "weights" / f"{scheme}.py", "bench_weights_" + scheme)
        out.append((v["name"], mod.make(generator.rng(config["weight_seed"], STREAM_WEIGHTS),
                                        config, v["weights"])))
    return out


def serve(root: Path, config: dict, net, traffic: dict, versions: list, inputs: dict):
    """The served path, set up and warmed: a `NetServer` over the configured
    target with the versions registered, each as the net module builds it,
    and for an online mix a `ServingEngine` in front (else None). Warm-up
    runs every program the window will run and no other: offline, the
    window's own first call; online, each set of versions a round can hold,
    through the server, then a few requests through the engine. Warm-up
    answers are not judged."""
    from repro import netgen

    names = [v for v, _ in versions]
    server = netgen.NetServer(session=netgen.Session(store=str(root / STORE_DIR)),
                              target=config["target"],
                              slot_capacity=int(config["slot_capacity"]))
    for v, ws in versions:
        server.register(v, net.build(config, ws))
    if traffic["mode"] != "online":
        server.predict_many(inputs["blocks"][0])
        return server, None
    row = inputs["pool"][:1]
    for k in range(1, len(names) + 1):
        for subset in itertools.combinations(names, k):
            server.predict_many({v: row for v in subset})
    engine = netgen.ServingEngine(server, max_batch_delay=traffic["max_batch_delay_s"],
                                  max_queue_depth=traffic["max_queue_depth"])
    wait([engine.submit(names[i % len(names)], inputs["pool"][i]) for i in range(8)],
         timeout=generator.DRAIN_S)
    return server, engine


def latencies(rec: dict) -> dict:
    """An online window's outcome: which requests were answered (`ok`),
    refused, failed with an exception or never answered, and each request's
    latency from its due time (a request with no answer counts until the
    wait for answers ended)."""
    served = rec["served"]
    ok = served >= 0
    return {"ok": ok,
            "refused": int((served == generator.REFUSED).sum()),
            "errored": int((served == generator.FAILED).sum()),
            "unanswered": int((served == generator.ANSWERED_NONE).sum()),
            "lat": np.where(ok, rec["t_done"] - rec["due"], rec["t_end"] - rec["due"])}


def quantile_ms(lat: np.ndarray, q: float) -> float:
    return float(np.quantile(lat, q, method="inverted_cdf")) * 1e3


def _counters(reg) -> dict:
    """{(kind, name, labels): value or (count, sum)} of every counter and
    histogram in the program's registry."""
    summary = reg.summary()
    snap = {}
    for kind, entries in (("counter", summary["counters"]), ("histogram", summary["histograms"])):
        for m in entries:
            key = (kind, m["name"], tuple(sorted((k, str(v)) for k, v in m["labels"].items())))
            snap[key] = (m["count"], m["sum"]) if kind == "histogram" else m["value"]
    return snap


class RunData:
    """What a per-layer reader may read: the window, the trace, the
    program's counters and spans over the window, the configuration as run
    (`config`; `widths` and `slot_capacity` from it), its net module (`net`:
    `net.ops`, `net.bytes_moved` and `net.min_seconds` of
    `(config, rows, versions)` price a call) and the dense-chain work model
    (`work`)."""

    work = work

    def __init__(self, mode, window_s, trace, before, after, spans, lag_s, completed,
                 config, net, versions, peak):
        self.mode, self.window_s, self.trace = mode, window_s, trace
        self._before, self._after, self.spans = before, after, spans
        self.lag_s, self.completed = lag_s, completed
        self.config, self.net, self.versions, self.peak = config, net, versions, peak
        self.widths = config.get("widths")
        self.slot_capacity = int(config["slot_capacity"])

    def _match(self, kind, name, labels):
        for key, v in self._after.items():
            if key[0] == kind and key[1] == name and all(
                    (k, str(val)) in key[2] for k, val in labels.items()):
                yield v, self._before.get(key)

    def delta(self, name: str, **labels) -> float:
        """Change over the window of counter `name`, summed over label sets
        that carry `labels`."""
        return float(sum(a - (b or 0) for a, b in self._match("counter", name, labels)))

    def hist_delta(self, name: str, **labels) -> tuple:
        """(count, sum) added to histogram `name` over the window."""
        n = s = 0.0
        for (ca, sa), b in self._match("histogram", name, labels):
            cb, sb = b or (0, 0.0)
            n, s = n + ca - cb, s + sa - sb
        return n, s

    def kernel_events(self, kernel: str) -> list:
        return xtrace.kernel_events(self.trace, kernel)


def _device_info(devices, chips: int, tr) -> dict:
    used = devices[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": used[0].platform, "kind": used[0].device_kind, "count": len(used),
            "memory_peak_bytes": max(peaks)}
    if tr is not None:
        info["busy_s"] = tr.busy_s
        info["window_s"] = tr.window_s
    return info


def run_cell(args, *, root: Path = ROOT, require_chip: bool = True) -> dict | None:
    """One run of one cell; returns the result object, or None where the
    chips the cell asks for are not there."""
    spec = load_cell(root, args.workload)
    cell, config, traffic, net = spec["cell"], spec["config"], spec["traffic"], spec["net"]

    import jax

    devices = jax.devices()
    chips = int(cell["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: cell {cell['name']} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        if require_chip:
            return None
    peak = work.peaks(devices[0].device_kind) if require_chip else None
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    sys.path.insert(0, str(root / "src"))
    from repro.netgen import telemetry
    from repro.netgen.engine import QueueFullError

    versions = make_versions(root, config, int(traffic["versions"]))
    names = [v for v, _ in versions]
    try:
        inputs = generator.make_inputs(traffic, net.row_length(config), names, args.seconds,
                                       args.seed)
    except generator.MixError as e:
        raise LayoutError(f"traffic {cell['traffic']}: {e}") from e
    online = traffic["mode"] == "online"
    server, engine = serve(root, config, net, traffic, versions, inputs)
    pool_rows = list(inputs["pool"]) if online else None
    gc.collect()

    reg = telemetry.get_registry()
    trace_dir = root / TRACE_DIR / f"{cell['name']}-{args.seed}"
    window_ns = [0]    # time.time_ns() as the window opens
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        telemetry.enable()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2

        def annotate(name):
            if name == xtrace.WINDOW:
                window_ns[0] = time.time_ns()
            return jax.profiler.TraceAnnotation(name)
    else:
        annotate = contextlib.nullcontext
    window_open = [False]
    compiles: list = []    # traces and compiles while the window is open

    def on_compile(event, _secs, **_kw):
        if window_open[0] and event in COMPILE_EVENTS:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    before = _counters(reg)
    setup_s = time.perf_counter() - T_START
    window_open[0] = True
    if args.trace:
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    if online:
        rec = generator.run_online(engine.submit, names, pool_rows, inputs,
                                   (QueueFullError,), annotate)
    else:
        rec = generator.run_offline(server.predict_many, inputs["blocks"], args.seconds,
                                    annotate)
    window_open[0] = False
    if args.trace:
        jax.profiler.stop_trace()
    after = _counters(reg)
    spans = reg.spans() if args.trace else []
    telemetry.disable()
    tr = xtrace.read(xtrace.find_xplane(trace_dir)) if args.trace else None
    device = _device_info(devices, chips, tr)
    if engine is not None:
        engine.shutdown(drain=False)
    del engine, server
    gc.collect()

    # -- the comparison with the plain reference ------------------------------
    ref_mod = load_file(root / "bench" / f"{config['reference']}.py",
                        "bench_reference_" + config["reference"])
    weights = dict(versions)
    e2e = {"setup_s": setup_s}
    if online:
        served, n = rec["served"], len(rec["served"])
        out = latencies(rec)
        ok, unanswered, errored = out["ok"], out["unanswered"], out["errored"]
        failed = out["refused"]
        e2e["p50_ms"] = quantile_ms(out["lat"], 0.50)
        gap = 0.0
        for k, v in enumerate(names):
            mine = ok & (inputs["ver"] == k)
            ref = ref_mod.logits(weights[v], config, inputs["pool"])
            gap = max(gap, ref_mod.widest_gap(ref[inputs["idx"][mine]], served[mine]))
        attempted, completed = n, int(ok.sum())
        lag_s = rec["t_sent"] - rec["due"]
        lag_s = lag_s[~np.isnan(lag_s)]
        window_s = rec["t_closed"] - rec["t0"]
    else:
        failed = unanswered = errored = 0
        refs = [{v: ref_mod.logits(weights[v], config, blk[v]) for v in names}
                for blk in inputs["blocks"]]
        gap, completed = 0.0, 0
        for b, out in rec["outs"]:
            for v in names:
                want = inputs["blocks"][b][v].shape[0]
                got = np.asarray(out.get(v, np.zeros(0, np.int64)))
                unanswered += max(0, want - got.shape[0])
                completed += min(want, got.shape[0])
                gap = max(gap, ref_mod.widest_gap(refs[b][v][:got.shape[0]], got[:want]))
        attempted = completed + unanswered
        window_s = rec["t_end"] - rec["t0"]
        e2e["preds_per_s"] = completed / window_s
        lag_s = None
    checks = {"max_gap": gap if math.isfinite(gap) else OVER_LIMIT,
              "unanswered": float(unanswered), "errored": float(errored)}
    correct = all(checks[k] <= LIMITS[k] for k in LIMITS)

    if args.trace:
        run = RunData(traffic["mode"], window_s, tr, before, after, spans, lag_s, completed,
                      config, net, len(names), peak)
        metrics = {}
        for mname, unit, mod in spec["per_layer"]:
            value = mod.read(run)
            if value is not None:
                metrics[mname] = {"value": float(value), "unit": unit}
        offset = window_ns[0] - tr.window[0]
        host_spans = [(n_, s, s + d, th) for n_, s, d, th in tr.host] + [
            (sp.name, sp.start_unix * 1e9 - offset,
             sp.start_unix * 1e9 - offset + sp.duration_s * 1e9, sp.thread) for sp in spans]
        breakdown = {"device_ops": xtrace.top_ops(tr),
                     "idle_gaps": xtrace.label_gaps(xtrace.idle_gaps(tr), host_spans)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {}
        for mname, unit in spec["end_to_end"]:
            if mname not in e2e:
                raise LayoutError(f"cell {cell['name']} lists {mname}, which a "
                                  f"{traffic['mode']} mix does not produce")
            metrics[mname] = {"value": float(e2e[mname]), "unit": unit}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed + unanswered + errored), "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = breakdown
    result["window_compiles"] = len(compiles)
    result["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]} for k in LIMITS}
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    result = run_cell(args)
    if result is None:
        return 2
    if result["window_compiles"]:
        print(f"bench: {result['window_compiles']} trace or compile event(s) inside the "
              "window", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
