"""CI gate over a `netgen.telemetry` trace directory.

`examples/mnist_fpga_pipeline.py --trace DIR` writes DIR/trace.jsonl
(one finished span per line) and DIR/metrics.prom (Prometheus text
exposition). This script fails CI when either file violates the
telemetry invariants:

  trace.jsonl   span ids unique; every parent_id resolves to a span in
                the same trace; durations and start times sane; the
                instrumented lifecycle actually present (compile,
                pipeline, pass, dispatch, kernel spans — or, when the
                metrics say zero compiles happened because the run
                warm-started from a cached ArtifactStore, store-load +
                dispatch + kernel spans); no compile span over
                --compile-budget-s (generous — it catches a
                pathological compile-time regression, not jitter).
  metrics.prom  every counter non-negative; per cache scope
                misses == compiles + store_hits + failures (each memory
                miss is served by exactly one lower tier, or raised); slot
                occupancy quantiles in (0, 1]; latency p50 <= p99; per
                (server, version) the latency histogram count equals
                netgen_requests_total (every dispatch observed exactly
                one per-version service time).

A third check spans BOTH files (`check_launches`): every
`netgen.kernel` span dispatched on the fusednet megakernel must record
exactly ONE Pallas launch (`launches` attr == 1 — the datapath's whole
point), and `netgen_kernel_launches_total{form="fusednet"}` must cover
every such launch, one or more slot rounds (warm-up and direct
predictor calls may launch outside a serving span, so the counter
bounds the span count from above). Skipped when the trace carries no
fusednet traffic.

  PYTHONPATH=src python benchmarks/check_trace.py DIR \\
      [--compile-budget-s 300]

A fourth check (`check_rounds`) gates the split of a launch (one or
more slot rounds): every `netgen.kernel` span has exactly one
`netgen.round.launch` child (the predictor call) and one
`netgen.round.fetch` child (the blocking copy of its result), the spans
the benchmark's per-launch metrics read.

A fifth check (`check_explore`) gates the design-space explorer's
counting identities when a trace carries explorer traffic: per
explorer scope, `netgen_explore_candidates_total` ==
`..._pruned_total` + `..._measured_total` (every considered candidate
was either statically rejected or measured) and
`..._artifacts_total` == `..._measured_total` (every measured
candidate is backed by exactly one store artifact).

The checks are importable pure functions (`check_spans`,
`check_metrics`, `check_launches`, `check_rounds`, `check_explore`) so
the telemetry tests exercise the same gate CI runs.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

REQUIRED_SPANS = ("netgen.compile", "netgen.pipeline", "netgen.pass",
                  "netgen.dispatch", "netgen.kernel")
# a fully warm-started process (every artifact served from the
# ArtifactStore — CI's cached-store runs) legitimately never compiles,
# so its trace shows store loads + serving instead of the compile tree
WARM_REQUIRED_SPANS = ("netgen.store.load", "netgen.dispatch",
                       "netgen.kernel")


def check_spans(spans: list[dict], *, compile_budget_s: float = 300.0,
                require: tuple = REQUIRED_SPANS) -> list[str]:
    """Invariant violations (empty list == pass) for parsed span dicts."""
    errors: list[str] = []
    if not spans:
        return ["no spans in trace"]
    by_id: dict[int, dict] = {}
    for rec in spans:
        sid = rec.get("span_id")
        if sid in by_id:
            errors.append(f"duplicate span_id {sid}")
        by_id[sid] = rec
    for rec in spans:
        name = rec.get("name", "?")
        sid = rec.get("span_id")
        parent = rec.get("parent_id")
        if parent is not None:
            if parent not in by_id:
                errors.append(f"orphan span {name} (id={sid}): "
                              f"parent_id {parent} not in trace")
            elif by_id[parent].get("trace_id") != rec.get("trace_id"):
                errors.append(f"span {name} (id={sid}) crosses traces: "
                              f"parent {parent}")
        if not isinstance(rec.get("duration_s"), (int, float)) \
                or rec["duration_s"] < 0:
            errors.append(f"span {name} (id={sid}) has bad duration "
                          f"{rec.get('duration_s')!r}")
        if not isinstance(rec.get("start_unix"), (int, float)) \
                or rec["start_unix"] <= 0:
            errors.append(f"span {name} (id={sid}) has bad start_unix "
                          f"{rec.get('start_unix')!r}")
        if name == "netgen.compile" and rec.get("duration_s", 0) \
                > compile_budget_s:
            errors.append(
                f"compile span over budget: {rec['duration_s']:.1f}s "
                f"> {compile_budget_s:.0f}s ({rec.get('attrs')})")
    names = {rec.get("name") for rec in spans}
    for want in require:
        if want not in names:
            errors.append(f"expected span {want!r} missing from trace")
    return errors


_PROM_LINE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$")


def parse_prometheus(text: str) -> list[tuple[str, dict, float]]:
    """(name, labels, value) triples from a text exposition."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        if m is None:
            raise ValueError(f"unparseable exposition line: {line!r}")
        labels = {}
        if m.group("labels"):
            for part in re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"',
                                   m.group("labels")):
                labels[part[0]] = part[1]
        out.append((m.group("name"), labels, float(m.group("value"))))
    return out


def check_metrics(samples: list[tuple[str, dict, float]]) -> list[str]:
    """Counter/histogram invariant violations (empty list == pass)."""
    errors: list[str] = []
    per_cache: dict[str, dict[str, float]] = defaultdict(dict)
    latency: dict[tuple, dict[str, float]] = defaultdict(dict)
    latency_counts: dict[tuple, float] = {}
    request_counts: dict[tuple, float] = {}
    # an idle server's occupancy summary legitimately exports 0-valued
    # quantiles (empty histogram): only gate scopes that saw traffic
    occ_counts = {labels.get("server"): value
                  for name, labels, value in samples
                  if name == "netgen_slot_occupancy_count"}
    for name, labels, value in samples:
        if name.endswith("_total") and value < 0:
            errors.append(f"negative counter {name}{labels}: {value}")
        if name == "netgen_slot_occupancy" and "quantile" in labels \
                and occ_counts.get(labels.get("server"), 0) > 0:
            if not 0.0 < value <= 1.0:
                errors.append(
                    f"slot occupancy quantile out of (0, 1]: "
                    f"{labels} -> {value}")
        cache = labels.get("cache")
        if cache is not None:
            if name == "netgen_cache_misses_total":
                per_cache[cache]["misses"] = value
            elif name == "netgen_cache_compiles_total":
                per_cache[cache]["compiles"] = value
            elif name == "netgen_cache_store_hits_total":
                per_cache[cache]["store_hits"] = value
            elif name == "netgen_cache_compile_failures_total":
                per_cache[cache]["failures"] = value
        if name == "netgen_predict_latency_seconds" and "quantile" in labels:
            key = (labels.get("server"), labels.get("version"))
            latency[key][labels["quantile"]] = value
        if name == "netgen_predict_latency_seconds_count":
            latency_counts[(labels.get("server"),
                            labels.get("version"))] = value
        if name == "netgen_requests_total":
            request_counts[(labels.get("server"),
                            labels.get("version"))] = value
    for cache, c in sorted(per_cache.items()):
        # failures: misses whose compile raised (a VerificationError from
        # the pre-backend analysis, a backend error) — counted so the
        # three lower-tier outcomes still sum to the misses exactly.
        if {"misses", "compiles", "store_hits"} <= set(c) and \
                c["misses"] != (c["compiles"] + c["store_hits"]
                                + c.get("failures", 0)):
            errors.append(
                f"cache {cache}: misses ({c['misses']:.0f}) != compiles "
                f"({c['compiles']:.0f}) + store_hits ({c['store_hits']:.0f})"
                f" + failures ({c.get('failures', 0):.0f})")
    for key, qs in sorted(latency.items()):
        if "0.5" in qs and "0.99" in qs and qs["0.5"] > qs["0.99"]:
            errors.append(f"latency p50 > p99 for server={key[0]} "
                          f"version={key[1]}: {qs['0.5']} > {qs['0.99']}")
    # every dispatched request produced exactly one per-version latency
    # observation — the identity that catches the whole-call-dt
    # misattribution bug (ISSUE 7): predict_many must observe each
    # version's own service time once, not the shared wall clock N times
    # (or zero times)
    for key in sorted(set(latency_counts) | set(request_counts)):
        n_lat = latency_counts.get(key, 0.0)
        n_req = request_counts.get(key, 0.0)
        if n_lat != n_req:
            errors.append(
                f"latency observations ({n_lat:.0f}) != requests "
                f"({n_req:.0f}) for server={key[0]} version={key[1]}")
    return errors


def check_launches(spans: list[dict],
                   samples: list[tuple[str, dict, float]]) -> list[str]:
    """The megakernel's launch-count contract (empty list == pass): a
    fusednet launch (one or more slot rounds) is ONE Pallas launch.
    Each `netgen.kernel` span with attrs.form == "fusednet" must carry
    launches == 1, and the `netgen_kernel_launches_total{form="fusednet"}`
    counter must be at least the number of such launches (predictor
    warm-ups launch outside any serving span, so equality is not
    required). No-op for traces without fusednet traffic."""
    errors: list[str] = []
    rounds = [rec for rec in spans
              if rec.get("name") == "netgen.kernel"
              and (rec.get("attrs") or {}).get("form") == "fusednet"]
    for rec in rounds:
        launches = (rec.get("attrs") or {}).get("launches")
        if launches != 1:
            errors.append(
                f"fusednet dispatch round (span_id="
                f"{rec.get('span_id')}) records launches={launches!r}, "
                f"expected exactly 1")
    total = sum(v for name, labels, v in samples
                if name == "netgen_kernel_launches_total"
                and labels.get("form") == "fusednet")
    if rounds and total < len(rounds):
        errors.append(
            f"{len(rounds)} fusednet dispatch rounds but "
            f"netgen_kernel_launches_total{{form=fusednet}} is only "
            f"{total:.0f}")
    return errors


ROUND_CHILDREN = ("netgen.round.launch", "netgen.round.fetch")


def check_rounds(spans: list[dict]) -> list[str]:
    """The split of a launch, one or more slot rounds (empty list ==
    pass): every `netgen.kernel` span parents exactly one
    `netgen.round.launch` and one `netgen.round.fetch` span."""
    children: dict = defaultdict(lambda: dict.fromkeys(ROUND_CHILDREN, 0))
    for rec in spans:
        if rec.get("name") in ROUND_CHILDREN:
            children[rec.get("parent_id")][rec["name"]] += 1
    errors: list[str] = []
    for rec in spans:
        if rec.get("name") != "netgen.kernel":
            continue
        got = children[rec.get("span_id")]
        for name in ROUND_CHILDREN:
            if got[name] != 1:
                errors.append(
                    f"netgen.kernel span (span_id={rec.get('span_id')}) "
                    f"has {got[name]} {name} children, expected exactly 1")
    return errors


def check_explore(samples: list[tuple[str, dict, float]]) -> list[str]:
    """The design-space explorer's counting identities (empty list ==
    pass), per `explorer=` scope: every unique candidate considered was
    either pruned pre-measurement by the shared legality checks or
    measured (`candidates == pruned + measured` — a candidate that
    silently vanished means the search lied about its coverage), and
    every measured candidate is backed by exactly one store artifact
    (`artifacts == measured`). No-op for traces without explorer
    traffic."""
    errors: list[str] = []
    short = {
        "netgen_explore_candidates_total": "candidates",
        "netgen_explore_pruned_total": "pruned",
        "netgen_explore_measured_total": "measured",
        "netgen_explore_artifacts_total": "artifacts",
    }
    per: dict[str, dict[str, float]] = defaultdict(dict)
    for name, labels, value in samples:
        scope = labels.get("explorer")
        if scope is not None and name in short:
            per[scope][short[name]] = value
    for scope, c in sorted(per.items()):
        cand = c.get("candidates", 0.0)
        pruned = c.get("pruned", 0.0)
        measured = c.get("measured", 0.0)
        if cand != pruned + measured:
            errors.append(
                f"explorer {scope}: candidates ({cand:.0f}) != pruned "
                f"({pruned:.0f}) + measured ({measured:.0f})")
        if c.get("artifacts", 0.0) != measured:
            errors.append(
                f"explorer {scope}: artifacts ({c.get('artifacts', 0.0):.0f})"
                f" != measured candidates ({measured:.0f}) — a measured "
                f"candidate must be backed by exactly one store artifact")
    return errors


def check_trace_dir(trace_dir, *, compile_budget_s: float = 300.0
                    ) -> list[str]:
    """All invariant violations for one --trace output directory."""
    trace_dir = Path(trace_dir)
    errors: list[str] = []
    samples: list[tuple[str, dict, float]] = []
    prom = trace_dir / "metrics.prom"
    if not prom.exists():
        errors.append(f"{prom} missing")
    else:
        try:
            samples = parse_prometheus(prom.read_text())
            errors += check_metrics(samples)
            errors += check_explore(samples)
        except ValueError as e:
            errors.append(str(e))
    # did this process compile anything, or warm-start off the store?
    compiles = sum(v for name, _, v in samples
                   if name == "netgen_cache_compiles_total")
    require = REQUIRED_SPANS if compiles > 0 else WARM_REQUIRED_SPANS
    jsonl = trace_dir / "trace.jsonl"
    if not jsonl.exists():
        errors.append(f"{jsonl} missing")
    else:
        spans = []
        for i, line in enumerate(jsonl.read_text().splitlines(), 1):
            try:
                spans.append(json.loads(line))
            except json.JSONDecodeError:
                errors.append(f"{jsonl}:{i}: not valid JSON")
        errors += check_spans(spans, compile_budget_s=compile_budget_s,
                              require=require)
        errors += check_launches(spans, samples)
        errors += check_rounds(spans)
    return errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", help="directory written by --trace")
    ap.add_argument("--compile-budget-s", type=float, default=300.0,
                    help="fail if any netgen.compile span exceeds this")
    args = ap.parse_args()
    errors = check_trace_dir(args.trace_dir,
                             compile_budget_s=args.compile_budget_s)
    if errors:
        for e in errors:
            print(f"TRACE GATE: {e}", file=sys.stderr)
        sys.exit(1)
    print(f"trace gate passed: {args.trace_dir}")


if __name__ == "__main__":
    main()
