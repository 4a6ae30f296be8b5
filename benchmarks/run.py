"""CPU rehearsal suites — one module per paper table (+ kernels). Prints
``name,us_per_call,derived`` CSV and, with ``--bench-json FILE``, writes
a trajectory artifact (git rev + every row + per-suite wall clock).
These are interpret-mode CPU timings: they check that each path runs
and agrees, and prove nothing about speed on the chip. The benchmark
of record is ``bench/run.py`` (cells in ``BENCHMARK.json``).

  python -m benchmarks.run [--full] [--only SUITE] [--fake-devices N]
      [--bench-json FILE] [--serve-json FILE] [--explore-report FILE]

--full runs paper-sized versions (500 hidden units, 60 epochs, full
Verilog emission); default is a fast sanity pass. --fake-devices N
spreads the sharded serving rows over N faked host devices (must be
set before jax initializes, hence a flag here). --serve-json
additionally writes the serve suite's detailed measurement dict;
--explore-report the explore suite's ExplorationReport JSON. Suite
artifacts are written ONLY under these declared output paths — no
suite drops files in the working directory.

Row conventions: ratio rows (`*_speedup`) put 0 in us_per_call and
carry `ratio=..;<num>_us=..;<den>_us=..` in derived — the ratio's own
measurement pair, self-contained in the trajectory artifact. The serve
suite emits one `netgen_serve_pallas_<form>_b256` row per datapath
(dense / packed / planes / fusednet).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT, check=True,
            capture_output=True, text=True).stdout.strip()
    except Exception:  # noqa: BLE001 — no git in some CI containers
        return "unknown"


def write_bench_json(path, rows: list[str], suite_seconds: dict,
                     full: bool) -> None:
    """The perf trajectory artifact: parse the printed CSV rows into
    records and stamp them with the git revision, so a future PR can
    diff its artifact against its parent's."""
    parsed = []
    for row in rows:
        name, _, rest = row.partition(",")
        us, _, derived = rest.partition(",")
        try:
            us_val: float | None = float(us)
        except ValueError:
            us_val = None
        parsed.append({"name": name, "us_per_call": us_val,
                       "derived": derived})
    payload = {
        "format": "bench-netgen-v1",
        "git_rev": _git_rev(),
        "created_unix": time.time(),
        "full": full,
        "suite_seconds": {k: round(v, 3) for k, v in suite_seconds.items()},
        "rows": parsed,
    }
    try:
        # fold the run's telemetry (compile/store/dispatch counters, the
        # bench timing histograms, cost_analysis gauges) into the
        # trajectory artifact — the roofline inputs ride along for free
        from repro.netgen import telemetry
        payload["telemetry"] = telemetry.summary()
    except Exception:  # noqa: BLE001 — a bench artifact must still be written
        pass
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names to run")
    ap.add_argument("--fake-devices", type=int, default=0, metavar="N",
                    help="fake N host devices for the sharded serving rows")
    ap.add_argument("--bench-json", default="",
                    help="write the perf trajectory artifact (git rev + "
                         "rows + timings) here; empty (default) disables")
    ap.add_argument("--serve-json", default=None,
                    help="also write the serve suite's detailed JSON here")
    ap.add_argument("--explore-report", default=None,
                    help="also write the explore suite's "
                         "ExplorationReport JSON here")
    ap.add_argument("--store", default=None,
                    help="persistent ArtifactStore dir for the explore "
                         "suite (CI hands it the cached .netgen-store)")
    ap.add_argument("--tune-store", default=None,
                    help="persistent TuneStore dir for the explore suite "
                         "(explored winners land here for warm replays)")
    args = ap.parse_args()
    if args.fake_devices:
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_kernels, bench_ladder, bench_netgen,
                            bench_netgen_engine, bench_netgen_explore,
                            bench_netgen_passes, bench_netgen_serve,
                            bench_throughput)

    suites = {
        "ladder": bench_ladder.run,          # paper §III accuracy table
        "netgen": bench_netgen.run,          # paper §V.D resource table
        "netgen_passes": bench_netgen_passes.run,  # per-pass IR attribution
        "netgen_serve": lambda full: bench_netgen_serve.run(
            full=full, json_path=args.serve_json),  # compile cache + multi-net
        "netgen_engine": bench_netgen_engine.run,  # online serving load gen
        "netgen_explore": lambda full: bench_netgen_explore.run(
            full=full, report_path=args.explore_report,
            store=args.store, tune_store=args.tune_store),  # joint DSE
        "throughput": bench_throughput.run,  # paper §V.E FPGA-vs-CPU table
        "kernels": bench_kernels.run,
    }
    print("name,us_per_call,derived")
    failed = 0
    all_rows: list[str] = []
    suite_seconds: dict[str, float] = {}
    only = (set(args.only.split(",")) if args.only else None)
    for name, fn in suites.items():
        if only is not None and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            for row in fn(full=args.full):
                print(row, flush=True)
                all_rows.append(row)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name}_FAILED,0,0")
            failed += 1
        suite_seconds[name] = time.perf_counter() - t0
    if args.bench_json:
        write_bench_json(args.bench_json, all_rows, suite_seconds, args.full)
        print(f"# wrote {args.bench_json} ({len(all_rows)} rows)",
              file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
