"""Find the knee of an online cell once, by a sweep of offered rates on the chip.

    python3 bench/sweep.py --workload <online cell> --seed <n> --seconds <s> \
        --windows 3 --rates 2000,4000,8000,...

One process, one set-up (the cell's configuration and mix, through
`run.serve`), then `--windows` windows per rate, each with its own seed.
Each window prints a JSON line: offered and answered requests, refused
ones, p50 and p99 from the due time, the generator's median and p99 lag,
and how long the answers took to drain after the last request was sent.

A rate is sustained when, taking the median over its windows (so that one
host stall in one window cannot decide it), no request was refused, the
answers drained within `DRAIN_OK_MS` of the last send (the queue did not
build up) and the generator's median lag stayed under `LAG_OK_MS`. The knee
is the highest swept rate at which it and every lower rate are sustained;
a cell then runs at a fixed rate near four fifths of it. The sweep is not
part of the benchmark's runs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)

import numpy as np  # noqa: E402

from bench import generator, run  # noqa: E402

DRAIN_OK_MS = 50.0
LAG_OK_MS = 1.0


def window(engine, names, mix, n_in, seconds, seed, reject) -> dict:
    inputs = generator.make_inputs(mix, n_in, names, seconds, seed)
    rec = generator.run_online(engine.submit, names, list(inputs["pool"]), inputs, reject)
    out = run.latencies(rec)
    lag = rec["t_sent"] - rec["due"]
    lag = lag[~np.isnan(lag)]
    last_done = np.nanmax(rec["t_done"]) if out["ok"].any() else rec["t_end"]
    return {"offered": len(rec["served"]), "answered": int(out["ok"].sum()),
            "refused": out["refused"], "errored": out["errored"],
            "p50_ms": run.quantile_ms(out["lat"], 0.50),
            "p99_ms": run.quantile_ms(out["lat"], 0.99),
            "lag_p50_ms": run.quantile_ms(lag, 0.50),
            "lag_p99_ms": run.quantile_ms(lag, 0.99),
            "drain_ms": (last_done - rec["t_closed"]) * 1e3}


def sustained(windows: list) -> bool:
    med = {k: statistics.median(w[k] for w in windows)
           for k in ("refused", "drain_ms", "lag_p50_ms")}
    return med["refused"] == 0 and med["drain_ms"] < DRAIN_OK_MS and med["lag_p50_ms"] < LAG_OK_MS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args(argv)
    spec = run.load_cell(ROOT, args.workload)
    config, traffic = spec["config"], spec["traffic"]
    if traffic["mode"] != "online":
        raise SystemExit("sweep: an online cell is needed")

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / run.CACHE_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.netgen.engine import QueueFullError

    versions = run.make_versions(ROOT, config, int(traffic["versions"]))
    names = [v for v, _ in versions]
    n_in = spec["net"].row_length(config)
    inputs = generator.make_inputs(traffic, n_in, names, 0.01, args.seed)
    _, engine = run.serve(ROOT, config, spec["net"], traffic, versions, inputs)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    knee, below_ok = None, True
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = {k: v for k, v in traffic.items() if k != "phases"} | {"rate_per_s": rate}
            ws = []
            for k in range(args.windows):
                ws.append(window(engine, names, mix, n_in, args.seconds, args.seed + k,
                                 (QueueFullError,)))
                print(json.dumps({"rate": rate, "window": k, **ws[-1]}), flush=True)
            ok = sustained(ws)
            below_ok = below_ok and ok
            knee = rate if below_ok else knee
            print(json.dumps({"rate": rate, "sustained": ok}), flush=True)
    finally:
        engine.shutdown(drain=False)
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
