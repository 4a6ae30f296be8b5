"""Compile-cache serving benchmark (the paper's compile-per-model economics).

Measures, in the `bench_throughput` CSV idiom:

  * cold compile (cache miss + first-trace warmup) vs warm predictor
    acquisition (cache hit) — ISSUE 2 acceptance: warm >= 100x faster
  * cold PROCESS vs warm STORE (ISSUE 3): a fresh Session pointed at an
    already-populated ArtifactStore directory loads the persisted
    artifact instead of recompiling — the cross-process warm-start the
    store exists for (load time vs full compile time, zero compiles
    asserted)
  * multi-version stacked dispatch (M versions, ONE jitted call) vs
    serving each compiled predictor individually, for M in 1..8 and
    batch sizes 1..1024, with a bit-exactness check on every
    configuration
  * the pallas activation/weight datapaths (ISSUE 4 + 5 + 9): dense vs
    `pallas[packed=true]` (end-to-end bit-packed activations) vs
    `pallas[planes=true]` (fully bit-packed: weights decomposed into
    popcount-accumulated signed bit-planes) vs `pallas[fusednet=true]`
    (the whole-net megakernel: every layer in ONE persistent launch),
    measured on the paper-sized 784-500-10 net under --full (bit-exact
    asserted against the jnp oracle) — the ISSUE-5 acceptance row
    (planes must beat the PR-4 packed path) and the ISSUE-9 one
    (fusednet must beat the per-layer planes chain by >= 1.2x)
  * XLA `jit_cost` bytes/flops of the jnp oracle (`netgen_serve_jit_cost_*`)
  * the persistent autotuner (ISSUE 5): `pallas[tuned=true]` grid
    search wall-clock, the winning (form, bm, bn, bkw), and the tuned
    predictor's timing next to the fixed-default forms
  * the design-space explorer (ISSUE 10): `Session.explore`'s joint
    pipeline x datapath x tile winner timed against the hand-tuned
    `pallas[tuned=true,fusednet=true]` path — the `netgen_explored_b256`
    row plus the pair-carrying `netgen_explored_vs_tuned_speedup` ratio
    row; --full asserts the explored config is no worse (>= 1.0x, or
    the search landed on the identical kernel config)
  * sharded vs single-device stacked serving (ISSUE 4): predict_many
    under a mesh with a data axis (shard_map over the slot dimension)
    vs the same requests without a mesh, bit-exact asserted; pass
    --fake-devices 8 (standalone runs only — the flag must precede
    jax initialization) to spread over faked host devices

The JSON artifact (CI uploads it) additionally registers the `cost`
target's Figure-7-style logic-cell estimates per pass for the benchmark
net.

  PYTHONPATH=src python benchmarks/bench_netgen_serve.py [--full] \\
      [--fake-devices N] [--json FILE]

The detailed measurement JSON is written ONLY when a path is given
(standalone --json, or benchmarks.run --serve-json): a run must never
drop artifacts outside its declared output paths — BENCH_netgen.json
is the single committed trajectory file.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np

def _nets(m: int, sizes, seed: int = 0):
    from repro.core import quantize
    out = []
    for i in range(m):
        rng = np.random.default_rng(seed + i)
        out.append(quantize.QuantizedNet(weights=[
            rng.integers(-5, 6, size=s).astype(np.int32)
            for s in zip(sizes, sizes[1:])]))
    return out


def _images(b: int, n_in: int, seed: int = 9) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, size=(b, n_in)).astype(np.uint8)


def _timed_mean(section: str, fn, reps: int) -> float:
    """Mean seconds per call over `reps` calls, timed through
    `telemetry.timed` — the SAME histogram code path production latency
    metrics use, so bench numbers and serving metrics cannot drift."""
    from repro.netgen import telemetry
    with telemetry.timed("bench_serve_seconds", section=section) as t:
        for _ in range(reps):
            fn()
    return t.elapsed / reps


def run(full: bool = False, json_path: str | None = None) -> list[str]:
    from repro import netgen

    sizes = (784, 128, 10) if full else (96, 48, 10)
    m_versions = (1, 2, 4, 8) if full else (1, 2, 4)
    batches = (1, 32, 1024) if full else (1, 32, 256)
    reps = 5 if full else 3
    warm_reps = 1000

    rows: list[str] = []
    results: dict = {"sizes": list(sizes), "backend": "jnp",
                     "cold_ms": [], "multi": []}
    nets = _nets(max(m_versions), sizes)

    # -- cold compile vs warm acquisition -----------------------------------
    cache = netgen.CompileCache(capacity=64)
    warm_batch = _images(32, sizes[0])
    for net in nets:
        t0 = time.perf_counter()
        compiled = cache.get_or_compile(net)
        np.asarray(compiled(warm_batch))     # includes first-trace jit cost
        results["cold_ms"].append((time.perf_counter() - t0) * 1e3)
    cold_s = float(np.mean(results["cold_ms"])) / 1e3

    warm_s = _timed_mean(
        "warm_acquire",
        lambda: [cache.get_or_compile(net) for net in nets],
        warm_reps) / len(nets)
    speedup = cold_s / warm_s
    results["warm_us"] = warm_s * 1e6
    results["warm_vs_cold_speedup"] = speedup
    results["cache_stats"] = vars(cache.stats())
    rows.append(f"netgen_serve_cold_compile,{cold_s*1e6:.0f},{1.0/cold_s:.1f}")
    rows.append(f"netgen_serve_warm_acquire,{warm_s*1e6:.2f},{1.0/warm_s:.0f}")
    # ratio rows carry no us_per_call of their own (it used to duplicate
    # the numerator row's): derived holds the ratio AND its measurement
    # pair, so the row is self-contained in BENCH_netgen.json
    rows.append(f"netgen_serve_warm_vs_cold_speedup,0,"
                f"ratio={speedup:.1f};cold_us={cold_s*1e6:.0f};"
                f"warm_us={warm_s*1e6:.2f}")

    # -- cold process vs warm store (persisted-artifact load) ----------------
    with tempfile.TemporaryDirectory() as store_dir:
        cold_sess = netgen.Session(store=store_dir)
        t0 = time.perf_counter()
        art = cold_sess.compile(nets[0], target="jnp")
        np.asarray(art(warm_batch))
        cold_process_s = time.perf_counter() - t0

        warm_sess = netgen.Session(store=store_dir)   # simulated new process
        t0 = time.perf_counter()
        warm_art = warm_sess.compile(nets[0], target="jnp")
        np.asarray(warm_art(warm_batch))
        warm_store_s = time.perf_counter() - t0
        st = warm_sess.stats()
        assert (st.compiles, st.store_hits) == (0, 1), vars(st)
        assert np.array_equal(np.asarray(art(warm_batch)),
                              np.asarray(warm_art(warm_batch)))
        results["store"] = {
            "cold_process_ms": cold_process_s * 1e3,
            "warm_store_ms": warm_store_s * 1e3,
            "speedup": cold_process_s / warm_store_s,
            "warm_compiles": st.compiles,
            "warm_store_hits": st.store_hits,
        }
        rows.append(f"netgen_serve_cold_process,{cold_process_s*1e6:.0f},"
                    f"{1.0/cold_process_s:.1f}")
        rows.append(f"netgen_serve_warm_store,{warm_store_s*1e6:.0f},"
                    f"{1.0/warm_store_s:.1f}")
        rows.append(f"netgen_serve_store_speedup,0,"
                    f"ratio={cold_process_s/warm_store_s:.1f};"
                    f"cold_process_us={cold_process_s*1e6:.0f};"
                    f"warm_store_us={warm_store_s*1e6:.0f}")

    # -- Figure-7-style logic-cell estimates (cost target) -------------------
    cost = netgen.compile_artifact(
        nets[0], target="cost", pipeline="zeros,prune,addends").artifact
    results["cost_fig7"] = cost.as_dict()
    for stage, cells in cost.per_pass:
        rows.append(f"netgen_cost_cells_{stage},0,{cells.total}")

    # -- pallas datapaths: dense vs packed vs planes (ISSUE 4 + 5) ----------
    psizes = (784, 500, 10) if full else sizes        # paper net under --full
    pnet = _nets(1, psizes, seed=7)[0]
    pb = 256
    px = _images(pb, psizes[0], seed=11)
    oracle = netgen.compile_artifact(pnet, target="jnp")
    forms = {"dense": netgen.compile_artifact(pnet, target="pallas"),
             "packed": netgen.compile_artifact(
                 pnet, target="pallas[packed=true]"),
             "planes": netgen.compile_artifact(
                 pnet, target="pallas[planes=true]"),
             "fusednet": netgen.compile_artifact(
                 pnet, target="pallas[fusednet=true]")}
    want = np.asarray(oracle(px))
    results["packed"] = {"sizes": list(psizes), "batch": pb}
    for form, art in forms.items():
        got = np.asarray(art(px))                    # warm + exactness
        assert np.array_equal(got, want), f"{form} diverged from jnp oracle"
        # best-of-3 means: the fusednet_vs_planes ratio below is a hard
        # acceptance gate, so each form gets the low-noise protocol the
        # telemetry-overhead section already uses
        dt = min(_timed_mean(f"pallas_{form}",
                             lambda art=art: np.asarray(art(px)), reps)
                 for _ in range(3))
        results["packed"][form] = {
            "us_per_batch": dt * 1e6, "preds_per_s": pb / dt,
            "plan_form": art.plan_form, "exact_vs_jnp": True,
        }
        rows.append(f"netgen_serve_pallas_{form}_b{pb},"
                    f"{dt*1e6:.0f},{pb/dt:.0f}")
    results["packed"]["packed_vs_dense_speedup"] = (
        results["packed"]["dense"]["us_per_batch"]
        / results["packed"]["packed"]["us_per_batch"])
    # ISSUE 5 acceptance: the bit-plane datapath beats the PR-4 packed path
    planes_vs_packed = (results["packed"]["packed"]["us_per_batch"]
                        / results["packed"]["planes"]["us_per_batch"])
    results["packed"]["planes_vs_packed_speedup"] = planes_vs_packed
    results["packed"]["planes_vs_dense_speedup"] = (
        results["packed"]["dense"]["us_per_batch"]
        / results["packed"]["planes"]["us_per_batch"])
    rows.append(f"netgen_serve_planes_vs_packed_speedup,0,"
                f"ratio={planes_vs_packed:.2f};"
                f"packed_us={results['packed']['packed']['us_per_batch']:.0f};"
                f"planes_us={results['packed']['planes']['us_per_batch']:.0f}")
    # ISSUE 9 acceptance: the whole-net megakernel beats the per-layer
    # planes chain (one launch + zero HBM round-trips for activations
    # vs depth launches) by >= 1.2x on the paper net
    fusednet_vs_planes = (results["packed"]["planes"]["us_per_batch"]
                          / results["packed"]["fusednet"]["us_per_batch"])
    results["packed"]["fusednet_vs_planes_speedup"] = fusednet_vs_planes
    rows.append(
        f"netgen_serve_fusednet_vs_planes_speedup,0,"
        f"ratio={fusednet_vs_planes:.2f};"
        f"planes_us={results['packed']['planes']['us_per_batch']:.0f};"
        f"fusednet_us={results['packed']['fusednet']['us_per_batch']:.0f}")
    if full:    # the acceptance claims are about the paper-sized net; the
        # fast-mode net is small enough for timing noise to flip ordering
        assert planes_vs_packed > 1.0, (
            f"planes datapath did not beat packed: {planes_vs_packed:.2f}x")
        assert fusednet_vs_planes >= 1.2, (
            f"fusednet megakernel did not beat the per-layer planes "
            f"chain by 1.2x: {fusednet_vs_planes:.2f}x")

    # -- persistent autotuner (ISSUE 5): search cost + tuned predictor ------
    tune_sess = netgen.Session()        # in-memory tuner (default_tuner)
    t0 = time.perf_counter()
    tuned = tune_sess.compile(pnet, target="pallas[tuned=true]")
    tune_s = time.perf_counter() - t0
    tuner = netgen.default_tuner()
    got = np.asarray(tuned(px))
    assert np.array_equal(got, want), "tuned datapath diverged from oracle"
    dt_tuned = _timed_mean("pallas_tuned",
                           lambda: np.asarray(tuned(px)), reps)
    results["tuned"] = {
        "search_ms": tune_s * 1e3,
        "plan_form": tuned.plan_form,
        "blocks": tuned.artifact.blocks,
        "us_per_batch": dt_tuned * 1e6,
        "preds_per_s": pb / dt_tuned,
        "tuner_stats": vars(tuner.stats),
    }
    rows.append(f"netgen_serve_pallas_tuned_b{pb},"
                f"{dt_tuned*1e6:.0f},{pb/dt_tuned:.0f}")
    rows.append(f"netgen_serve_tune_search,{tune_s*1e6:.0f},"
                f"{tuner.stats.measurements}")

    # -- design-space explorer (ISSUE 10): joint search vs hand-tuned -------
    # The explorer searches pipeline x datapath x tiles as ONE problem;
    # the acceptance claim is that its winner is no worse than the
    # hand-coded `pallas[tuned=true,fusednet=true]` path on the paper
    # net. Both sides get the same best-of-3 low-noise protocol.
    rep = tune_sess.explore(pnet, objective="latency", strategy="anneal",
                            budget=16 if full else 10, seed=0, batch=pb)
    spec, etgt = rep.best_config()
    explored = tune_sess.compile(pnet, target=etgt,
                                 pipeline=spec.spec_string())
    got = np.asarray(explored(px))
    assert np.array_equal(got, want), "explored config diverged from oracle"
    dt_explored = min(_timed_mean("pallas_explored",
                                  lambda: np.asarray(explored(px)), reps)
                      for _ in range(3))
    hand = tune_sess.compile(pnet, target="pallas[tuned=true,fusednet=true]")
    dt_hand = min(_timed_mean("pallas_hand_tuned",
                              lambda: np.asarray(hand(px)), reps)
                  for _ in range(3))
    explored_vs_tuned = dt_hand / dt_explored
    same_config = (explored.plan_form == hand.plan_form
                   and explored.artifact.datapath == hand.artifact.datapath
                   and explored.artifact.blocks == hand.artifact.blocks)
    results["explored"] = {
        "target": etgt, "pipeline": spec.spec_string(),
        "candidates": rep.candidates, "pruned": len(rep.pruned),
        "measured": len(rep.evaluations),
        "us_per_batch": dt_explored * 1e6,
        "hand_tuned_us_per_batch": dt_hand * 1e6,
        "explored_vs_tuned_speedup": explored_vs_tuned,
        "same_config_as_hand_tuned": same_config,
    }
    rows.append(f"netgen_explored_b{pb},"
                f"{dt_explored*1e6:.0f},{pb/dt_explored:.0f}")
    rows.append(f"netgen_explored_vs_tuned_speedup,0,"
                f"ratio={explored_vs_tuned:.2f};"
                f"tuned_us={dt_hand*1e6:.0f};"
                f"explored_us={dt_explored*1e6:.0f}")
    if full:
        # ISSUE 10 acceptance: the joint search finds a config no worse
        # than the hand-tuned fusednet path. When the search lands on
        # the *same* kernel config, "no worse" holds by definition and
        # the measured ratio is pure timing noise around 1.0.
        assert explored_vs_tuned >= 1.0 or same_config, (
            f"explored config ({etgt}) is worse than the hand-tuned "
            f"fusednet path: {explored_vs_tuned:.2f}x")

    # -- sharded vs single-device stacked serving (ISSUE 4) -----------------
    import math

    import jax
    from repro.netgen.mesh import make_host_mesh, use_mesh

    m, b = (4, 1024) if full else (2, 256)
    # the data axis must divide the slot capacity or the dispatch falls
    # back to single-device; use the largest device count that does
    n_dev = math.gcd(len(jax.devices()), b)
    shard_server = netgen.NetServer(cache=cache, slot_capacity=b)
    for i in range(m):
        shard_server.register(f"v{i}", nets[i])
    shard_reqs = {f"v{i}": _images(b, sizes[0], seed=200 + i)
                  for i in range(m)}
    single_out = shard_server.predict_many(shard_reqs)     # warm
    dt_single = _timed_mean(
        "stacked_single_device",
        lambda: shard_server.predict_many(shard_reqs), reps)
    with use_mesh(make_host_mesh(data=n_dev)):
        sharded_out = shard_server.predict_many(shard_reqs)  # warm
        dt_sharded = _timed_mean(
            "stacked_sharded",
            lambda: shard_server.predict_many(shard_reqs), reps)
    exact = all(np.array_equal(single_out[v], sharded_out[v])
                for v in shard_reqs)
    assert exact, "sharded dispatch diverged from single-device"
    assert shard_server.dispatch_counts["sharded"] > 0
    preds = m * b
    results["sharded"] = {
        "devices": n_dev, "versions": m, "batch": b, "exact": exact,
        "single_device_us": dt_single * 1e6,
        "sharded_us": dt_sharded * 1e6,
        "single_device_preds_per_s": preds / dt_single,
        "sharded_preds_per_s": preds / dt_sharded,
    }
    rows.append(f"netgen_serve_single_device_m{m}_b{b},"
                f"{dt_single*1e6:.0f},{preds/dt_single:.0f}")
    rows.append(f"netgen_serve_sharded{n_dev}_m{m}_b{b},"
                f"{dt_sharded*1e6:.0f},{preds/dt_sharded:.0f}")

    # -- stacked multi-net dispatch vs individual serving -------------------
    for m in m_versions:
        for b in batches:
            server = netgen.NetServer(cache=cache, slot_capacity=b)
            for i in range(m):
                server.register(f"v{i}", nets[i])
            reqs = {f"v{i}": _images(b, sizes[0], seed=100 + i)
                    for i in range(m)}

            out = server.predict_many(reqs)          # warm both paths
            individual = {v: np.asarray(server.compiled_for(v)(x))
                          for v, x in reqs.items()}
            exact = all(np.array_equal(out[v], individual[v]) for v in reqs)

            dt_stacked = _timed_mean(
                f"stacked_m{m}_b{b}",
                lambda: server.predict_many(reqs), reps)

            def _individual():
                for v, x in reqs.items():
                    np.asarray(server.compiled_for(v)(x))
            dt_indiv = _timed_mean(f"individual_m{m}_b{b}", _individual, reps)

            preds = m * b
            results["multi"].append({
                "versions": m, "batch": b, "exact": exact,
                "stacked_dispatch": bool(m > 1),
                "stacked_us": dt_stacked * 1e6,
                "individual_us": dt_indiv * 1e6,
                "stacked_preds_per_s": preds / dt_stacked,
                "individual_preds_per_s": preds / dt_indiv,
            })
            assert exact, f"stacked dispatch diverged at m={m} b={b}"
            rows.append(f"netgen_serve_stacked_m{m}_b{b},"
                        f"{dt_stacked*1e6:.1f},{preds/dt_stacked:.0f}")
            rows.append(f"netgen_serve_individual_m{m}_b{b},"
                        f"{dt_indiv*1e6:.1f},{preds/dt_indiv:.0f}")

    # -- telemetry overhead (ISSUE 6 acceptance) ----------------------------
    # Same paper-sized net as the datapath section, served through the
    # instrumented dispatch path with span tracing ON vs OFF. Metrics
    # are always live (they back the stats everyone reads), so "off"
    # here means what production pays by default: no span recording.
    from repro.netgen import telemetry

    ov_server = netgen.NetServer(cache=cache, slot_capacity=pb)
    ov_server.register("ov", pnet)
    ov_reqs = {"ov": px}
    ov_server.predict_many(ov_reqs)                          # warm
    ov_reps = 30 if full else 15
    was_enabled = telemetry.get_registry().enabled

    def _ov():
        ov_server.predict_many(ov_reqs)

    telemetry.disable()
    dt_off = min(_timed_mean("telemetry_off", _ov, ov_reps) for _ in range(3))
    telemetry.enable()
    dt_on = min(_timed_mean("telemetry_on", _ov, ov_reps) for _ in range(3))
    if not was_enabled:
        telemetry.disable()
    overhead = dt_on / dt_off - 1.0
    results["telemetry_overhead"] = {
        "sizes": list(psizes), "batch": pb,
        "tracing_off_us": dt_off * 1e6, "tracing_on_us": dt_on * 1e6,
        "overhead_frac": overhead,
    }
    rows.append(f"netgen_serve_telemetry_overhead,{dt_on*1e6:.1f},"
                f"{overhead*100:+.2f}%")
    # <= 5% when enabled (with a small absolute slack so a sub-ms
    # dispatch cannot fail on scheduler jitter alone)
    assert dt_on <= dt_off * 1.05 + 5e-4, (
        f"telemetry tracing overhead too high: on={dt_on*1e6:.1f}us "
        f"off={dt_off*1e6:.1f}us ({overhead*100:.1f}%)")

    # -- XLA cost analysis of the oracle -------------------------------------
    prof = telemetry.jit_cost(oracle.artifact, (pb, psizes[0]))
    if prof is not None:
        results["roofline_jit"] = {
            "target": "jnp", "sizes": list(psizes), "batch": pb, **prof}
        rows.append(f"netgen_serve_jit_cost_jnp,0,"
                    f"flops={prof['flops']:.0f};"
                    f"bytes={prof['bytes_accessed']:.0f}")

    results["telemetry"] = telemetry.summary()

    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--fake-devices", type=int, default=0, metavar="N",
                    help="fake N host devices for the sharded rows "
                         "(standalone runs only: must be set before jax "
                         "initializes)")
    ap.add_argument("--json", default=None,
                    help="write the full measurement set here (no file "
                         "is written without an explicit path)")
    args = ap.parse_args()
    if args.fake_devices:
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}")
    print("name,us_per_call,derived")
    for row in run(full=args.full, json_path=args.json):
        print(row, flush=True)


if __name__ == "__main__":
    main()
