import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the very first statements — jax locks the
device count at first initialization, and the production meshes need 512
placeholder host devices (and ONLY the dry-run may see them; tests and
benches run with 1 device).

Per cell this driver runs two kinds of lowerings:

  PRODUCTION (scan-over-layers, full grad-accum): proves the real artifact
  compiles on the mesh; memory_analysis() proves fit; post-opt HLO gives
  the collective schedule.

  ANALYSIS (multi-point, layer scans unrolled): XLA cost analysis counts
  while bodies ONCE, so flops/bytes/collectives from the production graph
  under-count by the trip counts. We therefore lower small unrolled
  variants — train: (L, accum) in {L1,L2}x{1,2}; serve: L in {L1,L2} —
  and solve the linear cost model
      cost(L, accum) = accum*(L*layer_micro + head_micro) + L*layer_opt + g
  for exact per-step totals, then add analytic corrections for the
  per-layer inner scans (flash blocks / SSD chunks) that remain rolled.

Usage:
  python -m repro.launch.dryrun --mesh single_pod [--arch A] [--shape S]
  python -m repro.launch.dryrun --mesh multi_pod  --arch qwen2-72b
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro import configs
from repro.data import specs as specs_lib
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import api, runtime
from repro.models.base import tree_sds
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.serve.engine import make_serve_step
from repro.train import step as step_lib

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")


def _rules_for(mesh) -> dict:
    if "pod" in mesh.shape:
        return {}                       # default rules already include pod
    return {"batch": ("data",)}


def _serve_params_sds(cfg, variant: dict):
    """Abstract serving params under a variant: optional dtype cast
    (fp32 master -> bf16 serving copy) and/or W8 int8 specialization."""
    import dataclasses as _dc
    import jax.numpy as jnp
    from repro.models.base import ParamInfo, is_info
    if variant.get("quant"):
        from repro.quantized.apply import abstract_quantized_params
        tree = abstract_quantized_params(cfg)
    else:
        tree = api.abstract_params(cfg)
    dt = variant.get("serve_dtype")
    if dt:
        def cast(i: ParamInfo) -> ParamInfo:
            if i.dtype == jnp.float32 and len(i.shape) >= 2:
                return _dc.replace(i, dtype=jnp.dtype(dt))
            return i
        tree = jax.tree.map(cast, tree, is_leaf=is_info)
    return tree_sds(tree)


def build_lowered(cfg, shape, mesh, *, remat: str = "full",
                  variant: dict | None = None):
    """Lower one cell's step on `mesh` (no compile). `variant` is the
    perf-hillclimb switchboard: {"flags": runtime flags, "rules": logical
    rule overrides, "serve_dtype": "bfloat16", "quant": True}."""
    from repro.models import runtime as rt
    variant = variant or {}
    rules = dict(_rules_for(mesh))
    rules.update(variant.get("rules", {}))
    with rt.with_flags(**variant.get("flags", {})), shd.use_mesh(mesh, rules):
        if shape.kind == "train":
            oc = adamw.OptConfig()
            train_step = step_lib.make_train_step(cfg, shape, oc, remat=remat)
            state_sds = tree_sds(step_lib.abstract_state(cfg))
            batch_sds = specs_lib.input_specs(cfg, shape)
            with mesh:
                return jax.jit(train_step, donate_argnums=(0,)).lower(
                    state_sds, batch_sds)
        if shape.kind == "prefill":
            params_sds = _serve_params_sds(cfg, variant)
            cache_sds = tree_sds(api.abstract_cache(
                cfg, shape.global_batch, shape.seq_len))
            batch_sds = specs_lib.input_specs(cfg, shape)

            def prefill_fn(params, batch, cache):
                return api.prefill(cfg, params, batch, cache)

            with mesh:
                return jax.jit(prefill_fn, donate_argnums=(2,)).lower(
                    params_sds, batch_sds, cache_sds)
        if shape.kind == "decode":
            params_sds = _serve_params_sds(cfg, variant)
            cache_sds = tree_sds(api.abstract_cache(
                cfg, shape.global_batch, shape.seq_len))
            io = specs_lib.input_specs(cfg, shape)
            serve_step = make_serve_step(cfg)
            with mesh:
                return jax.jit(serve_step, donate_argnums=(1,)).lower(
                    params_sds, cache_sds, io["tokens"], io["pos"])
        raise ValueError(shape.kind)


def _extract(compiled) -> dict:
    cost = compiled.cost_analysis()
    colls = rl.collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(v for k, v in colls.items() if not k.startswith("_"))),
        "breakdown": colls,
    }


def _reduced(cfg, n_layers: int):
    return dataclasses.replace(cfg, n_layers=n_layers)


def _analysis_Ls(cfg) -> tuple:
    if cfg.family == "hybrid":
        return cfg.attn_every, 2 * cfg.attn_every
    return 2, 4


def analyze_cell(cfg, shape, mesh, *, remat: str = "full",
                 variant: dict | None = None) -> dict:
    """Multi-point unrolled lowerings -> exact per-step cost totals."""
    L1, L2 = _analysis_Ls(cfg)

    def measure(L, accum_override=None, batch_override=None):
        c = _reduced(cfg, L)
        s = shape
        if accum_override is not None:
            s = dataclasses.replace(shape, accum=accum_override,
                                    global_batch=batch_override)
        with runtime.unrolled_scans():
            lowered = build_lowered(c, s, mesh, remat=remat, variant=variant)
            return _extract(lowered.compile())

    out = {}
    if shape.kind == "train":
        micro = shape.global_batch // shape.accum
        A = measure(L1, 1, micro)
        B = measure(L2, 1, micro)
        C = measure(L1, 2, 2 * micro)
        D = measure(L2, 2, 2 * micro)
        dL = L2 - L1
        for key in ("flops", "bytes", "coll"):
            lm = ((D[key] - C[key]) - (B[key] - A[key])) / dL
            hm = (C[key] - A[key]) - L1 * lm
            lo = (B[key] - A[key]) / dL - lm
            g = A[key] - (L1 * lm + hm) - L1 * lo
            out[key] = (shape.accum * (cfg.n_layers * lm + hm)
                        + cfg.n_layers * lo + g)
        corr_batch = micro
        scale_corr = shape.accum
    else:
        A = measure(L1)
        B = measure(L2)
        dL = L2 - L1
        for key in ("flops", "bytes", "coll"):
            per_layer = (B[key] - A[key]) / dL
            out[key] = A[key] + (cfg.n_layers - L1) * per_layer
        corr_batch = shape.global_batch
        scale_corr = 1

    corr = rl.inner_scan_corrections(
        cfg, batch=corr_batch, seq=shape.seq_len, kind=shape.kind)
    chips = mesh.devices.size
    out["flops"] += scale_corr * corr["flops"] / chips
    out["bytes"] += scale_corr * corr["bytes"] / chips
    out["corrections_per_device"] = {
        k: scale_corr * v / chips for k, v in corr.items()}
    return out


def run_cell(cfg, shape, mesh, *, remat: str = "full", analysis: bool = True,
             verbose: bool = True, variant: dict | None = None) -> tuple:
    """Production compile + (optional) analysis. Returns (record, meta)."""
    lowered = build_lowered(cfg, shape, mesh, remat=remat, variant=variant)
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    raw = _extract(compiled)

    ana = (analyze_cell(cfg, shape, mesh, remat=remat, variant=variant)
           if analysis else None)
    eff = ana if ana is not None else raw

    chips = mesh.devices.size
    record = rl.Roofline(
        arch=cfg.name,
        shape=shape.name,
        mesh="x".join(str(s) for s in mesh.devices.shape),
        chips=chips,
        flops_per_device=eff["flops"],
        bytes_per_device=eff["bytes"],
        collective_bytes=eff["coll"],
        collective_breakdown=raw["breakdown"],
        model_flops=rl.model_flops(cfg, shape),
        # memory_analysis (like cost_analysis) reports PER-DEVICE numbers
        # on a GSPMD-partitioned executable.
        peak_mem_per_device=float(
            (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)),
    )
    meta = {
        "compile_s": compile_s,
        "arg_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "out_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "raw_scan_counted_once": raw,
    }
    if verbose:
        print(f"  memory_analysis: args={meta['arg_bytes']/2**30:.2f}GiB "
              f"temp={meta['temp_bytes']/2**30:.2f}GiB "
              f"alias={meta['alias_bytes']/2**30:.2f}GiB "
              f"-> peak/device={record.peak_mem_per_device/2**30:.3f}GiB")
        print(f"  per-step/device: flops={record.flops_per_device:.3e} "
              f"bytes={record.bytes_per_device:.3e} "
              f"coll={record.collective_bytes:.3e} "
              f"({raw['breakdown'].get('_num_ops', 0)} coll ops in HLO)")
        print(f"  roofline: t_comp={record.t_compute*1e3:.2f}ms "
              f"t_mem={record.t_memory*1e3:.2f}ms "
              f"t_coll={record.t_collective*1e3:.2f}ms "
              f"bottleneck={record.bottleneck} "
              f"frac={record.roofline_fraction:.3f} "
              f"useful={record.useful_flops_ratio:.3f}")
    return record, meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single_pod", "multi_pod"],
                    default="single_pod")
    ap.add_argument("--arch", default=None, help="run one arch only")
    ap.add_argument("--shape", default=None, help="run one shape only")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--no-analysis", action="store_true",
                    help="production compile only (multi-pod proof runs)")
    ap.add_argument("--serve-opt", action="store_true",
                    help="serve cells use the optimized inference config "
                         "(bf16 serving copy, TP-only weights — §Perf)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi_pod"))
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(RESULTS_DIR, f"dryrun_{args.mesh}{args.tag}.json")
    done: dict[str, dict] = {}
    if os.path.exists(out_path) and not args.force:
        with open(out_path) as f:
            done = {r["cell"]: r for r in json.load(f)}

    cells = configs.all_cells()
    if args.arch:
        cells = [(c, s) for c, s in cells if c.name == args.arch]
    if args.shape:
        cells = [(c, s) for c, s in cells if s.name == args.shape]

    n_fail = 0
    for cfg, shape in cells:
        key = f"{cfg.name}/{shape.name}"
        if key in done and done[key].get("ok"):
            print(f"[skip] {key}")
            continue
        print(f"[cell] {key} on {args.mesh} "
              f"(B={shape.global_batch}, S={shape.seq_len}, {shape.kind})",
              flush=True)
        t0 = time.time()
        variant = None
        if args.serve_opt and shape.kind in ("prefill", "decode"):
            variant = {"serve_dtype": "bfloat16", "rules": {"fsdp": ()}}
        try:
            record, meta = run_cell(cfg, shape, mesh, remat=args.remat,
                                    analysis=not args.no_analysis,
                                    variant=variant)
            done[key] = {"cell": key, "ok": True, **record.as_dict(), **meta}
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            traceback.print_exc()
            done[key] = {"cell": key, "ok": False,
                         "error": f"{type(e).__name__}: {e}"}
            n_fail += 1
        print(f"  [{time.time()-t0:.1f}s total]", flush=True)
        with open(out_path, "w") as f:
            json.dump(list(done.values()), f, indent=1, default=float)

    ok = sum(1 for r in done.values() if r.get("ok"))
    print(f"\n== {ok}/{len(done)} cells OK ({n_fail} new failures) -> {out_path}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
