"""`repro.netgen` — the paper's net-to-hardware step as a real compiler.

The source paper's central artifact (§IV-§V) is a Python script that
walks a trained 784-500-10 net and prints a clockless Verilog module,
applying structural optimizations on the way. This package generalizes
that script into a small compiler over a typed circuit IR, so the same
rewrites serve arbitrary-depth nets and multiple execution targets:

    frontend.lower        quantized N-layer stack -> circuit IR
    PipelineSpec          declarative pass pipeline ("zeros,prune,...")
    plan.lower_circuit    optimized circuit -> ExecutionPlan, the ONE
                          layer-structured tensor lowering every array
                          backend executes (dense / bit-packed /
                          stacked multi-net forms)
    Target registry       IR -> artifact (jitted fn, Verilog text,
                          logic-cell cost report)
    Session + ArtifactStore   compile once per content, persist across
                          processes

Paper-section map
-----------------
  §III.B / Fig. 6 line 5   -> graph.InputCompare (pixel > threshold)
  §III.A step activation   -> graph.SignStep
  §V.D MSB sign-bit trick  -> SignStep emission in backends/verilog.py
                              (and the strict-vs-msb semantics note in
                              graph.evaluate)
  §V.B zero-weight pruning -> passes.delete_zero_terms (per-term) and
     (L4, ~50% cell cut)      passes.prune_dead_units (per-unit)
  §V.C multiplication-free -> passes.addend_rewrite (w*x -> |w| addends;
     (L5, 38k -> <16k cells)  after it, ops().mults == 0)
  beyond the paper         -> passes.share_common_addends (adder CSE;
                              `cse[bucketed=true]` scales it to the full
                              784-input net), the `cost` target
                              (Figure-7-style logic-cell estimates)
  Fig. 6 line 15 argmax    -> graph.Argmax, emitted as a priority mux
  Fig. 6/7 module shape    -> backends/verilog.py "legacy" style
                              (byte-compatible with the seed emitter)

Quick use
---------
Compilation goes through a `Session`: pick a target (an execution
backend from the registry — `netgen.list_targets()` enumerates them)
and a pipeline (a named or declarative `PipelineSpec`), get back an
`Artifact` carrying the optimized circuit, per-pass stats, a logic-cell
estimate, timings, and the artifact itself:

    from repro.core.quantize import quantize
    from repro import netgen

    session = netgen.Session(store=netgen.ArtifactStore("./netgen-store"))
    art = session.compile(quantize(params), target="jnp")
    preds = art(images_uint8)            # bit-exact vs predict_l3
    print(art.report())                  # per-pass savings + cell count

    verilog = session.compile(qnet, target="verilog", pipeline="hw").artifact
    cost = session.compile(qnet, target="cost", pipeline="hw").artifact
    print(cost.report())                 # per-pass cells vs paper Fig. 7

Pipelines are declarative strings — `"zeros,prune"` (named: "default"),
`"zeros,prune,addends,cse[budget=5000,bucketed=true]"` (named "hw" in
its unbudgeted form) — that round-trip through `PipelineSpec.parse` and
fingerprint stably, so they key the store. Because the store is
content-addressed by `QuantizedNet.digest()` x
`PipelineSpec.fingerprint()` x target, a SECOND process pointed at the
same directory warm-starts every artifact without recompiling.

`compile_net(...)` is the pre-Session entry point; it still works but
is deprecated and routed through a default Session.

Execution plans (the array-backend lowering)
--------------------------------------------
`repro.netgen.plan.lower_circuit` turns an optimized circuit into an
`ExecutionPlan` — per-layer weight matrices, activation kind, input
threshold, final argmax — and every array backend (jnp / pallas /
fused) is a thin executor over it. `plan.pack()` is the bit-packed
form: ±1-weighted single-bit activations travel 32-per-uint32 word
into `kernels.binary_matvec.binary_matmul_packed` (the paper's
single-bit wires, on the TPU), selected with `pallas[packed=true]`,
chained packed end-to-end (the step emits packed words — no int8
activation between layers) and bit-exact with the dense path.
`plan.planes()` goes further (`pallas[planes=true]`): each weight
matrix is decomposed into packed signed bit-planes
(`decompose_planes`, w = sum_b 2^b (pos_b - neg_b)) and accumulated by
popcount in `binary_matmul_planes` — both operands travel as bits,
with the plane count set by the layer's actual post-pass weight
magnitudes. `plan.stack_plans` joins M compatible plans along a model
axis for the serving layer. `pallas[fusednet=true]` is the planes form
taken to its limit: `plan.megakernel_view()` flattens the whole net
(hidden fan_outs pre-padded to the next layer's word width) and
`binary_forward_planes` runs EVERY layer in one persistent Pallas
launch — weights resident in VMEM, strict step + repack in-register
between layers (inter-layer activations never touch HBM), argmax fused
— one launch per forward instead of one per layer. Artifacts record
the compiled form (`artifact.plan_form`), the datapath
(`artifact.datapath`) and launch count (`artifact.launches_per_call`),
and re-derive the plan via `artifact.plan()`.

Autotuning (`repro.netgen.tune`): `pallas[tuned=true]` grid-searches
the kernel block sizes (bm, bn, bkw) — and the datapath form, unless
pinned — per plan shape x device kind; `fused[tuned=true]` searches
its batch tile. `Session(tune_store=...)` persists the winners
content-addressed (a second process performs ZERO tuning
measurements); `session.tune_stats()` shows hits vs measurements.

Design-space exploration (`repro.netgen.explore`)
-------------------------------------------------
The paper's levers — pass pipeline, datapath form, kernel tile sizes —
interact, so `Session.explore(...)` searches them as ONE optimization
problem: a seeded `Explorer` ("random" permutation or simulated
annealing) over a `SearchSpace` of pipeline spec strings x
dense/packed/planes/fusednet x (bm, bn, bkw) tiles x optionally
several nets (the ladder-depth axis), under a pluggable lower-is-
better objective ("latency", deterministic "cells" from the Fig-7
estimate, "combined", or `make_objective(fn, name=...)`). Illegal
candidates are pruned BEFORE measurement through the shared
`analysis.tile_legality` / `IrregularCircuitError` checks; every
measured candidate compiles through the Session (artifacts persist in
the ArtifactStore) and the whole search persists as one content-
addressed `TuneRecord`, so a second process replays the returned
`ExplorationReport` with zero measurements and zero compiles.
`pallas[explored=true]` resolves the persisted winner for a plan
shape, and the serving layer's stacked dispatch prefers it over the
hand-coded form precedence (`NetServer(prefer_explored=...)`):

    rep = session.explore(qnet, objective="latency", budget=24, seed=0)
    spec, target = rep.best_config()
    art = session.compile(qnet, target=target, pipeline=spec)
    print(rep.describe())            # candidates / pruned / winner

Serving (compile cache + multi-version dispatch + mesh sharding)
----------------------------------------------------------------
`repro.netgen.serve` makes the compile-per-model-then-serve workflow
operational: `CompileCache` is the Session's in-memory tier (same
content addressing, LRU, thread-safe), and a `NetServer` routes request
batches — cross-model batches of stack-compatible versions run as ONE
jitted multi-net dispatch, and when a device mesh with a data axis is
active (`repro.parallel.sharding.use_mesh`) that dispatch shards its
slot dimension across the mesh via `shard_map` (single-device fallback
otherwise):

    session = netgen.Session(store=netgen.ArtifactStore(cache_dir),
                             tune_store=tune_dir)
    handle = session.compile_async(qnet, target="pallas[tuned=true]")
    server = netgen.NetServer(session=session, slot_capacity=64)
    server.register("v1", qnet)              # warm: async compile + store
    server.register("v1-replica", qnet)      # memory hit, ~us
    out = server.predict_many({"v1": imgs_a, "v2": imgs_b})
    print(session.stats().row())             # hits/misses/compile time

    with shd.use_mesh(make_host_mesh(data=8)):    # 8-way batch sharding
        out = server.predict_many({"v1": imgs_a, "v2": imgs_b})

Online serving (`repro.netgen.engine`) is the async front door over
that dispatch: clients `submit()` SINGLE uint8 requests (getting a
Future) or call the blocking `infer()`, and a batcher thread performs
continuous slot formation — fill a slot block or wait `max_batch_delay`,
whichever first — grouping stack-compatible versions into one stacked
dispatch per round. SLO knobs: `max_batch_delay`, `max_queue_depth`
(explicit `QueueFullError` rejection), per-request `deadline`
(`DeadlineExceededError`); exiting the context manager drains the queue:

    with session.engine(slot_capacity=256, max_batch_delay=0.002) as eng:
        eng.register("v1", qnet)
        label = eng.submit("v1", image).result()   # or eng.infer(...)

See `benchmarks/bench_netgen_serve.py` for cold-vs-warm,
cold-process-vs-warm-store, and stacked-vs-individual numbers,
`benchmarks/bench_netgen_engine.py` for the closed/open-loop (Poisson)
p50/p99/throughput sweep of the engine vs one-request-per-dispatch, and
the top-level README.md for the end-to-end quickstart.

Static analysis & verification (`repro.netgen.analysis`)
--------------------------------------------------------
The invariants the paper's Verilog relies on — exact accumulator
ranges, sound bit-widths, lossless packed/bit-plane lowering — are
machine-checked instead of assumed:

    verify_circuit(c)     structural IR verifier: DAG well-formedness,
                          src validity, kind-specific invariants, and
                          per-pass postconditions ("no zero-weight
                          terms after zeros", ...)
    analyze_ranges(c)     interval dataflow: per-node exact [lo, hi]
                          plus the magnitude bound that sizes widths —
                          proves every accumulator fits its emitted
                          `signed_width` (subsumes `value_bounds` /
                          `evaluate(check_widths=True)`)
    verify_plan(p)        ExecutionPlan certification: chain shapes,
                          packed-padding exactness, `decompose_planes`
                          losslessness, int32 popcount-accumulation
                          safety (also `plan.verify()`)
    diagnose_stack(cs)    structured stack-compatibility report (the
                          NetServer records it as `stack_report()`
                          instead of silently falling back)

Wiring: `PipelineSpec.run(verify=True)` checks the full suite at every
pass boundary (default follows the NETGEN_VERIFY env var — on in
tests/CI, off in prod, where violations count
`netgen_verify_failures_total` instead of raising);
`Session.compile_resolved` runs one pre-backend analysis, hands the
proven widths to the verilog/cost backends (`Target.wants_analysis`),
and records a proof summary on the Artifact (`artifact.analysis`,
persisted in meta.json, shown by `artifact.report()`); the kernel
tuner statically rejects illegal/duplicate tile candidates before
measuring them (`analysis.tile_legality`); and
`python -m repro.netgen.analysis <store-dir>` lints every artifact in
an ArtifactStore, failing on corrupt, stale, or unsound entries.

Observability (`repro.netgen.telemetry`)
----------------------------------------
Every layer above reports into one zero-dependency, thread-safe
registry: counters/gauges/histograms are ALWAYS live (they back
`CacheStats` / `StoreStats` / `TuneStats` / `NetServer.dispatch_counts`
atomically), while nested trace spans are opt-in:

    from repro.netgen import telemetry
    telemetry.enable(profile=True)   # spans on + jit cost_analysis/artifact
    ... compile and serve ...
    print(telemetry.report())        # human table: metrics + span totals
    telemetry.prometheus()           # text exposition (scrape or file)
    telemetry.export_jsonl(path)     # one finished span per line
    telemetry.summary()              # JSON-stable dict (BENCH_netgen.json)
    telemetry.disable(); telemetry.reset()

API surface: `counter/gauge/histogram(name, **labels)` (get-or-create;
histograms have exact nearest-rank `p50/p95/p99`), `span(name, **attrs)`
(nested per-thread; no-op context unless enabled), `timed(name,
**labels)` (time a block into a histogram — the benches use this),
`jit_cost(fn, shape)` (XLA flops/bytes for roofline rows),
`new_scope(prefix)` (per-instance label), `get_registry()`. The traced
span tree and metric names are documented in the telemetry module
docstring; `examples/mnist_fpga_pipeline.py --trace DIR` shows the
whole thing end to end. The serving path's spans split a launch (a
run of 2^k whole slot rounds; one round for a call of at most
`slot_capacity` rows per version) into `netgen.round.stage` (padding
the slot block), then under `netgen.kernel` `netgen.round.launch` (the
predictor call) and `netgen.round.fetch` (the blocking result copy);
`netgen_slot_rounds_total` counts the rounds served; the engine's batcher
adds `netgen.engine.form` (waiting for and forming a batch),
`netgen.engine.admit` (deadlines, grouping, stacking its rows) and
`netgen.engine.resolve` (setting its futures) beside
`netgen.engine.batch`. While tracing is on each span is also a
`jax.profiler.TraceAnnotation`, so a profiler trace shows it on the
profiler's clock. `benchmarks/bench_netgen_serve.py` measures tracing on
against off on the host; it asserts nothing about the off path.

`repro.core.netgen` remains as a thin compatibility shim with the old
`specialize` / `emit_verilog` / `prune` / `stats` names.
"""
from __future__ import annotations

import dataclasses
import warnings

from repro.netgen import analysis, backends, telemetry
from repro.netgen.analysis import (
    Diagnostic, RangeAnalysis, StackReport, VerificationError,
    analyze_ranges, diagnose_stack, verify_circuit, verify_plan,
)
from repro.netgen.backends.cost import CellCounts, CostReport
from repro.netgen.explore import (
    Candidate, ExplorationReport, Explorer, Objective, SearchSpace,
    make_objective,
)
from repro.netgen.frontend import lower
from repro.netgen.graph import (
    Argmax, Circuit, InputCompare, IrregularCircuitError, SignStep, Term,
    WeightedSum, as_layered_weights, circuit_from_arrays, circuit_to_arrays,
    evaluate, node_widths,
)
from repro.netgen.passes import (
    DEFAULT_PASSES, HW_PASSES, CircuitOps, Pass, PassStats, addend_rewrite,
    delete_zero_terms, ops, prune_dead_units, run_pipeline,
    share_common_addends,
)
from repro.netgen.pipeline import (
    PipelineSpec, list_passes, list_pipelines, register_pass,
    register_pipeline,
)
from repro.netgen.plan import (
    ExecutionPlan, PlanLayer, decompose_planes, lower_circuit, stack_plans,
)
from repro.netgen.session import (
    Artifact, ArtifactStore, Session, compile_artifact,
)
from repro.netgen.session import _validate_batch  # noqa: F401  (serving)
from repro.netgen.targets import (
    Target, list_targets, register_target, resolve_target,
)
from repro.netgen.tune import (
    KernelTuner, TuneRecord, TuneStats, TuneStore, default_tuner,
)

__all__ = [
    "Argmax", "Artifact", "ArtifactStore", "CacheKey", "Candidate",
    "CellCounts", "Circuit", "CircuitOps", "CompileCache", "CompiledNet",
    "CostReport", "DEFAULT_PASSES", "DeadlineExceededError", "Diagnostic",
    "EngineClosedError", "EngineStats", "ExecutionPlan",
    "ExplorationReport", "Explorer", "HW_PASSES", "InputCompare",
    "IrregularCircuitError", "KernelTuner", "NetServer", "Objective",
    "Pass", "PassStats", "PipelineSpec", "PlanLayer", "QueueFullError",
    "RangeAnalysis", "SearchSpace", "ServingEngine", "Session", "SignStep",
    "StackReport", "Target", "Term", "TuneRecord", "TuneStats",
    "TuneStore", "VerificationError", "WeightedSum", "addend_rewrite",
    "analysis", "analyze_ranges", "as_layered_weights", "backends",
    "cached_compile_net", "circuit_from_arrays", "circuit_to_arrays",
    "compile_artifact", "compile_net", "decompose_planes",
    "default_session", "default_tuner", "delete_zero_terms",
    "diagnose_stack", "emit_verilog", "engine", "evaluate",
    "list_passes", "list_pipelines", "list_targets", "lower",
    "lower_circuit", "make_objective", "node_widths", "ops",
    "prune_dead_units",
    "register_pass", "register_pipeline", "register_target",
    "resolve_target", "run_pipeline", "serve", "share_common_addends",
    "specialize", "stack_layered_weights", "stack_plans", "telemetry",
    "verify_circuit", "verify_plan",
]


@dataclasses.dataclass(frozen=True)
class CompiledNet:
    """Result of one end-to-end compilation through the deprecated
    `compile_net` shim: the optimized circuit, the per-pass statistics,
    and the backend artifact (a jitted callable for jnp/pallas/fused,
    the module source string for verilog). New code should hold the
    richer `Artifact` a `Session.compile` returns."""
    circuit: Circuit
    pass_stats: tuple[PassStats, ...]
    backend: str
    artifact: object

    def __call__(self, x_uint8):
        if not callable(self.artifact):
            raise TypeError(
                f"{self.backend} artifact is not callable (use .artifact)")
        _validate_batch(x_uint8, self.circuit.n_inputs)
        return self.artifact(x_uint8)

    def report(self) -> str:
        """Human-readable per-pass savings table."""
        return "\n".join(s.row() for s in self.pass_stats)


_DEFAULT_SESSION: Session | None = None


def default_session() -> Session:
    """The process-wide Session the deprecated entry points route
    through (memory tier only; configure your own Session for a
    persistent ArtifactStore)."""
    global _DEFAULT_SESSION
    if _DEFAULT_SESSION is None:
        _DEFAULT_SESSION = Session(capacity=16)
    return _DEFAULT_SESSION


def compile_net(
    net,
    *,
    backend: str = "jnp",
    passes=None,
    input_threshold: int | None = None,
    **backend_opts,
) -> CompiledNet:
    """Deprecated: use `Session.compile(net, target=..., pipeline=...)`.

    Kept as a thin shim routed through the default Session. `net` is
    anything `frontend.lower` accepts (a QuantizedNet of any depth, an
    object with `.weights`, or a list of integer matrices). `passes`
    accepts the old pass-callable sequences as well as PipelineSpec /
    spec strings; None means the "default" pipeline. Pass sequences a
    `PipelineSpec` cannot represent (closures, repeated passes) still
    compile — directly and uncached, exactly as the pre-Session
    `compile_net` did.
    """
    warnings.warn(
        "netgen.compile_net is deprecated; use netgen.Session(...).compile("
        "net, target=..., pipeline=...) — see the repro.netgen docstring",
        DeprecationWarning, stacklevel=2)
    try:
        spec = PipelineSpec.coerce(passes)
    except ValueError:
        # unrepresentable legacy pipeline: compile the old way (no cache)
        circuit = lower(net, input_threshold=input_threshold)
        circuit, stats = run_pipeline(circuit, passes)
        artifact = backends.compile_circuit(circuit, backend, **backend_opts)
        return CompiledNet(circuit=circuit, pass_stats=stats,
                           backend=backend.partition("[")[0],
                           artifact=artifact)
    art = default_session().compile(
        net, target=backend, pipeline=spec,
        input_threshold=input_threshold, **backend_opts)
    return CompiledNet(
        circuit=art.circuit, pass_stats=art.pass_stats,
        backend=art.backend, artifact=art.artifact)


def specialize(net, *, backend: str = "jnp", passes=None, pipeline=None, **kw):
    """Compile and return just the jitted predictor (old netgen name)."""
    return default_session().compile(
        net, target=backend,
        pipeline=pipeline if pipeline is not None else passes, **kw).artifact


def emit_verilog(net, *, addend: bool = True, module_name: str = "nn_inference",
                 passes=None) -> str:
    """Compile and return just the Verilog source (old netgen name).

    Matches the seed emitter's behavior: zero terms are always dropped at
    generation time; `addend=True` additionally applies the L5 rewrite.
    """
    if passes is None:
        passes = "zeros,addends" if addend else "zeros"
    return default_session().compile(
        net, target="verilog", pipeline=passes,
        module_name=module_name, addend=addend).artifact


# Serving layer (imported last: it builds on the session machinery).
from repro.netgen import serve  # noqa: E402
from repro.netgen.serve import (  # noqa: E402
    CacheKey, CompileCache, NetServer, cached_compile_net,
    stack_layered_weights,
)
from repro.netgen import engine  # noqa: E402  (builds on serve)
from repro.netgen.engine import (  # noqa: E402
    DeadlineExceededError, EngineClosedError, EngineStats, QueueFullError,
    ServingEngine,
)
