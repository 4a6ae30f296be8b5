"""Compile-cache serving of netgen-specialized predictors.

The paper's economics (§IV-§V) are compile-per-model-then-serve: the
expensive step is specializing a trained net into a fixed circuit; the
cheap step is running it. This module makes that split operational, the
ROADMAP's "Serving specialized programs" item:

  CompileCache — the in-memory tier of the Session API. The key is the
      sha256 digest of the quantized weights + input threshold
      (`repro.core.quantize.weights_digest`) crossed with the canonical
      `PipelineSpec` and `Target` strings. A hit returns the *same*
      `Artifact` object that was compiled before; a miss consults the
      optional persistent `ArtifactStore` (so a second process
      warm-starts without recompiling), then compiles, records
      wall-clock compile time, persists, and LRU-evicts past a fixed
      capacity. Thread-safe: the lock covers lookup/insert only, a
      per-key in-flight future coalesces concurrent requests for the
      same key onto one compile, and compiles on unrelated keys never
      block each other (no head-of-line blocking).

  NetServer — a multi-version predictor server in the style of
      `repro.serve.engine`: fixed-capacity slot batching (one live jit
      trace per model), per-request routing by version name, and
      *cross-model* batching: versions whose circuits lower to
      compatible ExecutionPlans are stacked along a model axis
      (`repro.netgen.plan.stack_plans`) and served by one jitted
      multi-net dispatch (the target's `compile_multi` form, with the
      server's declared target options — interpret, packed — forwarded
      through the registry) — M versions, one XLA call. For the
      bit-plane datapath (`pallas[planes=true]` / `fusednet=true`) the
      stacked dispatch is the whole-net megakernel: one persistent
      Pallas launch per dispatch for all M versions and every
      layer, recorded on the `netgen.kernel` span (form/launches) and
      in `netgen_kernel_launches_total{form}`. A call of more rows
      than one slot round holds is served in few launches, each a run
      of whole slot rounds (a power of two of them, at most
      `MAX_ROUNDS_PER_LAUNCH`), fetched once each; a call of at most
      `slot_capacity` rows per version is one round, one launch.
      When a device
      mesh with a data axis is active (`repro.parallel.sharding
      .use_mesh`), the stacked dispatch additionally shards its slot
      (batch) dimension across the mesh with `shard_map` — the
      predictions of a slot block are row-independent, so each device
      serves `slot_capacity / n_data` rows of every version — and
      falls back to the single-device dispatch when no mesh is active,
      the mesh has no data axis, or the capacity does not divide.
      A NetServer can be built over a `Session`
      (`NetServer(session=Session(store=...))`) to share its memory
      tier and persistent store, or over legacy backend/passes/cache
      keywords.

Hidden-width padding used for stacking is exact: a zero-padded column is
an empty accumulator, and under the strict step semantics step(0) = 0,
so padded units contribute nothing downstream (their outgoing rows are
zero-padded too).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import Sequence

import numpy as np

from repro.core.quantize import weights_digest
from repro.netgen import telemetry
from repro.netgen.backends import compile_multi
from repro.netgen.frontend import _extract_weights
from repro.netgen.graph import Circuit, IrregularCircuitError
from repro.netgen.pipeline import PipelineSpec
from repro.netgen.plan import lower_circuit, stack_plans
from repro.netgen.session import (
    Artifact, ArtifactStore, _validate_batch, artifact_key, compile_resolved,
)
from repro.netgen.targets import resolve_target, target_string
from repro.serve.slots import pad_slots

__all__ = [
    "CacheCounters", "CacheKey", "CacheStats", "CompileCache",
    "DEFAULT_CACHE", "MAX_ROUNDS_PER_LAUNCH", "NetServer",
    "cached_compile_net", "stack_layered_weights",
]

# The most slot rounds one launch covers. Each launch pays a host cost
# of its own (the dispatch and the blocking fetch of its result) beside
# the transfer of its input, so a multi-round call is served in
# launches of 2^k whole rounds. 64 pays the per-launch part once per up
# to 64 rounds, and bounds what the mechanism costs: at most 7 compiled
# programs per version set (1, 2, 4 ... 64 rounds) and one launch's
# input at 64 * slot_capacity * n_in bytes per version (12.8 MB at
# 256 x 784, 50 MB at 256 x 3,072).
MAX_ROUNDS_PER_LAUNCH = 64


# ---------------------------------------------------------------------------
# Content-addressed compile cache
# ---------------------------------------------------------------------------

def _pass_fingerprint(p) -> str:
    """Canonical spec item for one pass callable (registry name plus
    bracketed options, e.g. `cse[budget=2]`). `functools.partial` of a
    registered pass maps its bound keywords back to declared options, so
    a budgeted variant does not alias the unbudgeted one.

    Lambdas and closures are refused (by `PipelineSpec.from_passes`):
    their qualified name does not cover their captured state, so two
    different ones would alias to the same key and the cache would hand
    back a predictor compiled with the OTHER pipeline. Spell
    parameterized passes declaratively (`"cse[budget=5]"`) or as
    functools.partial of a registered module-level function.
    """
    return PipelineSpec.from_passes([p]).spec_string()


@dataclasses.dataclass(frozen=True)
class CacheKey:
    """What a compiled predictor is a function of: weight content digest,
    target name, canonical pipeline spec, and target options."""
    digest: str
    backend: str
    passes: str
    opts: tuple


@dataclasses.dataclass
class CacheStats:
    """Point-in-time snapshot of a compile tier's counters (see
    `CacheCounters` for the live, atomic backing metrics)."""
    hits: int = 0              # memory-tier hits
    misses: int = 0            # memory-tier misses (store hit OR compile)
    evictions: int = 0
    compile_seconds: float = 0.0   # total wall-clock spent compiling
    compiles: int = 0          # actual full compilations
    store_hits: int = 0        # misses served by the persistent store
    load_seconds: float = 0.0  # wall-clock spent loading from the store
    failures: int = 0          # misses whose compile raised (verify/backend)

    def row(self) -> str:
        return (f"cache: {self.hits} hits, {self.misses} misses "
                f"({self.store_hits} from store, {self.failures} failed), "
                f"{self.evictions} evictions, "
                f"{self.compile_seconds * 1e3:.1f} ms compiling, "
                f"{self.load_seconds * 1e3:.1f} ms loading")


class CacheCounters:
    """The live telemetry metrics behind one compile tier's `CacheStats`
    — atomic `telemetry.Counter`s plus two duration histograms, labelled
    with a process-unique `cache=` scope so two tiers never merge in the
    shared registry. `CompileCache` and the uncached `Session` path both
    mutate these (increments are race-free without the owner's lock);
    `snapshot()` is the dataclass read API everything else consumes."""

    __slots__ = ("scope", "hits", "misses", "evictions", "compiles",
                 "store_hits", "failures", "compile_seconds", "load_seconds")

    def __init__(self, scope: str | None = None,
                 registry: "telemetry.Registry | None" = None):
        tel = registry if registry is not None else telemetry.get_registry()
        self.scope = scope if scope is not None else telemetry.new_scope(
            "cache")
        self.hits = tel.counter("netgen_cache_hits_total", cache=self.scope)
        self.misses = tel.counter(
            "netgen_cache_misses_total", cache=self.scope)
        self.evictions = tel.counter(
            "netgen_cache_evictions_total", cache=self.scope)
        self.compiles = tel.counter(
            "netgen_cache_compiles_total", cache=self.scope)
        self.store_hits = tel.counter(
            "netgen_cache_store_hits_total", cache=self.scope)
        # Misses that ended in a raised compile (e.g. a VerificationError
        # from the pre-backend analysis): the third leg of the identity
        # misses == compiles + store_hits + failures that the CI metrics
        # gate (benchmarks/check_trace.py) holds per cache scope.
        self.failures = tel.counter(
            "netgen_cache_compile_failures_total", cache=self.scope)
        self.compile_seconds = tel.histogram(
            "netgen_cache_compile_seconds", cache=self.scope)
        self.load_seconds = tel.histogram(
            "netgen_cache_load_seconds", cache=self.scope)

    def snapshot(self) -> CacheStats:
        return CacheStats(
            hits=int(self.hits.value),
            misses=int(self.misses.value),
            evictions=int(self.evictions.value),
            compiles=int(self.compiles.value),
            store_hits=int(self.store_hits.value),
            failures=int(self.failures.value),
            compile_seconds=float(self.compile_seconds.sum),
            load_seconds=float(self.load_seconds.sum))


class _InFlight:
    """One in-progress compile: waiters block on the event instead of on
    the cache lock, so a cold compile of key A never serializes hits (or
    other compiles) on unrelated keys behind it."""

    __slots__ = ("event", "error")

    def __init__(self):
        self.event = threading.Event()
        self.error: BaseException | None = None


class CompileCache:
    """LRU-bounded, thread-safe, content-addressed compile cache — the
    in-memory tier over an optional persistent `ArtifactStore`.

    Compiles run OUTSIDE the cache lock: the lock covers only lookup and
    insert, while a per-key in-flight future makes concurrent requests
    for the same key coalesce onto one compile. Requests for other keys
    proceed concurrently — a cold compile cannot head-of-line-block a
    hit on an unrelated key (the admission path of the serving engine
    routes every request through here, so this matters under load)."""

    def __init__(self, capacity: int = 32, store: ArtifactStore | None = None,
                 tuner=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.store = store
        self.tuner = tuner       # forwarded to wants_tuner target compiles
        self._lock = threading.RLock()
        self._entries: "OrderedDict[CacheKey, Artifact]" = OrderedDict()
        self._inflight: dict[CacheKey, _InFlight] = {}
        self._compile_seconds: dict[CacheKey, float] = {}
        self._counters = CacheCounters()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: CacheKey) -> bool:
        with self._lock:
            return key in self._entries

    def keys(self) -> list[CacheKey]:
        with self._lock:
            return list(self._entries)

    def stats(self) -> CacheStats:
        """Snapshot of the hit/miss/eviction counters (atomic; safe to
        read while other threads compile)."""
        return self._counters.snapshot()

    def compile_seconds(self, key: CacheKey) -> float | None:
        """Recorded compile time of a resident entry (None if evicted)."""
        with self._lock:
            return self._compile_seconds.get(key)

    def _resolve(self, net, backend, passes, input_threshold, backend_opts):
        ws, thr = _extract_weights(net, input_threshold)
        spec = PipelineSpec.coerce(passes)
        tgt, opts = resolve_target(backend, backend_opts)
        key = CacheKey(
            digest=weights_digest(ws, thr),
            backend=tgt.name,
            passes=spec.spec_string(),
            opts=tuple(sorted(opts.items())),
        )
        return key, spec, tgt, opts, ws, thr

    def key_for(self, net, *, backend: str = "jnp",
                passes=None, input_threshold: int | None = None,
                **backend_opts) -> CacheKey:
        """The content-addressed key `get_or_compile` would use. `net` is
        anything the frontend accepts; weights are canonicalized the same
        way the frontend lowers them, so two nets with equal integer
        content produce the same key regardless of container or dtype.
        `passes` accepts a PipelineSpec, a spec/registry string, or a
        sequence of pass callables (see `_pass_fingerprint`)."""
        key, *_ = self._resolve(
            net, backend, passes, input_threshold, backend_opts)
        return key

    def get_or_compile(self, net, *, backend: str = "jnp",
                       passes=None, input_threshold: int | None = None,
                       **backend_opts) -> Artifact:
        """Return the cached `Artifact` for this exact (weights, pipeline,
        target, options) combination — from memory, then the store, then
        by compiling (and persisting) on first sight anywhere."""
        key, spec, tgt, opts, ws, thr = self._resolve(
            net, backend, passes, input_threshold, backend_opts)
        while True:
            owner = False
            with self._lock:
                hit = self._entries.get(key)
                if hit is not None:
                    self._entries.move_to_end(key)
                    self._counters.hits.inc()
                    return hit
                flight = self._inflight.get(key)
                if flight is None:
                    flight = self._inflight[key] = _InFlight()
                    self._counters.misses.inc()   # this call owns the miss
                    owner = True
            if owner:
                return self._compile_owner(
                    key, flight, spec, tgt, opts, ws, thr)
            # joiner: block until the owner resolves this key, then
            # re-check the table (a hit in the common case — counted as
            # one; an immediate eviction falls through to a fresh miss)
            flight.event.wait()
            if flight.error is not None:
                raise flight.error

    def _compile_owner(self, key, flight, spec, tgt, opts, ws, thr):
        """Resolve one miss outside the lock: store lookup, then a full
        compile; publish into the table and release the waiters."""
        try:
            compiled = None
            dt = None
            skey = artifact_key(key.digest, spec, target_string(tgt, opts))
            if self.store is not None:
                compiled = self.store.get(skey)
                if compiled is not None:
                    self._counters.store_hits.inc()
                    self._counters.load_seconds.observe(
                        compiled.timings.get("load_s", 0.0))
            if compiled is None:
                t0 = time.perf_counter()
                compiled = compile_resolved(
                    ws, thr, key.digest, spec, tgt, opts, tuner=self.tuner)
                dt = time.perf_counter() - t0
                self._counters.compiles.inc()
                self._counters.compile_seconds.observe(dt)
                if self.store is not None:
                    self.store.put(compiled)
        except BaseException as e:
            self._counters.failures.inc()
            with self._lock:
                self._inflight.pop(key, None)
            flight.error = e
            flight.event.set()
            raise
        with self._lock:
            self._entries[key] = compiled
            if dt is not None:
                self._compile_seconds[key] = dt
            self._inflight.pop(key, None)
            while len(self._entries) > self.capacity:
                evicted, _ = self._entries.popitem(last=False)
                self._compile_seconds.pop(evicted, None)
                self._counters.evictions.inc()
        flight.event.set()
        return compiled


DEFAULT_CACHE = CompileCache(capacity=64)


def cached_compile_net(net, **kw) -> Artifact:
    """`compile_artifact` through the process-wide DEFAULT_CACHE."""
    return DEFAULT_CACHE.get_or_compile(net, **kw)


# ---------------------------------------------------------------------------
# Cross-model weight stacking
# ---------------------------------------------------------------------------

def stack_layered_weights(circuits: Sequence[Circuit]
                          ) -> tuple[int, list[np.ndarray]]:
    """Stack M regular circuits' weight matrices for the multi-net
    targets: lower each circuit to its ExecutionPlan and join them with
    `repro.netgen.plan.stack_plans` (which owns the compatibility
    checks and the exact hidden-width padding).

    Returns (input_threshold, [per-layer (M, fan_in, fan_out) int32]) —
    the pre-plan calling convention, kept for callers that want the raw
    arrays. Raises IrregularCircuitError for shared/CSE circuits (via
    `lower_circuit`) and ValueError for incompatible topologies.
    """
    if not circuits:
        raise ValueError("no circuits to stack")
    plan = stack_plans([lower_circuit(c) for c in circuits])
    return plan.input_threshold, [l.weights for l in plan.layers]


def _kernel_attrs(fn) -> dict:
    """The datapath attributes a `netgen.kernel` span carries when the
    predictor declares them (pallas builds do): `form` names the
    executed datapath and `launches` the pallas_call count one predictor
    call performs — `benchmarks/check_trace.py` gates that every fusednet
    launch (one or more slot rounds) records exactly one pallas_call."""
    dp = getattr(fn, "datapath", None)
    if dp is None:
        return {}
    attrs = {"form": dp}
    launches = getattr(fn, "launches_per_call", None)
    if launches is not None:
        attrs["launches"] = launches
    return attrs


def _launch_rounds(rounds: int) -> list[int]:
    """The launches that serve `rounds` slot rounds: powers of two of
    at most MAX_ROUNDS_PER_LAUNCH rounds, largest first (32 -> [32],
    33 -> [32, 1], 100 -> [64, 32, 4])."""
    out = []
    while rounds > 0:
        k = min(MAX_ROUNDS_PER_LAUNCH, 1 << (rounds.bit_length() - 1))
        out.append(k)
        rounds -= k
    return out


def _shard_stacked(fn, mesh, capacity: int):
    """Wrap a stacked dispatch ((M, B, n_in) -> (M, B)) in
    `shard_map` over the mesh's data axes, splitting the slot (batch)
    dimension — each device serves B / n_data rows of every version.
    A launch's B is a whole number of slot rounds (k * capacity), so it
    divides across the mesh whenever the capacity does. Returns None
    (single-device fallback) when the mesh has no data axis or the
    capacity does not divide across it."""
    import jax

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not data_axes:
        return None
    n = 1
    for a in data_axes:
        n *= mesh.shape[a]
    if n < 1 or capacity % n != 0:
        return None
    from jax.sharding import PartitionSpec as P
    ax = data_axes if len(data_axes) > 1 else data_axes[0]
    in_specs = (P(None, ax, None),)
    out_specs = P(None, ax)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    wrapped = jax.jit(mapped)
    # keep the datapath identity visible on the sharded wrapper: the
    # kernel span's form/launches attrs come from these
    for attr in ("datapath", "launches_per_call", "plan_form"):
        if hasattr(fn, attr):
            try:
                setattr(wrapped, attr, getattr(fn, attr))
            except AttributeError:   # jitted fns normally allow attrs
                break
    return wrapped


# ---------------------------------------------------------------------------
# Multi-version server
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Version:
    name: str
    compiled: Artifact


class NetServer:
    """Serve uint8 image batches across registered model versions.

    Single-version requests (`predict`) route to that version's cached
    `Artifact` with fixed-capacity slot batching (the
    `repro.serve.engine` pattern — one live jit trace per model; larger
    batches are served in launches of 2^k whole slot rounds, see
    `MAX_ROUNDS_PER_LAUNCH`). Multi-version requests (`predict_many`) stack
    compatible versions' ExecutionPlans into one jitted multi-net
    dispatch — sharded over the slot dimension with `shard_map` when a
    mesh with a data axis is active (see the module doc); incompatible
    sets (different depth/width/classes, or a target without a multi
    form) fall back to per-version routing. `dispatch_counts` records
    which path served each request ("sharded" counts alongside
    "stacked", not instead of it).

    Construction: pass `session=` to compile through a `Session` (its
    memory tier and persistent store are reused; `target=`/`pipeline=`
    select what to compile), or the legacy `backend=`/`passes=`/`cache=`
    keywords. The target must produce a callable artifact.
    """

    def __init__(self, *, session=None, target: str | None = None,
                 pipeline=None, backend: str = "jnp",
                 passes=None, cache: CompileCache | None = None,
                 slot_capacity: int = 256, warmup: bool = True,
                 prefer_explored: bool = True):
        target = target if target is not None else backend
        self._target, self._opts = resolve_target(target)
        if not self._target.callable:
            raise ValueError(
                f"NetServer needs a callable backend, got {target!r} "
                f"(kind: {self._target.kind})")
        if slot_capacity < 1:
            raise ValueError(f"slot_capacity must be >= 1, got {slot_capacity}")
        if session is not None:
            if cache is not None:
                raise ValueError("pass session= or cache=, not both")
            if session.cache is None:
                raise ValueError(
                    "NetServer needs a Session with an in-memory tier "
                    "(capacity > 0)")
            self.cache = session.cache
        else:
            self.cache = cache if cache is not None else CompileCache()
        self.session = session
        # tuned=true stacked dispatch builds reuse the same persistent
        # tuning records as the single-version compiles
        self._tuner = getattr(self.cache, "tuner", None)
        self.backend = self._target.name
        # prefer a design-space-explored datapath record over the
        # hand-coded form precedence for stacked dispatch builds, when
        # the target declares `explored` and the caller didn't pin it
        # (a missing record leaves the option inert — see
        # `repro.netgen.explore`)
        self.prefer_explored = bool(prefer_explored) and \
            any(name == "explored" for name, _ in self._target.opts)
        self.passes = pipeline if pipeline is not None else passes
        self.slot_capacity = int(slot_capacity)
        self.warmup = bool(warmup)
        self._lock = threading.RLock()
        self._versions: "OrderedDict[str, _Version]" = OrderedDict()
        self._multi: dict[tuple, tuple] = {}
        # why a version set could not stack: {key: analysis.StackReport}
        self._stack_reports: dict[tuple, object] = {}
        self._generation = 0   # bumped by register/unregister; guards _multi
        self._tel = telemetry.get_registry()
        self._scope = telemetry.new_scope("server")
        self._dispatch = {
            path: self._tel.counter(
                "netgen_dispatch_total", server=self._scope, path=path)
            for path in ("single", "stacked", "sharded", "fallback")}
        self._h_occupancy = self._tel.histogram(
            "netgen_slot_occupancy", server=self._scope)
        self._slot_rounds = self._tel.counter(
            "netgen_slot_rounds_total", server=self._scope)

    @property
    def dispatch_counts(self) -> dict:
        """Per-path dispatch counts as a plain dict snapshot (the live
        values are atomic telemetry counters labelled with this
        server's scope)."""
        return {path: int(c.value) for path, c in self._dispatch.items()}

    def _latency(self, version: str):
        return self._tel.histogram(
            "netgen_predict_latency_seconds",
            server=self._scope, version=version)

    def _requests(self, version: str):
        """Per-version request counter: incremented exactly once per
        dispatch call per version, so `benchmarks/check_trace.py` can
        gate that every request produced exactly one latency
        observation (the misattribution bug fixed in ISSUE 7)."""
        return self._tel.counter(
            "netgen_requests_total", server=self._scope, version=version)

    # -- registry ------------------------------------------------------------

    def register(self, version: str, net) -> Artifact:
        """Compile (through the cache, and the session's store when one
        is configured) and register a model version. When `warmup` is
        on, the serving shape is traced and executed BEFORE the version
        is published into the routing table — a concurrent `predict`
        either sees the old state (KeyError / previous weights) or a
        fully warm predictor, never a registered-but-cold one whose
        first request pays the jit latency `warmup=True` promises to
        hide (and whose warmup a concurrent stacked dispatch would then
        redundantly re-run)."""
        compiled = self.cache.get_or_compile(
            net, backend=self.backend, passes=self.passes, **self._opts)
        if self.warmup:
            z = np.zeros((self.slot_capacity, compiled.circuit.n_inputs),
                         np.uint8)
            np.asarray(compiled(z))
        with self._lock:
            self._versions[version] = _Version(version, compiled)
            self._multi.clear()
            self._stack_reports.clear()
            self._generation += 1
        return compiled

    def unregister(self, version: str) -> None:
        with self._lock:
            del self._versions[version]
            self._multi.clear()
            self._stack_reports.clear()
            self._generation += 1

    def stack_report(self, names=None):
        """Why a version set fell back to per-version dispatch: the
        structured `repro.netgen.analysis.StackReport` recorded when
        `_stacked_fn` diagnosed the set (None for sets that stacked
        fine or were never requested). With `names`, the report for
        that version set under the currently active mesh; without,
        {version-name tuple: report} for every diagnosed set."""
        from repro.parallel.sharding import active_mesh
        with self._lock:
            if names is None:
                return {k[0]: r for k, r in self._stack_reports.items()}
            return self._stack_reports.get(
                (tuple(sorted(names)), active_mesh()))

    def versions(self) -> list[str]:
        with self._lock:
            return list(self._versions)

    def compiled_for(self, version: str) -> Artifact:
        with self._lock:
            v = self._versions.get(version)
        if v is None:
            raise KeyError(
                f"unknown version {version!r} (registered: {self.versions()})")
        return v.compiled

    # -- serving -------------------------------------------------------------

    def predict(self, version: str, x_uint8) -> np.ndarray:
        """Route one batch to one version. Returns predictions (B,)."""
        compiled = self.compiled_for(version)
        self._dispatch["single"].inc()
        t0 = time.perf_counter()
        with self._tel.span("netgen.dispatch", path="single",
                            versions=version):
            out = self._run_slots(compiled, np.asarray(x_uint8))
        self._requests(version).inc()
        self._latency(version).observe(time.perf_counter() - t0)
        return out

    def predict_many(self, requests: dict) -> dict:
        """Serve {version: uint8 batch} in one cross-model stacked dispatch
        when the requested versions are stack-compatible (else per-version
        fallback). Returns {version: predictions}.

        Skewed batches do not waste rounds: the call is cut into
        segments by which versions still have requested rows, each
        segment's rounds are stacked into launches of 2^k whole rounds
        (`_launch_rounds`), an exhausted version's padded all-zero block
        is never launched (it would burn kernel work and skew the
        occupancy histogram with rows nobody asked for), and the last
        remaining version finishes through the single-version slot
        path. `netgen_predict_latency_seconds` records per-version
        SERVICE time — the launches a version actually participated in
        — so a 1-row version no longer inherits the whole-call latency
        of a 4096-row co-batched one."""
        t0 = time.perf_counter()
        names = tuple(sorted(requests))
        compiled = {v: self.compiled_for(v) for v in names}
        xs = {v: np.asarray(requests[v]) for v in names}
        for v in names:
            _validate_batch(xs[v], compiled[v].circuit.n_inputs)
        if len(names) == 1:
            (v,) = names
            self._dispatch["single"].inc()
            with self._tel.span("netgen.dispatch", path="single",
                                versions=v):
                out = {v: self._run_slots(compiled[v], xs[v])}
            self._requests(v).inc()
            self._latency(v).observe(time.perf_counter() - t0)
            return out

        fn, sharded = self._stacked_fn(names)
        if fn is None:
            self._dispatch["fallback"].inc()
            out = {}
            with self._tel.span("netgen.dispatch", path="fallback",
                                versions=len(names)):
                for v in names:
                    t1 = time.perf_counter()
                    out[v] = self._run_slots(compiled[v], xs[v])
                    self._requests(v).inc()
                    self._latency(v).observe(time.perf_counter() - t1)
            return out

        self._dispatch["stacked"].inc()
        if sharded:
            self._dispatch["sharded"].inc()
        cap = self.slot_capacity
        ends = {v: (x.shape[0] + cap - 1) // cap for v, x in xs.items()}
        rounds = max(ends.values())
        out: dict[str, list] = {v: [] for v in names}
        service = {v: 0.0 for v in names}
        with self._tel.span("netgen.dispatch",
                            path="sharded" if sharded else "stacked",
                            versions=len(names), rounds=rounds):
            r = 0
            while r < rounds:
                active = tuple(v for v in names if ends[v] > r)
                if len(active) == 1:
                    (v,) = active
                    t1 = time.perf_counter()
                    out[v].append(self._run_slots(
                        compiled[v], xs[v][r * cap:]))
                    service[v] += time.perf_counter() - t1
                    break
                # a strict subset of a stackable set is itself stackable;
                # its multi-net fn is cached in _multi like the full set's
                afn = fn if active == names else self._stacked_fn(active)[0]
                # every active version has rows in each round up to the
                # first one's end: those rounds stack whole into launches
                for k in _launch_rounds(min(ends[v] for v in active) - r):
                    chunks = [xs[v][r * cap:(r + k) * cap] for v in active]
                    t1 = time.perf_counter()
                    preds, valid = self._stacked_round(afn, chunks, round=r)
                    dt = time.perf_counter() - t1
                    for i, v in enumerate(active):
                        out[v].append(preds[i, :valid[i]])
                        service[v] += dt
                    r += k
        for v in names:
            self._requests(v).inc()
            self._latency(v).observe(service[v])
        return {v: (np.concatenate(out[v]) if out[v]
                    else np.zeros((0,), np.int64)) for v in names}

    # -- internals -----------------------------------------------------------

    def _observe_rounds(self, valid: list, rounds: int) -> None:
        """One `netgen_slot_occupancy` observation per slot round of a
        launch, over the rows requested in that round (`valid` holds
        each version's requested rows of the launch), and the launch's
        rounds into `netgen_slot_rounds_total`."""
        cap = self.slot_capacity
        for j in range(rounds):
            used = sum(min(max(n - j * cap, 0), cap) for n in valid)
            self._h_occupancy.observe(used / (len(valid) * cap))
        self._slot_rounds.inc(rounds)

    def _stacked_round(self, fn, chunks: list, round: int = 0
                       ) -> tuple[np.ndarray, list]:
        """ONE stacked launch — the slot mechanics shared by
        `predict_many` and the async serving engine
        (`repro.netgen.engine`): pad each version's chunk into the
        (M, rounds * cap, n_in) slot block, observe occupancy over the
        slots actually requested, run the jitted multi-net fn. The
        launch covers ceil(longest chunk / cap) slot rounds: one for the
        engine, which hands over at most cap rows a version, and 2^k for
        `predict_many`'s multi-round calls. `round` is the index of the
        launch's first slot round within its call. Returns the
        (M, rounds * cap) predictions and the per-version valid row
        counts."""
        cap = self.slot_capacity
        rounds = -(-max(c.shape[0] for c in chunks) // cap)
        with self._tel.span("netgen.round.stage"):
            block = np.zeros(
                (len(chunks), rounds * cap, chunks[0].shape[1]), np.uint8)
            valid = []
            for i, chunk in enumerate(chunks):
                block[i], n = pad_slots(chunk, rounds * cap)
                valid.append(n)
        self._observe_rounds(valid, rounds)
        attrs = {"round": round, "valid": sum(valid), "rounds": rounds,
                 **_kernel_attrs(fn)}
        with self._tel.span("netgen.kernel", **attrs):
            with self._tel.span("netgen.round.launch"):
                y = fn(block)
            with self._tel.span("netgen.round.fetch"):
                preds = np.asarray(y)        # (M, rounds * cap)
        return preds, valid

    def _run_slots(self, compiled: Artifact, x: np.ndarray) -> np.ndarray:
        """Serve one version's rows in launches of 2^k whole slot rounds
        (`_launch_rounds`): a full launch passes its slice of `x` as it
        is, only the last one is padded, and each is fetched once."""
        _validate_batch(x, compiled.circuit.n_inputs)
        cap = self.slot_capacity
        if x.shape[0] == 0:
            return np.zeros((0,), np.int64)
        attrs = _kernel_attrs(getattr(compiled, "artifact", None))
        outs = []
        start = 0
        for rounds in _launch_rounds(-(-x.shape[0] // cap)):
            rows = rounds * cap
            with self._tel.span("netgen.round.stage"):
                padded, n = pad_slots(x[start:start + rows], rows)
            self._observe_rounds([n], rounds)
            with self._tel.span("netgen.kernel", valid=n, rounds=rounds,
                                **attrs):
                with self._tel.span("netgen.round.launch"):
                    y = compiled(padded)
                with self._tel.span("netgen.round.fetch"):
                    outs.append(np.asarray(y)[:n])
            start += rows
        return np.concatenate(outs)

    def _stacked_fn(self, names: tuple) -> tuple:
        """Build (or recall) the multi-net dispatch for this version set;
        returns (fn, sharded) with fn None when the set cannot be
        stacked. The stacked plan is compiled through the Target
        registry (`backends.compile_multi`), so the declared target
        options — interpret, packed — reach the multi form through the
        same validation as the single-version path. When a mesh with a
        data axis is active the dispatch is wrapped in `shard_map` over
        the slot dimension (the cache is keyed on the mesh, so leaving
        the mesh context falls back to the single-device build).
        Compilation happens outside the lock; a generation check before
        storing guards against a concurrent (un)register racing the
        build — a stale fn must never enter `_multi`, or it would
        silently serve old weights.

        A set that cannot stack is no longer a silent fallback: the
        static diagnosis (`repro.netgen.analysis.diagnose_stack`, or
        the irregular-circuit / plan-verification error of the build) is
        recorded as a `StackReport` readable through `stack_report()`
        and counted in `netgen_stack_incompat_total{reason}`."""
        from repro.netgen import analysis
        from repro.parallel.sharding import active_mesh

        mesh = active_mesh()
        key = (names, mesh)
        while True:
            with self._lock:
                if key in self._multi:
                    return self._multi[key]
                generation = self._generation
                circuits = [self._versions[v].compiled.circuit for v in names]
            report = None
            if self._target.compile_multi is None:
                entry = (None, False)
                report = analysis.StackReport(
                    compatible=False, n_versions=len(names),
                    diagnostics=(analysis.Diagnostic(
                        check="stack.target",
                        message=f"target {self._target.name!r} has no "
                                "multi-net dispatch"),))
            else:
                report = analysis.diagnose_stack(circuits)
                if not report.compatible:
                    entry = (None, False)
                else:
                    try:
                        plan = stack_plans(
                            [lower_circuit(c) for c in circuits])
                        opts = dict(self._opts)
                        if self.prefer_explored and "explored" not in opts:
                            opts["explored"] = True
                        fn = compile_multi(
                            plan, backend=self._target.name,
                            tuner=self._tuner, **opts)
                        sharded_fn = (
                            None if mesh is None else
                            _shard_stacked(fn, mesh, self.slot_capacity))
                        entry = ((sharded_fn, True) if sharded_fn is not None
                                 else (fn, False))
                        report = None
                    except (IrregularCircuitError,
                            analysis.VerificationError) as e:
                        # only a plan the stacked form cannot take falls
                        # back; a kernel that fails to lower or compile
                        # is an error the caller must see
                        entry = (None, False)
                        report = analysis.StackReport(
                            compatible=False, n_versions=len(names),
                            diagnostics=(analysis.Diagnostic(
                                check="stack.build", message=str(e)),))
            with self._lock:
                if self._generation == generation:
                    self._multi[key] = entry
                    if report is not None:
                        self._stack_reports[key] = report
                        self._tel.counter(
                            "netgen_stack_incompat_total",
                            server=self._scope, reason=report.reason).inc()
                    return entry
            # registry changed underneath the build: retry with fresh circuits
