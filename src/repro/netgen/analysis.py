"""Static analysis & verification for the netgen compiler.

The paper's generated hardware is only correct because every
accumulator is sized to the *exact* value range of the trained weights
(§IV-§V: scaled inputs, selected addends, the MSB sign step). Before
this module that guarantee rested on scattered ad-hoc checks —
`Circuit.validate()`, `evaluate(check_widths=True)`, per-backend shape
asserts — none of which ran by default. `repro.netgen.analysis` is the
machine-checked invariant layer that replaces them:

  Structural verifier — `verify_circuit`: DAG well-formedness (dense
      unique ids, topological order, src-reference validity), output
      wiring, kind-specific arity/field invariants (pixel ranges, step
      sources, argmax fan-in), and per-pass postconditions ("no
      zero-weight terms after `zeros`", "no |w| != 1 terms after
      `addends`", "no dead hidden units after `prune`"). The promotion
      of `Circuit.validate()` into a diagnostic engine: violations are
      `Diagnostic` records naming the check, the node, and the pipeline
      stage, raised together as one `VerificationError`.

  Range dataflow — `analyze_ranges`: one topological sweep computing,
      per node, the exact value interval [lo, hi] *and* the paper's
      symmetric magnitude bound sum(|w| * bound(src)) that sizes
      hardware registers. The interval is strictly tighter (an
      all-negative-weight accumulator has hi == 0), which is what lets
      `check_ranges` *prove* — not assert at runtime — that every
      WeightedSum fits its inferred `signed_width` and that the
      popcount kernel's int32 accumulation is safe at the actual
      fan-in. `RangeAnalysis.bounds()`/`widths()` reproduce
      `graph.value_bounds`/`graph.node_widths` exactly, so the Verilog
      and cost backends consume THIS analysis instead of recomputing
      (golden Verilog is byte-identical). `check_observed` replaces
      `evaluate(check_widths=True)`: any value the interpreter can
      produce is bracketed by the static interval.

  Plan certification — `verify_plan`: packed lane padding exactness
      (pad rows beyond the true fan-in are zero), `decompose_planes`
      losslessness (bit-planes reconstruct the int32 matrix bit for
      bit, positive/negative planes are disjoint, the plane count
      covers the post-pass magnitude range), layer chaining, and int32
      accumulation safety per layer.

  Tile legality — `tile_legality`: the pallas kernels clamp any block
      size to the (rounded) problem dims, so two candidates that clamp
      to the same effective (bm, bn, bkw) per layer run the *same*
      kernel. The legality closure statically rejects non-positive
      blocks and clamp-duplicates so `KernelTuner` never spends a
      measurement on a candidate that cannot change the outcome.

  Layer-level (ConvNet) circuits — TensorInput, Conv, MaxPool and
      Dense nodes: the verifier checks each layer's source and shapes,
      the dataflow bounds every layer's accumulators per unit, and
      `check_ranges` certifies the conv kernel's MXU products exact:
      int8 weights (|w| <= 127), int8 activations ({0, 1}, or pixels
      taken as x - 128), int32 accumulators that cannot overflow.

  Stack compatibility — `diagnose_stack`: the structured report of WHY
      a set of model versions cannot share one stacked dispatch
      (irregular circuit, depth/threshold/input/class disagreement),
      consumed by `NetServer` in place of its former silent
      `except (IrregularCircuitError, ValueError)` fallback.

  Store linting — `lint_store` / `python -m repro.netgen.analysis
      <store-dir>`: re-verify every persisted artifact in an
      `ArtifactStore` (format, schema fields, circuit invariants,
      content-address consistency, cost and proof-summary agreement
      with a recompute), exiting non-zero with structured diagnostics
      on any corrupt or stale entry. CI runs it over the cached
      `.netgen-store`.

Wiring: `PipelineSpec.run(verify=...)` checks invariants between
passes (default from the `NETGEN_VERIFY` env var — on in tests/CI, off
in prod); `Session.compile_resolved` always runs the range analysis
pre-backend, raising under strict verification and otherwise counting
`netgen_verify_failures_total` and proceeding; the proof summary
persists with the artifact (`meta.json`) and prints in
`artifact.report()`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
from pathlib import Path
from typing import Callable, Mapping, Sequence

import numpy as np

from repro.netgen.graph import (
    LAYER_NODES, Argmax, Circuit, Conv, Dense, InputCompare,
    IrregularCircuitError, MaxPool, SignStep, TensorInput, WeightedSum,
    argmax_classes, argmax_scores, eval_layer, layer_kind, signed_width,
)
from repro.netgen.plan import (
    ARGMAX, PACK_LANES, STEP, ExecutionPlan, lower_circuit,
)

__all__ = [
    "Diagnostic", "FUSEDNET_VMEM_BYTES", "RangeAnalysis", "StackReport",
    "VerificationError", "analyze", "analyze_ranges", "check_envelope",
    "check_observed", "check_ranges", "diagnose_stack",
    "fusednet_vmem_bytes", "lint_store", "proof_summary", "strict_verify",
    "summary_row", "tile_legality", "tile_report", "verify_circuit",
    "verify_plan",
]

_SUMMARY_FORMAT = "netgen-analysis-v1"
INT32_MAX = 2 ** 31 - 1


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One invariant violation: which check, where, and why. `check` is
    a dotted invariant class ("structure.topo-order", "range.envelope",
    "plan.planes-lossless", "stack.depth", "store.key"); `stage` names
    the pipeline pass (or store entry) the violation was detected
    after, `node` the offending IR node when one exists."""
    check: str
    message: str
    node: int | None = None
    stage: str | None = None

    def row(self) -> str:
        where = ""
        if self.stage is not None:
            where += f" after {self.stage!r}"
        if self.node is not None:
            where += f" at node {self.node}"
        return f"[{self.check}]{where}: {self.message}"


class VerificationError(ValueError):
    """A batch of invariant violations, raised together so one broken
    pass reports every consequence, not just the first."""

    def __init__(self, diagnostics: Sequence[Diagnostic]):
        self.diagnostics = tuple(diagnostics)
        shown = [d.row() for d in self.diagnostics[:8]]
        if len(self.diagnostics) > len(shown):
            shown.append(f"... and {len(self.diagnostics) - len(shown)} more")
        super().__init__(
            f"{len(self.diagnostics)} invariant violation(s):\n  "
            + "\n  ".join(shown))


def _finish(diags: list, collect: bool) -> list:
    if diags and not collect:
        raise VerificationError(diags)
    return diags


def strict_verify() -> bool:
    """Whether verification failures should raise (the `NETGEN_VERIFY`
    env var: on by default in tests/CI via conftest/workflow env, off
    in prod where failures only count `netgen_verify_failures_total`)."""
    import os
    v = os.environ.get("NETGEN_VERIFY", "0").strip().lower()
    return v not in ("", "0", "false", "off", "no")


# ---------------------------------------------------------------------------
# Structural verifier
# ---------------------------------------------------------------------------

def _term_arrays(n: WeightedSum) -> tuple[np.ndarray, np.ndarray]:
    """(weights, srcs) of one accumulator as int64 arrays — the hot
    per-term sweeps (verifier, range dataflow, postconditions) are
    vectorized over these instead of looping Python-side (post-addend
    circuits carry sum(|w|) terms; a per-term interpreter loop made the
    analysis cost ~20% of pipeline time, numpy keeps it under 10%)."""
    k = len(n.terms)
    ws = np.fromiter((t.weight for t in n.terms), np.int64, count=k)
    srcs = np.fromiter((t.src for t in n.terms), np.int64, count=k)
    return ws, srcs


def _extract_terms(circuit: Circuit) -> list:
    """Term arrays for every node, aligned with `circuit.nodes` (None
    for non-accumulators). Extraction touches every Term once and
    dominates analysis cost, so `analyze` computes this list one time
    and threads it through the verifier, the postconditions, and the
    range sweep via their private `_terms` parameter."""
    return [_term_arrays(n) if isinstance(n, WeightedSum) else None
            for n in circuit.nodes]


def verify_circuit(circuit: Circuit, *, after_pass: str | None = None,
                   stage: str | None = None,
                   collect: bool = False,
                   _terms: list | None = None) -> list[Diagnostic]:
    """Check every structural invariant of the IR; with `after_pass`
    also the named pass's postconditions. Raises `VerificationError`
    unless `collect=True` (then the diagnostics are returned)."""
    diags: list[Diagnostic] = []

    def bad(check: str, message: str, node: int | None = None) -> None:
        diags.append(Diagnostic(
            check=check, message=message, node=node, stage=stage))

    # kind-by-id array for the vectorized per-term checks (0 = not yet
    # defined at this point of the topological sweep)
    max_id = max((n.id for n in circuit.nodes if n.id >= 0), default=-1)
    kind = np.zeros(max_id + 1, np.int8)
    _BIT, _SUM, _ARGMAX, _LAYER = 1, 2, 3, 4

    terms = _extract_terms(circuit) if _terms is None else _terms
    seen: dict[int, object] = {}
    shapes: dict[int, tuple] = {}       # layer node id -> output shape
    step_of: dict[int, int] = {}        # sum id -> step id
    pixels: dict[int, int] = {}         # pixel index -> node id
    for i, n in enumerate(circuit.nodes):
        if n.id in seen:
            bad("structure.duplicate-id", f"node id {n.id} defined twice",
                n.id)
        if isinstance(n, LAYER_NODES):
            _verify_layer(n, seen, shapes, circuit, bad)
        elif isinstance(n, InputCompare):
            if not 0 <= n.pixel < circuit.n_inputs:
                bad("structure.input-pixel",
                    f"pixel {n.pixel} outside [0, {circuit.n_inputs})", n.id)
            elif n.pixel in pixels:
                bad("structure.input-pixel",
                    f"pixel {n.pixel} compared twice "
                    f"(also node {pixels[n.pixel]})", n.id)
            else:
                pixels[n.pixel] = n.id
            if not 0 <= n.threshold <= 255:
                bad("structure.input-threshold",
                    f"threshold {n.threshold} outside the uint8 range", n.id)
        elif isinstance(n, WeightedSum):
            if n.layer < 1:
                bad("structure.sum-layer",
                    f"layer tag {n.layer} < 1", n.id)
            _, srcs = terms[i]
            in_range = (srcs >= 0) & (srcs <= max_id)
            kinds = np.zeros(len(srcs), np.int8)
            kinds[in_range] = kind[srcs[in_range]]
            if not np.all(kinds > 0):          # fast path: all defined
                for s in sorted(set(srcs[kinds == 0].tolist())):
                    bad("structure.topo-order",
                        f"reads node {s} before it is defined", n.id)
            if np.any(kinds == _ARGMAX):
                for s in sorted(set(srcs[kinds == _ARGMAX].tolist())):
                    bad("structure.term-src",
                        f"term reads the Argmax node {s}", n.id)
            if np.any(kinds == _LAYER):
                for s in sorted(set(srcs[kinds == _LAYER].tolist())):
                    bad("structure.term-src",
                        f"term reads the layer-level node {s}", n.id)
        elif isinstance(n, SignStep):
            src = seen.get(n.src)
            if src is None:
                bad("structure.topo-order",
                    f"reads node {n.src} before it is defined", n.id)
            elif not isinstance(src, WeightedSum):
                bad("structure.step-src",
                    f"step source {n.src} is {type(src).__name__}, "
                    "not a WeightedSum", n.id)
            elif n.src in step_of:
                bad("structure.step-dup",
                    f"sum {n.src} already feeds step {step_of[n.src]}", n.id)
            else:
                step_of[n.src] = n.id
        elif isinstance(n, Argmax):
            if not n.srcs:
                bad("structure.argmax-arity", "argmax over zero scores", n.id)
            if len(set(n.srcs)) != len(n.srcs):
                bad("structure.argmax-dup",
                    "argmax reads a score twice", n.id)
            for s in n.srcs:
                src = seen.get(s)
                if src is None:
                    bad("structure.topo-order",
                        f"reads node {s} before it is defined", n.id)
                elif isinstance(src, Dense) and not src.step:
                    if len(n.srcs) != 1:
                        bad("structure.argmax-src",
                            "an argmax over a Dense layer's scores reads "
                            "nothing else", n.id)
                elif not isinstance(src, WeightedSum):
                    bad("structure.argmax-src",
                        f"score {s} is {type(src).__name__}, "
                        "not a WeightedSum or a scoring Dense", n.id)
        seen[n.id] = n
        if 0 <= n.id <= max_id:
            kind[n.id] = (_SUM if isinstance(n, WeightedSum)
                          else _ARGMAX if isinstance(n, Argmax)
                          else _LAYER if isinstance(n, LAYER_NODES) else _BIT)

    out = seen.get(circuit.output)
    if out is None or not isinstance(out, Argmax):
        bad("structure.output", "output must name an Argmax node",
            circuit.output)

    if after_pass is not None:
        post = _POSTCONDITIONS.get(after_pass)
        if post is not None:
            post(circuit, bad, terms)
    return _finish(diags, collect)


def _verify_layer(n, seen: dict, shapes: dict, circuit: Circuit, bad) -> None:
    """Source and shape checks of one layer-level node; records its
    output shape in `shapes`."""
    if isinstance(n, TensorInput):
        if len(n.shape) != 3 or min(n.shape) < 1 \
                or int(np.prod(n.shape)) != circuit.n_inputs:
            bad("structure.input-shape",
                f"image {n.shape} is not {circuit.n_inputs} inputs", n.id)
        if n.mode not in ("compare", "pixels"):
            bad("structure.input-mode", f"unknown input mode {n.mode!r}", n.id)
        if not 0 <= n.threshold <= 255:
            bad("structure.input-threshold",
                f"threshold {n.threshold} outside the uint8 range", n.id)
        shapes[n.id] = tuple(n.shape)
        return
    src = shapes.get(n.src)
    if src is None:
        what = "before it is defined" if n.src not in seen else "a node with no map"
        bad("structure.layer-src", f"reads node {n.src}: {what}", n.id)
        return
    if isinstance(n, (Conv, Dense)):
        if n.layer < 1:
            bad("structure.sum-layer", f"layer tag {n.layer} < 1", n.id)
        if n.thresholds.shape != (n.weights.shape[-1],):
            bad("structure.thresholds",
                f"{n.thresholds.shape} thresholds for {n.weights.shape[-1]} units",
                n.id)
    if isinstance(n, Conv):
        if len(src) != 3 or n.weights.ndim != 4 or n.weights.shape[2] != src[2] \
                or n.weights.shape[0] > src[0] or n.weights.shape[1] > src[1]:
            bad("structure.conv-shape",
                f"kernel {n.weights.shape} does not fit the {src} map", n.id)
            return
        kh, kw, _, cout = n.weights.shape
        shapes[n.id] = (src[0] - kh + 1, src[1] - kw + 1, cout)
    elif isinstance(n, MaxPool):
        if len(src) != 3 or n.size < 1 or n.size > min(src[:2]):
            bad("structure.pool-shape", f"{n.size}x{n.size} pool of a {src} map", n.id)
            return
        shapes[n.id] = (src[0] // n.size, src[1] // n.size, src[2])
    else:
        k = int(np.prod(src))
        if n.weights.ndim != 2 or n.weights.shape[0] != k:
            bad("structure.dense-shape",
                f"weights {n.weights.shape} over {k} inputs", n.id)
            return
        shapes[n.id] = (n.weights.shape[1],)


# -- per-pass postconditions (keyed by registry AND function name) ----------

def _post_zeros(circuit: Circuit, bad, terms: list) -> None:
    for i, n in enumerate(circuit.nodes):
        if isinstance(n, WeightedSum) and n.terms:
            ws, _ = terms[i]
            if not ws.all():
                bad("postcondition.zeros",
                    "zero-weight term survived delete_zero_terms", n.id)


def _post_addends(circuit: Circuit, bad, terms: list) -> None:
    for i, n in enumerate(circuit.nodes):
        if isinstance(n, WeightedSum) and n.terms:
            ws, _ = terms[i]
            nonunit = np.abs(ws) != 1
            if nonunit.any():
                w = int(ws[nonunit][0])
                bad("postcondition.addends",
                    f"non-unit weight {w} survived addend_rewrite", n.id)


def _post_prune(circuit: Circuit, bad, terms: list) -> None:
    consumed = {nid for nid, cs in circuit.consumers().items() if cs}
    by_id = circuit._by_id()
    out = by_id.get(circuit.output)
    final = set(out.srcs) if isinstance(out, Argmax) else set()
    for n in circuit.nodes:
        if isinstance(n, SignStep):
            src = by_id.get(n.src)
            if isinstance(src, WeightedSum) and not src.terms:
                bad("postcondition.prune",
                    f"step of the empty (constant-0) sum {n.src} survived "
                    "prune_dead_units", n.id)
            if n.id not in consumed:
                bad("postcondition.prune",
                    "unread hidden step survived prune_dead_units", n.id)
        elif isinstance(n, WeightedSum):
            if n.id not in consumed and n.id not in final:
                bad("postcondition.prune",
                    "unread hidden sum survived prune_dead_units", n.id)


_POSTCONDITIONS: dict[str, Callable] = {
    "zeros": _post_zeros, "delete_zero_terms": _post_zeros,
    "addends": _post_addends, "addend_rewrite": _post_addends,
    "prune": _post_prune, "prune_dead_units": _post_prune,
}


# ---------------------------------------------------------------------------
# Range dataflow
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NodeRange:
    """Per-node result of the dataflow: the exact value interval
    [lo, hi], the paper's symmetric magnitude bound (what hardware
    widths are sized from — `sum(|w| * bound(src))`, identical to
    `graph.value_bounds`), and the signed bit-width sized from it."""
    lo: int
    hi: int
    bound: int
    width: int

    @property
    def max_abs(self) -> int:
        return max(abs(self.lo), abs(self.hi))


@dataclasses.dataclass(frozen=True)
class RangeAnalysis:
    """The full per-node range map for one circuit, with the
    `value_bounds`/`node_widths`-compatible views the Verilog and cost
    backends consume (so wire widths come from ONE analysis).
    `units` holds, for each Conv and Dense node, the per-unit interval
    (lo, hi) of its accumulator before its threshold."""
    ranges: dict[int, NodeRange]
    units: dict = dataclasses.field(default_factory=dict)

    def __getitem__(self, nid: int) -> NodeRange:
        return self.ranges[nid]

    def bounds(self) -> dict[int, int]:
        """Per-node magnitude bound — exactly `graph.value_bounds`."""
        return {nid: r.bound for nid, r in self.ranges.items()}

    def widths(self) -> dict[int, int]:
        """Per-node signed bit-width — exactly `graph.node_widths`."""
        return {nid: r.width for nid, r in self.ranges.items()}

    def output_envelope(self, circuit: Circuit) -> tuple:
        """The (lo, hi) interval of every class score, in argmax
        order — the quantity an exact rewrite may tighten but never
        widen (the pipeline verifier's cross-pass invariant)."""
        out = circuit.node(circuit.output)
        if not isinstance(out, Argmax):
            return ()
        env: list = []
        for s in out.srcs:
            src = circuit.node(s)
            if isinstance(src, Dense) and s in self.units:
                lo, hi = self.units[s]
                t = np.asarray(src.thresholds, np.int64)
                env += list(zip((lo - t).tolist(), (hi - t).tolist()))
            else:
                env.append((self.ranges[s].lo, self.ranges[s].hi))
        return tuple(env)


def analyze_ranges(circuit: Circuit, *,
                   _terms: list | None = None) -> RangeAnalysis:
    """One topological sweep computing every node's `NodeRange` with
    exact integer interval arithmetic (see module doc). Terms reading
    an undefined source contribute nothing — structural breakage is
    `verify_circuit`'s to report; this sweep must not crash on the
    circuit it is diagnosing."""
    terms = _extract_terms(circuit) if _terms is None else _terms
    ranges: dict[int, NodeRange] = {}
    # id-indexed interval arrays for the vectorized accumulator sweep
    # (undefined srcs read a 0-everything slot and contribute nothing)
    max_id = max((n.id for n in circuit.nodes if n.id >= 0), default=-1)
    lo_a = np.zeros(max_id + 1, np.int64)
    hi_a = np.zeros(max_id + 1, np.int64)
    bd_a = np.zeros(max_id + 1, np.int64)
    units: dict = {}
    for i, n in enumerate(circuit.nodes):
        if isinstance(n, LAYER_NODES):
            r = _layer_range(n, ranges, units)
            if r is not None:
                ranges[n.id] = r
        elif isinstance(n, (InputCompare, SignStep)):
            ranges[n.id] = NodeRange(lo=0, hi=1, bound=1, width=1)
            if 0 <= n.id <= max_id:
                hi_a[n.id] = bd_a[n.id] = 1
        elif isinstance(n, WeightedSum):
            ws, srcs = terms[i]
            ok = (srcs >= 0) & (srcs <= max_id)
            if not ok.all():
                ws, srcs = ws[ok], srcs[ok]
            slo, shi = lo_a[srcs], hi_a[srcs]
            pos = ws >= 0
            lo = int(np.where(pos, ws * slo, ws * shi).sum())
            hi = int(np.where(pos, ws * shi, ws * slo).sum())
            bound = int((np.abs(ws) * bd_a[srcs]).sum())
            ranges[n.id] = NodeRange(
                lo=lo, hi=hi, bound=bound, width=signed_width(bound))
            if 0 <= n.id <= max_id:
                lo_a[n.id], hi_a[n.id], bd_a[n.id] = lo, hi, bound
        elif isinstance(n, Argmax):
            k = argmax_classes(circuit, n)
            ranges[n.id] = NodeRange(
                lo=0, hi=max(k - 1, 0), bound=max(k - 1, 1),
                width=max(math.ceil(math.log2(max(k, 2))), 1))
    return RangeAnalysis(ranges=ranges, units=units)


_BITS = NodeRange(lo=0, hi=1, bound=1, width=1)


def _layer_range(n, ranges: dict, units: dict) -> NodeRange | None:
    """The value range of one layer-level node (None where its source has
    none: structural breakage is the verifier's to report). A Conv or
    Dense node's per-unit accumulator intervals go into `units`."""
    if isinstance(n, TensorInput):
        return NodeRange(lo=0, hi=255, bound=255, width=signed_width(255)) \
            if n.mode == "pixels" else _BITS
    src = ranges.get(n.src)
    if src is None:
        return None
    if isinstance(n, MaxPool):
        return src
    w = np.asarray(n.weights, np.int64)
    w = w.reshape(-1, w.shape[-1])
    lo = np.where(w >= 0, w * src.lo, w * src.hi).sum(axis=0)
    hi = np.where(w >= 0, w * src.hi, w * src.lo).sum(axis=0)
    units[n.id] = (lo, hi)
    if isinstance(n, Conv) or n.step:
        return _BITS
    t = np.asarray(n.thresholds, np.int64)
    slo, shi = int((lo - t).min(initial=0)), int((hi - t).max(initial=0))
    bound = max(abs(slo), abs(shi))
    return NodeRange(lo=slo, hi=shi, bound=bound, width=signed_width(bound))


def check_ranges(circuit: Circuit, ranges: RangeAnalysis | None = None, *,
                 stage: str | None = None,
                 collect: bool = False) -> list[Diagnostic]:
    """Prove every accumulator fits its inferred signed width and stays
    int32-safe (the popcount kernel accumulates int32 at the actual
    fan-in). The width proof is the theorem the Verilog backend relies
    on: interval ⊆ [-2^(w-1), 2^(w-1) - 1]."""
    if ranges is None:
        ranges = analyze_ranges(circuit)
    diags: list[Diagnostic] = []
    for n in circuit.nodes:
        if isinstance(n, (Conv, Dense)):
            diags += _check_layer(circuit, n, ranges, stage)
        if not isinstance(n, WeightedSum):
            continue
        r = ranges.ranges.get(n.id)
        if r is None:
            diags.append(Diagnostic(
                check="range.missing", stage=stage, node=n.id,
                message="no range computed for accumulator"))
            continue
        lim = 1 << (r.width - 1)
        if r.lo < -lim or r.hi > lim - 1:
            diags.append(Diagnostic(
                check="range.width-overflow", stage=stage, node=n.id,
                message=f"interval [{r.lo}, {r.hi}] does not fit the "
                        f"inferred {r.width}-bit signed register"))
        if r.bound > INT32_MAX:
            diags.append(Diagnostic(
                check="range.int32", stage=stage, node=n.id,
                message=f"magnitude bound {r.bound} exceeds int32 — the "
                        "popcount kernel's accumulator would overflow"))
    return _finish(diags, collect)


def _check_layer(circuit: Circuit, n, ranges: RangeAnalysis,
                 stage: str | None) -> list[Diagnostic]:
    """int32 safety of a layer's accumulators and thresholds, and for a
    Conv the certificate that the conv kernel's int8 MXU products are
    exact: |w| <= 127, int8 activations (a pixel input is taken as
    x - 128, which moves the accumulator by 128 * sum(w) per channel)."""
    diags = []
    lo, hi = ranges.units.get(n.id, (np.zeros(1, np.int64), np.zeros(1, np.int64)))
    worst = max(int(np.abs(lo).max(initial=0)), int(np.abs(hi).max(initial=0)))
    if isinstance(n, Conv):
        w = np.asarray(n.weights, np.int64)
        if int(np.abs(w).max(initial=0)) > 127:
            diags.append(Diagnostic(
                check="range.mxu-int8", stage=stage, node=n.id,
                message="conv weight outside int8's [-127, 127]: the MXU "
                        "product would not be exact"))
        src = circuit.node(n.src)
        if isinstance(src, TensorInput) and src.mode == "pixels":
            worst = int(128 * np.abs(w).sum(axis=(0, 1, 2)).max(initial=0)) \
                + int(np.abs(n.thresholds).max(initial=0))
    t = int(np.abs(np.asarray(n.thresholds, np.int64)).max(initial=0))
    if worst > INT32_MAX or t > INT32_MAX:
        diags.append(Diagnostic(
            check="range.int32", stage=stage, node=n.id,
            message=f"accumulator bound {worst} or threshold {t} exceeds "
                    "int32 — the kernel's accumulator would overflow"))
    return diags


def check_envelope(before: tuple, after: tuple, *, stage: str | None = None,
                   collect: bool = False) -> list[Diagnostic]:
    """Cross-pass invariant: an exact rewrite may tighten a class
    score's interval (pruning a constant-0 unit drops its slack) but
    must never widen it — a widened envelope means the pass changed
    the arithmetic (mis-sized a weight, dropped a source)."""
    diags: list[Diagnostic] = []
    if len(before) != len(after):
        diags.append(Diagnostic(
            check="range.class-count", stage=stage,
            message=f"pass changed the class count: "
                    f"{len(before)} -> {len(after)}"))
        return _finish(diags, collect)
    for k, ((blo, bhi), (alo, ahi)) in enumerate(zip(before, after)):
        if alo < blo or ahi > bhi:
            diags.append(Diagnostic(
                check="range.envelope", stage=stage,
                message=f"class {k} score interval widened from "
                        f"[{blo}, {bhi}] to [{alo}, {ahi}] — the rewrite "
                        "is not value-preserving"))
    return _finish(diags, collect)


def check_observed(circuit: Circuit, x_uint8, *,
                   step_semantics: str = "strict",
                   ranges: RangeAnalysis | None = None) -> None:
    """Execute the circuit on a uint8 batch and check every observed
    node value against its static interval — the dynamic face of the
    range analysis (subsumes `evaluate(check_widths=True)`: the
    interval is proven to fit the width by `check_ranges`, so any
    bracketed value fits too). Raises `VerificationError` on escape."""
    if ranges is None:
        ranges = analyze_ranges(circuit)
    x = np.asarray(x_uint8)
    vals: dict[int, np.ndarray] = {}
    diags: list[Diagnostic] = []
    for n in circuit.nodes:
        if isinstance(n, LAYER_NODES):
            vals[n.id] = eval_layer(n, vals, x)
        elif isinstance(n, InputCompare):
            vals[n.id] = (
                x[:, n.pixel].astype(np.int64) > n.threshold).astype(np.int64)
        elif isinstance(n, WeightedSum):
            acc = np.zeros(x.shape[0], dtype=np.int64)
            for t in n.terms:
                acc += t.weight * vals[t.src]
            vals[n.id] = acc
        elif isinstance(n, SignStep):
            v = vals[n.src]
            vals[n.id] = (
                v > 0 if step_semantics == "strict" else v >= 0
            ).astype(np.int64)
        elif isinstance(n, Argmax):
            vals[n.id] = np.argmax(argmax_scores(n, vals, x.shape[0]), axis=1)
        r = ranges.ranges[n.id]
        v = vals[n.id]
        lo, hi = int(v.min(initial=0)), int(v.max(initial=0))
        if lo < r.lo or hi > r.hi:
            diags.append(Diagnostic(
                check="range.observed", node=n.id,
                message=f"observed values span [{lo}, {hi}] outside the "
                        f"static interval [{r.lo}, {r.hi}]"))
    _finish(diags, collect=False)


def analyze(circuit: Circuit, *, after_pass: str | None = None,
            stage: str | None = None, collect: bool = False
            ) -> tuple[RangeAnalysis, list[Diagnostic]]:
    """The compile driver's one-shot: structural verification + range
    proofs in a single call. Returns (ranges, diagnostics); raises
    unless `collect=True`."""
    terms = _extract_terms(circuit)
    diags = verify_circuit(circuit, after_pass=after_pass, stage=stage,
                           collect=True, _terms=terms)
    ranges = analyze_ranges(circuit, _terms=terms)
    diags += check_ranges(circuit, ranges, stage=stage, collect=True)
    return ranges, _finish(diags, collect)


# ---------------------------------------------------------------------------
# Proof summary (persisted with the Artifact)
# ---------------------------------------------------------------------------

def proof_summary(circuit: Circuit,
                  ranges: RangeAnalysis | None = None) -> dict:
    """The JSON-stable certificate `Session.compile_resolved` stamps on
    every Artifact (and `meta.json` persists): what the range analysis
    proved about the shipped circuit. `slack_bits` totals the bits the
    symmetric sizing bound spends beyond what the exact intervals need
    — the headroom a future interval-sized emitter could reclaim."""
    if ranges is None:
        ranges = analyze_ranges(circuit)
    sums = [n for n in circuit.nodes if isinstance(n, WeightedSum)]
    layered = [n for n in circuit.nodes if isinstance(n, (Conv, Dense))]
    layer_widths: dict[str, int] = {}
    max_abs = 0
    slack = 0
    for n in layered:
        lo, hi = ranges.units[n.id]
        acc = max(int(np.abs(lo).max(initial=0)), int(np.abs(hi).max(initial=0)))
        layer_widths[str(n.layer)] = signed_width(acc)
        max_abs = max(max_abs, acc)
    for n in sums:
        r = ranges.ranges[n.id]
        key = str(n.layer)
        layer_widths[key] = max(layer_widths.get(key, 0), r.width)
        max_abs = max(max_abs, r.max_abs)
        slack += r.width - signed_width(r.max_abs)
    return {
        "format": _SUMMARY_FORMAT,
        "nodes": len(circuit.nodes),
        "sum_nodes": len(sums),
        "terms": sum(len(n.terms) for n in sums),
        "max_width": max((layer_widths[k] for k in layer_widths), default=0),
        "max_abs_acc": max_abs,
        "layer_widths": layer_widths,
        "slack_bits": slack,
        "int32_safe": all(
            ranges.ranges[n.id].bound <= INT32_MAX for n in sums)
        and not any(_check_layer(circuit, n, ranges, None) for n in layered),
        "layer_nodes": len(layered),
        "verified": True,
    }


def summary_row(summary: Mapping) -> str:
    """One-line rendering of a proof summary for `artifact.report()`."""
    n = summary['sum_nodes'] + summary.get('layer_nodes', 0)
    return (f"analysis: proved {n} accumulators fit "
            f"<= {summary['max_width']} bits (max |acc| "
            f"{summary['max_abs_acc']}, slack {summary['slack_bits']} bits, "
            f"int32_safe={str(bool(summary['int32_safe'])).lower()})")


# ---------------------------------------------------------------------------
# ExecutionPlan certification
# ---------------------------------------------------------------------------

def _unpack_words(words: np.ndarray) -> np.ndarray:
    """uint32 (..., W, N) -> {0,1} int64 (..., W*32, N) (bit i of word j
    is packed lane 32*j + i, matching `plan.decompose_planes`)."""
    shifts = np.arange(PACK_LANES, dtype=np.uint32)
    bits = (words[..., :, None, :] >> shifts[None, :, None]) & np.uint32(1)
    lead = words.shape[:-2]
    return bits.reshape(
        *lead, words.shape[-2] * PACK_LANES, words.shape[-1]).astype(np.int64)


def verify_plan(plan: ExecutionPlan, *, stage: str | None = None,
                collect: bool = False) -> list[Diagnostic]:
    """Certify an ExecutionPlan's form invariants (see module doc):
    layer chaining, packed lane-padding exactness, bit-plane
    losslessness and magnitude coverage, int32 accumulation safety."""
    diags: list[Diagnostic] = []

    def bad(check: str, message: str, layer: int | None = None) -> None:
        where = message if layer is None else f"layer {layer}: {message}"
        diags.append(Diagnostic(check=check, message=where, stage=stage))

    if not plan.layers:
        bad("plan.empty", "plan has no layers")
        return _finish(diags, collect)
    if plan.conv:
        _verify_conv_plan(plan, bad)
        return _finish(diags, collect)

    for i, layer in enumerate(plan.layers):
        want_act = STEP if i < plan.depth - 1 else ARGMAX
        if layer.activation != want_act:
            bad("plan.activation",
                f"activation {layer.activation!r}, expected {want_act!r}", i)
        want_ndim = 3 if plan.stacked else 2
        if layer.weights.ndim != want_ndim:
            bad("plan.stacked",
                f"weights ndim {layer.weights.ndim}, expected {want_ndim}", i)
            return _finish(diags, collect)
        if plan.stacked and layer.weights.shape[0] != plan.n_models:
            bad("plan.stacked",
                f"model axis {layer.weights.shape[0]} != n_models "
                f"{plan.n_models}", i)

    # layer chaining: fan_in of layer l+1 equals fan_out of layer l
    # (padded up to a lane multiple in the packed forms); layer 0 reads
    # the binarized inputs.
    def padded(k: int) -> int:
        if not plan.packed:
            return k
        return -(-k // PACK_LANES) * PACK_LANES if k else 0

    expect = padded(plan.n_inputs)
    true_fan_in = plan.n_inputs
    for i, layer in enumerate(plan.layers):
        if layer.fan_in != expect:
            bad("plan.chain",
                f"fan_in {layer.fan_in} != expected {expect} "
                "(predecessor fan_out)", i)
        if plan.packed:
            if layer.fan_in % PACK_LANES:
                bad("plan.pack",
                    f"packed fan_in {layer.fan_in} is not a multiple of "
                    f"{PACK_LANES}", i)
            if layer.words != layer.fan_in // PACK_LANES:
                bad("plan.pack",
                    f"words {layer.words} != fan_in // {PACK_LANES}", i)
            # lane padding exactness: every pad row must be zero, or a
            # padded activation bit could couple into a real score
            pad = layer.weights[..., true_fan_in:, :]
            if pad.size and np.any(pad != 0):
                bad("plan.pad-exact",
                    f"nonzero weights in the {layer.fan_in - true_fan_in} "
                    "zero-pad rows", i)
        if plan.bitplanes:
            _verify_planes(layer, i, bad)
        # int32 accumulation safety at the actual fan-in: the worst
        # column's sum of |w| bounds what the popcount kernel can
        # accumulate for one output
        mags = np.abs(layer.weights.astype(np.int64)).sum(axis=-2)
        worst = int(mags.max(initial=0))
        if worst > INT32_MAX:
            bad("plan.int32",
                f"max column magnitude {worst} exceeds int32", i)
        true_fan_in = layer.fan_out
        expect = padded(layer.fan_out)
    return _finish(diags, collect)


def _verify_conv_plan(plan: ExecutionPlan, bad) -> None:
    """A conv plan's chain: each layer reads the shape the one before it
    gives, one threshold a unit, int32-safe columns; a step everywhere
    but the last (dense, argmax) layer."""
    shape: tuple = tuple(plan.input_shape or ())
    for i, layer in enumerate(plan.layers):
        want_act = STEP if i < plan.depth - 1 else ARGMAX
        if layer.activation != want_act:
            bad("plan.activation",
                f"activation {layer.activation!r}, expected {want_act!r}", i)
        if layer.kind != "dense" and tuple(layer.in_shape or ()) != shape:
            bad("plan.chain", f"reads a {layer.in_shape} map, gets {shape}", i)
            return
        if layer.kind == "pool":
            shape = (shape[0] // layer.size, shape[1] // layer.size, shape[2])
            continue
        units = layer.weights.shape[-1]
        if layer.thresholds is None or layer.thresholds.shape != (units,):
            bad("plan.thresholds", f"needs {units} thresholds", i)
        if layer.kind == "conv":
            kh, kw, cin, cout = layer.weights.shape
            if cin != shape[2] or kh > shape[0] or kw > shape[1]:
                bad("plan.chain", f"kernel {layer.weights.shape} on a {shape} map", i)
                return
            shape = (shape[0] - kh + 1, shape[1] - kw + 1, cout)
            mags = np.abs(layer.weights.astype(np.int64)).sum(axis=(0, 1, 2))
        else:
            if layer.fan_in != int(np.prod(shape)):
                bad("plan.chain", f"fan_in {layer.fan_in} != {int(np.prod(shape))}", i)
                return
            shape = (units,)
            mags = np.abs(layer.weights.astype(np.int64)).sum(axis=0)
        if int(mags.max(initial=0)) * 255 > INT32_MAX:
            bad("plan.int32", "max column magnitude exceeds int32", i)


def _verify_planes(layer, i: int, bad) -> None:
    if layer.pos_planes is None or layer.neg_planes is None \
            or layer.n_planes is None:
        bad("plan.planes", "bit-plane form with no planes materialized", i)
        return
    if layer.pos_planes.shape != layer.neg_planes.shape:
        bad("plan.planes",
            f"pos/neg plane shapes differ: {layer.pos_planes.shape} vs "
            f"{layer.neg_planes.shape}", i)
        return
    if layer.pos_planes.shape[-3] != layer.n_planes:
        bad("plan.planes",
            f"plane axis {layer.pos_planes.shape[-3]} != n_planes "
            f"{layer.n_planes}", i)
        return
    mag = int(np.abs(layer.weights).max(initial=0))
    need = max(1, mag.bit_length())
    if layer.n_planes < need:
        bad("plan.planes-range",
            f"{layer.n_planes} planes cannot cover max |w| = {mag} "
            f"(needs {need})", i)
    if np.any(layer.pos_planes & layer.neg_planes):
        bad("plan.planes-disjoint",
            "a weight bit is set in both the positive and negative "
            "plane", i)
    # losslessness: the planes must reconstruct the int32 matrix bit
    # for bit — w = sum_b 2^b (unpack(pos_b) - unpack(neg_b))
    pos = _unpack_words(layer.pos_planes)
    neg = _unpack_words(layer.neg_planes)
    shifts = (1 << np.arange(layer.pos_planes.shape[-3], dtype=np.int64))
    recon = ((pos - neg)
             * shifts[:, None, None]).sum(axis=-3)
    if not np.array_equal(recon, layer.weights.astype(np.int64)):
        bad("plan.planes-lossless",
            "bit-plane decomposition does not reconstruct the weight "
            "matrix", i)


# ---------------------------------------------------------------------------
# Tile legality (consumed by KernelTuner)
# ---------------------------------------------------------------------------

def _rup(x: int, m: int = 8) -> int:
    # mirrors kernels.binary_matvec's clamping of tiny dims
    return max(m, ((x + m - 1) // m) * m)


def effective_tiles(plan: ExecutionPlan, form: str, blocks: Mapping,
                    batch: int) -> tuple:
    """The per-layer (bm, bn, bk/bkw) the kernels will ACTUALLY run
    after clamping a candidate's block sizes to the problem dims —
    two candidates with equal effective tiles launch identical grids
    (see `binary_matmul*`'s `min(b·, _rup(dim))` clamps). The fusednet
    megakernel has no fan-out tiling, so its per-layer tiles are
    (bm, bkw) pairs — candidates differing only in `bn` clamp to the
    same megakernel and dedupe."""
    bm, bn, bkw = int(blocks["bm"]), int(blocks["bn"]), int(blocks["bkw"])
    tiles = []
    fan_in = plan.n_inputs
    for layer in plan.layers:
        n = layer.fan_out
        if form == "fusednet":
            k_eff = min(bkw, max(-(-fan_in // PACK_LANES), 1))
            tiles.append((min(bm, _rup(batch)), k_eff))
        elif form == "dense":
            k_eff = min(bkw * PACK_LANES, _rup(fan_in))
            tiles.append((min(bm, _rup(batch)), min(bn, _rup(n)), k_eff))
        else:
            # packed/planes kernels see KW = ceil(fan_in / 32) lane words
            k_eff = min(bkw, max(-(-fan_in // PACK_LANES), 1))
            tiles.append((min(bm, _rup(batch)), min(bn, _rup(n)), k_eff))
        fan_in = n
    return tuple(tiles)


# A TPU vreg holds 128 lanes: Mosaic takes a block whose last dim is the
# whole (padded) array dim or a multiple of this, and refuses any other.
TPU_LANES = 128


def _lane_reason(plan: ExecutionPlan, form: str, blocks: Mapping,
                 batch: int) -> str | None:
    """Why the TPU compiler would refuse this candidate's lane tiling,
    or None. In the per-layer kernels the K tile is the last dim of the
    activation block (bits for dense, words for packed/planes) and the
    fan-out tile the last dim of the weight and output blocks. The
    fusednet megakernel keeps whole arrays resident and writes a
    (bm, 1) column, so it tiles no lane dim."""
    if form == "fusednet":
        return None
    fan_in = plan.n_inputs
    tiles = effective_tiles(plan, form, blocks, batch)
    for i, ((_, bn, bk), layer) in enumerate(zip(tiles, plan.layers)):
        k_full = (fan_in if form == "dense"
                  else max(-(-fan_in // PACK_LANES), 1))
        for axis, tile, full in (("K", bk, k_full),
                                 ("fan-out", bn, layer.fan_out)):
            if tile < full and tile % TPU_LANES:
                return (f"layer {i} {axis} tile {tile} is neither the "
                        f"whole width {full} nor a multiple of "
                        f"{TPU_LANES} lanes")
        fan_in = layer.fan_out
    return None


# VMEM budget for the whole-net megakernel: everything it keeps resident
# per grid step must fit one TPU core's vector memory (~16 MiB).
FUSEDNET_VMEM_BYTES = 16 * 1024 * 1024


def fusednet_vmem_bytes(plan: ExecutionPlan, *, bm: int,
                        bkw: int | None = None, batch: int | None = None
                        ) -> int:
    """Estimated per-grid-step VMEM residency of the fusednet megakernel
    for this plan, computed analytically from layer geometry and weight
    magnitudes (no plane decomposition is materialized — this runs per
    tuner candidate). Mirrors `MegakernelView.vmem_bytes`: all layers'
    bit-plane weights (one model's worth when stacked) + the input tile
    + the peak per-layer working set."""
    if batch is not None:
        bm = min(bm, _rup(batch))
    weight = 0
    peak = 0
    fan_in = plan.n_inputs
    depth = plan.depth
    for i, layer in enumerate(plan.layers):
        w = max(1, -(-fan_in // PACK_LANES))
        hidden = i < depth - 1
        n = layer.fan_out
        n_pad = (max(1, -(-n // PACK_LANES)) * PACK_LANES if hidden
                 else max(1, n))
        p = max(1, int(np.abs(layer.weights).max(initial=0)).bit_length())
        weight += 2 * p * w * n_pad * 4
        ck = min(bkw, w) if bkw else w
        work = 2 * bm * ck * n_pad * 4 + bm * n_pad * 4 + bm * w * 4
        peak = max(peak, work)
        fan_in = n
    return weight + bm * plan.n_inputs + peak + bm * 4


def tile_report(plan: ExecutionPlan, candidates: Sequence[Mapping], *,
                batch: int, multi: bool = False
                ) -> tuple[list, list]:
    """Split a candidate grid into (legal, rejected) where rejected is
    [(candidate, reason), ...]: non-positive blocks, lane tiles the TPU
    compiler refuses, fusednet residency over the VMEM budget, and
    clamp-duplicates of an earlier candidate (searching both wastes a
    measurement on the same kernel)."""
    legal: list = []
    rejected: list = []
    seen: dict = {}
    for cand in candidates:
        reason = _tile_reason(plan, cand, batch=batch, seen=seen)
        if reason is None:
            legal.append(cand)
        else:
            rejected.append((cand, reason))
    return legal, rejected


def _tile_reason(plan: ExecutionPlan, cand: Mapping, *, batch: int,
                 seen: dict) -> str | None:
    form = cand.get("form", plan.form)
    for k in ("bm", "bn", "bkw"):
        v = cand.get(k)
        if v is not None and int(v) < 1:
            return f"non-positive block size {k}={v}"
    blocks = {k: cand.get(k) for k in ("bm", "bn", "bkw")}
    if any(v is None for v in blocks.values()):
        return None                      # partial candidate: cannot judge
    lanes = _lane_reason(plan, form, blocks, batch)
    if lanes is not None:
        return f"{form}: {lanes} (the TPU compiler refuses it)"
    if form == "fusednet":
        need = fusednet_vmem_bytes(
            plan, bm=int(blocks["bm"]), bkw=int(blocks["bkw"]), batch=batch)
        if need > FUSEDNET_VMEM_BYTES:
            return (f"fusednet residency {need} B exceeds the "
                    f"{FUSEDNET_VMEM_BYTES} B VMEM budget")
    eff = (form, effective_tiles(plan, form, blocks, batch))
    prior = seen.get(eff)
    if prior is not None:
        return (f"clamps to the same effective tiles as candidate "
                f"{prior} — duplicate kernel")
    seen[eff] = dict(cand)
    return None


def tile_legality(plan: ExecutionPlan, *, batch: int,
                  multi: bool = False) -> Callable[[Mapping], str | None]:
    """A fresh legality closure for one tuning search: `legal(cand)`
    returns None (keep) or a rejection reason. Stateful — it remembers
    effective tiles already admitted — so build one per search."""
    seen: dict = {}

    def legal(cand: Mapping) -> str | None:
        return _tile_reason(plan, cand, batch=batch, seen=seen)

    return legal


# ---------------------------------------------------------------------------
# Stack compatibility (consumed by the serving layer)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StackReport:
    """Why a version set can (or cannot) share one stacked dispatch.
    `diagnostics` is empty when `compatible`; otherwise each entry
    names the disagreeing axis (stack.depth / stack.threshold /
    stack.inputs / stack.classes) or the version whose circuit has no
    layered tensor form (stack.irregular)."""
    compatible: bool
    n_versions: int
    diagnostics: tuple = ()

    @property
    def reason(self) -> str:
        return self.diagnostics[0].check if self.diagnostics else "none"

    def describe(self) -> str:
        if self.compatible:
            return f"{self.n_versions} versions stack-compatible"
        return (f"{self.n_versions} versions cannot stack:\n  "
                + "\n  ".join(d.row() for d in self.diagnostics))


def diagnose_stack(items: Sequence) -> StackReport:
    """Structured stack-compatibility report over circuits or dense
    single-net plans — the checks `plan.stack_plans` enforces by
    raising, surfaced as diagnostics the serving layer can record
    instead of swallowing."""
    diags: list[Diagnostic] = []
    plans: list[ExecutionPlan] = []
    for i, item in enumerate(items):
        if isinstance(item, ExecutionPlan):
            plans.append(item)
            continue
        kind = layer_kind(item)
        if kind is not None:
            diags.append(Diagnostic(
                check="stack.layer-kind", stage=f"version {i}",
                message=f"stacked dispatch serves dense chains; this version "
                        f"has a {kind} layer"))
            continue
        try:
            plans.append(lower_circuit(item))
        except IrregularCircuitError as e:
            diags.append(Diagnostic(
                check="stack.irregular", stage=f"version {i}",
                message=str(e)))
    if not items:
        diags.append(Diagnostic(check="stack.empty",
                                message="no versions to stack"))
    if diags:
        return StackReport(compatible=False, n_versions=len(items),
                           diagnostics=tuple(diags))
    for i, p in enumerate(plans):
        if p.conv:
            diags.append(Diagnostic(
                check="stack.layer-kind", stage=f"version {i}",
                message="stacked dispatch serves dense chains; this version "
                        "is a conv plan"))
        elif p.packed or p.stacked:
            diags.append(Diagnostic(
                check="stack.form", stage=f"version {i}",
                message="stacking takes dense single-net plans"))

    def axis(check: str, label: str, values: list) -> None:
        if len(set(values)) > 1:
            diags.append(Diagnostic(
                check=check,
                message=f"versions disagree on {label}: "
                        f"{sorted(set(values))}"))

    axis("stack.depth", "depth", [p.depth for p in plans])
    axis("stack.threshold", "input threshold",
         [p.input_threshold for p in plans])
    axis("stack.inputs", "input width", [p.n_inputs for p in plans])
    axis("stack.classes", "class count", [p.n_classes for p in plans])
    return StackReport(compatible=not diags, n_versions=len(items),
                       diagnostics=tuple(diags))


# ---------------------------------------------------------------------------
# ArtifactStore linter (`python -m repro.netgen.analysis <store>`)
# ---------------------------------------------------------------------------

_META_REQUIRED = ("format", "digest", "pipeline", "target", "kind",
                  "pass_stats", "cost", "timings")


def lint_store(root) -> dict[str, list[Diagnostic]]:
    """Re-verify every entry of an `ArtifactStore` directory. Returns
    {key: diagnostics} for the entries that FAILED (clean stores map to
    {}). Checks: meta schema, circuit invariants + range proofs,
    content-address consistency (a mismatched key is a stale entry
    compiled by different sources or schema), recomputed cost and
    proof-summary agreement, plan-form certification for callables."""
    # lazy imports: session imports this module for the compile driver
    from repro.netgen.backends.cost import logic_cells
    from repro.netgen.graph import circuit_from_arrays
    from repro.netgen.pipeline import PipelineSpec
    from repro.netgen.session import _FORMAT, artifact_key

    root = Path(root).expanduser()
    if not root.is_dir():
        raise FileNotFoundError(f"no artifact store at {root}")
    failures: dict[str, list[Diagnostic]] = {}
    for entry in sorted(p for p in root.iterdir() if p.is_dir()):
        if entry.name.startswith(".tmp-"):
            continue
        diags = _lint_entry(entry, _FORMAT, artifact_key, PipelineSpec,
                            circuit_from_arrays, logic_cells)
        if diags:
            failures[entry.name] = diags
    return failures


def _lint_entry(entry: Path, fmt: str, artifact_key, PipelineSpec,
                circuit_from_arrays, logic_cells) -> list[Diagnostic]:
    key = entry.name
    diags: list[Diagnostic] = []

    def bad(check: str, message: str) -> None:
        diags.append(Diagnostic(check=check, message=message, stage=key[:12]))

    try:
        with open(entry / "meta.json") as f:
            meta = json.load(f)
    except Exception as e:
        bad("store.meta", f"unreadable meta.json: {e}")
        return diags
    if meta.get("format") != fmt:
        bad("store.format",
            f"format {meta.get('format')!r} != expected {fmt!r}")
        return diags
    missing = [k for k in _META_REQUIRED if k not in meta]
    if missing:
        bad("store.fields", f"meta.json missing {missing}")
        return diags

    try:
        with np.load(entry / "circuit.npz") as z:
            circuit = circuit_from_arrays(z)
    except Exception as e:
        bad("store.circuit", f"unreadable circuit.npz: {e}")
        return diags
    for d in verify_circuit(circuit, stage=key[:12], collect=True):
        diags.append(d)
    ranges = analyze_ranges(circuit)
    diags.extend(check_ranges(circuit, ranges, stage=key[:12], collect=True))

    try:
        spec = PipelineSpec.coerce(meta["pipeline"])
        want = artifact_key(meta["digest"], spec, meta["target"])
    except Exception as e:
        bad("store.key", f"cannot recompute content address: {e}")
        want = None
    if want is not None and want != key:
        bad("store.key",
            "stale entry: stored content address does not match the "
            "current compiler sources/spec (recompute "
            f"{want[:12]}... != {key[:12]}...)")

    cost = logic_cells(circuit, analysis=ranges).as_dict()
    if cost != meta["cost"]:
        bad("store.cost",
            f"recomputed cell estimate {cost} != stored {meta['cost']}")
    recorded = meta.get("analysis")
    if recorded is not None and recorded != proof_summary(circuit, ranges):
        bad("store.analysis",
            "stored proof summary does not match a recompute")
    if meta["kind"] == "text" and not (entry / "artifact.txt").exists():
        bad("store.artifact", "text artifact with no artifact.txt")
    if meta["kind"] == "callable":
        form = meta.get("plan_form") or "dense"
        if form not in ("dense", "packed", "planes", "conv"):
            bad("store.plan", f"unknown plan_form {form!r}")
        else:
            try:
                plan = lower_circuit(circuit, form=form)
            except IrregularCircuitError as e:
                bad("store.plan", f"callable artifact's circuit has no "
                                  f"layered form: {e}")
            else:
                diags.extend(verify_plan(plan, stage=key[:12], collect=True))
    return diags


def main(argv: Sequence[str] | None = None) -> int:
    """CLI: lint every artifact in a store directory; exit 0 when all
    entries verify, 1 with one structured diagnostic line per failure
    otherwise."""
    parser = argparse.ArgumentParser(
        prog="python -m repro.netgen.analysis",
        description="lint every artifact in a netgen ArtifactStore")
    parser.add_argument("store", help="ArtifactStore root directory")
    parser.add_argument("-q", "--quiet", action="store_true",
                        help="suppress the per-entry OK lines")
    args = parser.parse_args(argv)
    try:
        failures = lint_store(args.store)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    root = Path(args.store).expanduser()
    keys = sorted(p.name for p in root.iterdir()
                  if p.is_dir() and not p.name.startswith(".tmp-"))
    for key in keys:
        if key in failures:
            for d in failures[key]:
                print(f"FAIL {key[:12]} {d.row()}")
        elif not args.quiet:
            print(f"ok   {key[:12]}")
    n_bad = len(failures)
    print(f"linted {len(keys)} artifact(s): "
          f"{len(keys) - n_bad} ok, {n_bad} failed")
    return 1 if failures else 0


if __name__ == "__main__":      # pragma: no cover — exercised in CI
    sys.exit(main())
