"""Typed circuit IR for the netgen compiler.

The paper's "hardware generation" script (§IV-§V) walks trained weight
matrices and prints Verilog directly. Here the same network is first
lowered into an explicit *circuit graph* — the representation every
optimization pass and every backend operates on:

  InputCompare  — paper §III.B / Fig. 6 line 5: `pixel > threshold` -> 1 bit
  WeightedSum   — a signed accumulator node: sum of weighted single-bit (or
                  shared sub-sum) sources. The paper's `hi`/`fi` wires.
  SignStep      — paper §III.A + §V.D: the step activation, realized on
                  hardware as the (negated) MSB of the accumulator.
  Argmax        — paper Fig. 6 line 15: the priority-mux comparison network
                  producing the predicted class index.

A convolutional net (`repro.core.convnet.ConvNet`) lowers to layer-level
nodes instead, one per layer, so its IR holds O(layers) objects rather
than one `Term` per multiply-accumulate (about 59M for FINN's CNV):

  TensorInput   — the (H, W, C) image of a request row, binarized by one
                  threshold ("compare") or read as 8-bit values ("pixels").
  Conv          — a valid convolution with one threshold per channel:
                  `acc > t` -> a {0, 1} map.
  MaxPool       — a max-pool of a {0, 1} map (an OR).
  Dense         — a dense layer over the HWC-flattened input, `acc > t`
                  (`step`) or the scores `acc - t` that feed the Argmax.

The per-unit optimization passes leave layer nodes alone; the Verilog
and cost backends, the tuner and the explorer refuse them
(`LayerKindError`).

Nodes are immutable and identified by dense integer ids; a `Circuit` is a
topologically-ordered tuple of nodes. Every value-carrying node has a
*signed bit-width* inferred exactly from the maximum magnitude it can
reach (`value_bound` / `signed_width`), which is what the Verilog backend
uses to size wires and what the interpreter uses to check that no
emitted accumulator could overflow.

`evaluate` is the reference interpreter: it executes the circuit with
the exact node semantics over a uint8 input batch. It is the arbiter in
backend-parity tests (jnp / pallas / Verilog must all agree with it).

A faithfulness note on the step node: the compiled TPU backends (and the
paper's *software* ladder, `quantize.predict_l3`) compute `acc > 0`,
while the paper's emitted Verilog uses the MSB trick `~acc[msb]`, i.e.
`acc >= 0`. The two differ only when an accumulator is exactly zero —
never observed on trained nets, but reachable on adversarial ones.
`evaluate(..., step_semantics=...)` exposes both so each backend can be
checked against the semantics it actually implements.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Union

import numpy as np

NodeId = int


@dataclasses.dataclass(frozen=True)
class Term:
    """One addend of a WeightedSum: `weight * value(src)`."""
    weight: int
    src: NodeId


@dataclasses.dataclass(frozen=True)
class InputCompare:
    """1-bit comparator on one raw input component: `x[pixel] > threshold`."""
    id: NodeId
    pixel: int
    threshold: int


@dataclasses.dataclass(frozen=True)
class WeightedSum:
    """Signed integer accumulator: `sum(t.weight * value(t.src))`.

    `layer` tags which dense layer the node was lowered from (1-based);
    pass-created sharing nodes keep the layer of their consumers. Backends
    that reconstruct dense matrices group by this tag.
    """
    id: NodeId
    terms: tuple[Term, ...]
    layer: int


@dataclasses.dataclass(frozen=True)
class SignStep:
    """Step activation of one accumulator (1 bit)."""
    id: NodeId
    src: NodeId


@dataclasses.dataclass(frozen=True)
class Argmax:
    """Priority argmax over the final accumulators (first max wins)."""
    id: NodeId
    srcs: tuple[NodeId, ...]


@dataclasses.dataclass(frozen=True, eq=False)
class TensorInput:
    """The image input of a layer-level circuit: `shape` (H, W, C) read
    HWC row-major from the uint8 request row. `mode` "compare" gives
    `x > threshold` (1 bit a pixel); "pixels" gives the 8-bit values."""
    id: NodeId
    shape: tuple[int, int, int]
    mode: str
    threshold: int


@dataclasses.dataclass(frozen=True, eq=False)
class Conv:
    """Valid stride-1 convolution of the (H, W, C) map `src` by integer
    `weights` (kh, kw, C, C_out), then `acc > thresholds[c]` -> {0, 1}.
    `layer` numbers the weighted layers from 1."""
    id: NodeId
    src: NodeId
    weights: np.ndarray
    thresholds: np.ndarray
    layer: int


@dataclasses.dataclass(frozen=True, eq=False)
class MaxPool:
    """Max over non-overlapping `size` x `size` windows of a map."""
    id: NodeId
    src: NodeId
    size: int


@dataclasses.dataclass(frozen=True, eq=False)
class Dense:
    """Integer `weights` (k, n) over the HWC-flattened `src`; `step`:
    `acc > thresholds` -> {0, 1}, else the scores `acc - thresholds`."""
    id: NodeId
    src: NodeId
    weights: np.ndarray
    thresholds: np.ndarray
    layer: int
    step: bool


Node = Union[InputCompare, WeightedSum, SignStep, Argmax,
             TensorInput, Conv, MaxPool, Dense]
LAYER_NODES = (TensorInput, Conv, MaxPool, Dense)
_LAYER_KIND = {TensorInput: "input", Conv: "conv", MaxPool: "pool", Dense: "dense"}


def node_srcs(n: Node) -> tuple[NodeId, ...]:
    """The ids a node reads."""
    if isinstance(n, WeightedSum):
        return tuple(t.src for t in n.terms)
    if isinstance(n, (SignStep, Conv, MaxPool, Dense)):
        return (n.src,)
    if isinstance(n, Argmax):
        return n.srcs
    return ()


class LayerKindError(ValueError):
    """Raised where a component handles per-unit dense circuits or plans
    only and meets a layer-level (conv) one. The message names the
    component and the layer kind."""


def layer_kind(circuit: "Circuit") -> str | None:
    """The first weighted or pooling layer kind of a layer-level circuit
    ("conv", "pool", "dense"), or None for a per-unit circuit."""
    kinds = [_LAYER_KIND[type(n)] for n in circuit.nodes if isinstance(n, LAYER_NODES)]
    if not kinds:
        return None
    return next((k for k in kinds if k in ("conv", "pool")), kinds[-1])


def refuse_layers(circuit: "Circuit", what: str) -> None:
    """Raise `LayerKindError` naming `what` and the layer kind when the
    circuit is layer-level."""
    kind = layer_kind(circuit)
    if kind is not None:
        raise LayerKindError(
            f"{what} handles per-unit dense circuits; this circuit has a "
            f"{kind} layer (a layer-level ConvNet circuit)")


class IrregularCircuitError(ValueError):
    """Raised when a backend needs the regular layered form (dense weight
    matrices) but the circuit has been rewritten into a general DAG
    (e.g. by common-addend sharing)."""


@dataclasses.dataclass(frozen=True)
class Circuit:
    """A complete inference circuit: uint8 input vector -> class index.

    `nodes` is topologically ordered (every Term.src / SignStep.src /
    Argmax.src precedes its consumer). `output` is the Argmax node id.
    """
    n_inputs: int
    input_threshold: int
    nodes: tuple[Node, ...]
    output: NodeId

    # -- structure helpers ---------------------------------------------------

    def node(self, nid: NodeId) -> Node:
        return self._by_id()[nid]

    def _by_id(self) -> dict[NodeId, Node]:
        cache = getattr(self, "_id_cache", None)
        if cache is None or len(cache) != len(self.nodes):
            cache = {n.id: n for n in self.nodes}
            object.__setattr__(self, "_id_cache", cache)
        return cache

    def by_kind(self, kind: type) -> list[Node]:
        return [n for n in self.nodes if isinstance(n, kind)]

    @property
    def depth(self) -> int:
        """Number of weighted layers the circuit was lowered from."""
        tagged = [n.layer for n in self.nodes
                  if isinstance(n, (WeightedSum, Conv, Dense))]
        return max(tagged, default=0)

    def consumers(self) -> dict[NodeId, list[NodeId]]:
        """Map node id -> ids of nodes that read it."""
        out: dict[NodeId, list[NodeId]] = {n.id: [] for n in self.nodes}
        for n in self.nodes:
            for s in node_srcs(n):
                out[s].append(n.id)
        return out

    def validate(self) -> None:
        """Check topological order, id uniqueness, and output wiring.

        This is the quick inline sanity check; the full structural
        verifier (kind-specific arity/field invariants, pass
        postconditions, range/overflow proofs) lives in
        `repro.netgen.analysis.verify_circuit` and runs at every pass
        boundary under `PipelineSpec.run(verify=True)`."""
        seen: set[NodeId] = set()
        for n in self.nodes:
            if n.id in seen:
                raise ValueError(f"duplicate node id {n.id}")
            for s in node_srcs(n):
                if s not in seen:
                    raise ValueError(
                        f"node {n.id} reads {s} before it is defined")
            seen.add(n.id)
        if self.output not in seen or not isinstance(self.node(self.output), Argmax):
            raise ValueError("output must name an Argmax node")


# ---------------------------------------------------------------------------
# Bit-width inference
# ---------------------------------------------------------------------------

def value_bounds(circuit: Circuit) -> dict[NodeId, int]:
    """Exact per-node bound on |value|: single-bit nodes are 1; a sum node
    reaches at most `sum(|w| * bound(src))`. One topological sweep."""
    bound: dict[NodeId, int] = {}
    for n in circuit.nodes:
        if isinstance(n, (InputCompare, SignStep)):
            bound[n.id] = 1
        elif isinstance(n, WeightedSum):
            bound[n.id] = sum(abs(t.weight) * bound[t.src] for t in n.terms)
        elif isinstance(n, Argmax):
            bound[n.id] = max(argmax_classes(circuit, n) - 1, 1)
        elif isinstance(n, TensorInput):
            bound[n.id] = 255 if n.mode == "pixels" else 1
        elif isinstance(n, (Conv, MaxPool)) or (isinstance(n, Dense) and n.step):
            bound[n.id] = 1
        elif isinstance(n, Dense):           # the scores acc - t
            mag = np.abs(n.weights).sum(axis=0) * bound[n.src] + np.abs(n.thresholds)
            bound[n.id] = int(mag.max(initial=0))
    return bound


def argmax_classes(circuit: Circuit, n: Argmax) -> int:
    """Classes an Argmax ranks: one per scalar source, n per Dense one."""
    total = 0
    for s in n.srcs:
        src = circuit.node(s)
        total += src.weights.shape[1] if isinstance(src, Dense) else 1
    return total


def signed_width(bound: int) -> int:
    """Bits for a signed register holding values in [-bound, bound]."""
    return max(math.ceil(math.log2(bound + 1)) + 1, 2) if bound > 0 else 2


def node_widths(circuit: Circuit) -> dict[NodeId, int]:
    """Per-node signed bit-widths (1 for the single-bit node kinds)."""
    widths: dict[NodeId, int] = {}
    for nid, b in value_bounds(circuit).items():
        n = circuit.node(nid)
        if isinstance(n, (InputCompare, SignStep)):
            widths[nid] = 1
        elif isinstance(n, Argmax):
            k = argmax_classes(circuit, n)
            widths[nid] = max(math.ceil(math.log2(max(k, 2))), 1)
        else:
            widths[nid] = signed_width(b)
    return widths


# ---------------------------------------------------------------------------
# Layered-form extraction (for dense backends)
# ---------------------------------------------------------------------------

def as_layered_weights(circuit: Circuit) -> list[np.ndarray]:
    """Reconstruct dense int32 weight matrices from a *regular* circuit.

    Regular means: layer-l sums read only layer-(l-1) activations (inputs
    for l == 1), every hidden sum feeds exactly one SignStep, and the
    Argmax reads exactly the last layer's sums. Addend-rewritten circuits
    are fine (duplicate unit terms re-accumulate); shared/CSE circuits are
    not and raise IrregularCircuitError; layer-level circuits raise
    LayerKindError.
    """
    refuse_layers(circuit, "dense weight-matrix extraction")
    inputs = circuit.by_kind(InputCompare)
    sums = circuit.by_kind(WeightedSum)
    steps = circuit.by_kind(SignStep)
    depth = circuit.depth
    if depth == 0:
        raise IrregularCircuitError("circuit has no WeightedSum nodes")

    step_of = {s.src: s.id for s in steps}
    by_layer: dict[int, list[WeightedSum]] = {}
    for n in sums:
        by_layer.setdefault(n.layer, []).append(n)

    # activation index of each source node for the next layer up. A layer
    # pruned down to zero units yields a zero-width matrix (downstream
    # layers then sum nothing and score 0 — the constant-0 predictor).
    src_index: dict[NodeId, int] = {
        n.id: i for i, n in enumerate(sorted(inputs, key=lambda n: n.pixel))}
    mats: list[np.ndarray] = []
    for layer in range(1, depth + 1):
        cols = by_layer.get(layer, [])
        w = np.zeros((len(src_index), len(cols)), dtype=np.int32)
        next_index: dict[NodeId, int] = {}
        for j, n in enumerate(cols):
            for t in n.terms:
                if t.src not in src_index:
                    raise IrregularCircuitError(
                        f"layer {layer} sum {n.id} reads non-layer source {t.src}")
                w[src_index[t.src], j] += t.weight
            if layer < depth:
                if n.id not in step_of:
                    raise IrregularCircuitError(
                        f"hidden sum {n.id} has no SignStep")
                next_index[step_of[n.id]] = j
        mats.append(w)
        src_index = next_index
    return mats


# ---------------------------------------------------------------------------
# Array codec (for the persistent ArtifactStore)
# ---------------------------------------------------------------------------

_KIND_CODES = {InputCompare: 0, WeightedSum: 1, SignStep: 2, Argmax: 3,
               TensorInput: 4, Conv: 5, MaxPool: 6, Dense: 7}
_MODES = ("compare", "pixels")
_LAYER_META = 8          # int64 fields a layer node keeps in `lay_meta`


def circuit_to_arrays(circuit: Circuit) -> dict[str, np.ndarray]:
    """Encode a circuit (regular OR irregular DAG) as a flat dict of
    integer arrays — the on-disk form `repro.netgen.session.ArtifactStore`
    persists via `np.savez`. Compact (terms are one (host_row, weight,
    src) int64 triple each, not a Python object) and code-free (no
    pickle: the store stays loadable across refactors and trustworthy
    across processes). `circuit_from_arrays` is the exact inverse. A
    layer-level node keeps one row of `lay_meta` (its source and shape),
    its weights flattened into `lay_w` and its thresholds into `lay_t`.
    """
    kinds, ids = [], []
    cmp_pixel, cmp_thr = [], []
    sum_layer, sum_nterms, term_weight, term_src = [], [], [], []
    step_src, argmax_srcs, argmax_nsrcs = [], [], []
    lay_meta: list[list[int]] = []
    lay_w: list[np.ndarray] = []
    lay_t: list[np.ndarray] = []
    for n in circuit.nodes:
        kinds.append(_KIND_CODES[type(n)])
        ids.append(n.id)
        if isinstance(n, LAYER_NODES):
            if isinstance(n, TensorInput):
                row = [*n.shape, _MODES.index(n.mode), n.threshold]
            elif isinstance(n, MaxPool):
                row = [n.src, n.size]
            else:
                row = [n.src, n.layer, int(getattr(n, "step", True)), *n.weights.shape]
                lay_w.append(np.asarray(n.weights, np.int64).ravel())
                lay_t.append(np.asarray(n.thresholds, np.int64).ravel())
            lay_meta.append(row + [0] * (_LAYER_META - len(row)))
        elif isinstance(n, InputCompare):
            cmp_pixel.append(n.pixel)
            cmp_thr.append(n.threshold)
        elif isinstance(n, WeightedSum):
            sum_layer.append(n.layer)
            sum_nterms.append(len(n.terms))
            for t in n.terms:
                term_weight.append(t.weight)
                term_src.append(t.src)
        elif isinstance(n, SignStep):
            step_src.append(n.src)
        else:
            argmax_nsrcs.append(len(n.srcs))
            argmax_srcs.extend(n.srcs)
    i64 = lambda xs: np.asarray(xs, dtype=np.int64)  # noqa: E731
    return {
        "header": i64([circuit.n_inputs, circuit.input_threshold,
                       circuit.output]),
        "kinds": i64(kinds), "ids": i64(ids),
        "cmp_pixel": i64(cmp_pixel), "cmp_thr": i64(cmp_thr),
        "sum_layer": i64(sum_layer), "sum_nterms": i64(sum_nterms),
        "term_weight": i64(term_weight), "term_src": i64(term_src),
        "step_src": i64(step_src),
        "argmax_nsrcs": i64(argmax_nsrcs), "argmax_srcs": i64(argmax_srcs),
        "lay_meta": i64(lay_meta).reshape(-1, _LAYER_META),
        "lay_w": np.concatenate(lay_w) if lay_w else i64([]),
        "lay_t": np.concatenate(lay_t) if lay_t else i64([]),
    }


def circuit_from_arrays(arrays) -> Circuit:
    """Rebuild a circuit from `circuit_to_arrays` output (or an opened
    `np.load` of it). Validates the result before returning it."""
    a = {k: np.asarray(arrays[k]) for k in (
        "header", "kinds", "ids", "cmp_pixel", "cmp_thr", "sum_layer",
        "sum_nterms", "term_weight", "term_src", "step_src",
        "argmax_nsrcs", "argmax_srcs")}
    n_inputs, input_threshold, output = (int(v) for v in a["header"])
    layer_keys = ("lay_meta", "lay_w", "lay_t")
    if all(k in getattr(arrays, "files", arrays) for k in layer_keys):
        a.update({k: np.asarray(arrays[k]) for k in layer_keys})
    nodes: list[Node] = []
    ci = si = ti = pi = ai = aj = li = wi = hi = 0
    for kind, nid in zip(a["kinds"].tolist(), a["ids"].tolist()):
        if kind >= 4:
            m = [int(v) for v in a["lay_meta"][li]]
            li += 1
            if kind == 4:
                nodes.append(TensorInput(id=nid, shape=(m[0], m[1], m[2]),
                                         mode=_MODES[m[3]], threshold=m[4]))
                continue
            if kind == 6:
                nodes.append(MaxPool(id=nid, src=m[0], size=m[1]))
                continue
            shape = tuple(m[3:7] if kind == 5 else m[3:5])
            size = int(np.prod(shape))
            w = a["lay_w"][wi:wi + size].reshape(shape)
            t = a["lay_t"][hi:hi + shape[-1]]
            wi, hi = wi + size, hi + shape[-1]
            if kind == 5:
                nodes.append(Conv(id=nid, src=m[0], weights=w, thresholds=t, layer=m[1]))
            else:
                nodes.append(Dense(id=nid, src=m[0], weights=w, thresholds=t,
                                   layer=m[1], step=bool(m[2])))
        elif kind == 0:
            nodes.append(InputCompare(
                id=nid, pixel=int(a["cmp_pixel"][ci]),
                threshold=int(a["cmp_thr"][ci])))
            ci += 1
        elif kind == 1:
            k = int(a["sum_nterms"][si])
            terms = tuple(
                Term(weight=int(a["term_weight"][ti + j]),
                     src=int(a["term_src"][ti + j])) for j in range(k))
            nodes.append(WeightedSum(
                id=nid, terms=terms, layer=int(a["sum_layer"][si])))
            si += 1
            ti += k
        elif kind == 2:
            nodes.append(SignStep(id=nid, src=int(a["step_src"][pi])))
            pi += 1
        elif kind == 3:
            k = int(a["argmax_nsrcs"][ai])
            nodes.append(Argmax(id=nid, srcs=tuple(
                int(s) for s in a["argmax_srcs"][aj:aj + k])))
            ai += 1
            aj += k
        else:
            raise ValueError(f"unknown node kind code {kind}")
    circuit = Circuit(n_inputs=n_inputs, input_threshold=input_threshold,
                      nodes=tuple(nodes), output=output)
    circuit.validate()
    return circuit


# ---------------------------------------------------------------------------
# Reference interpreter (the semantic arbiter for every backend)
# ---------------------------------------------------------------------------

def evaluate(
    circuit: Circuit,
    x_uint8: np.ndarray,
    *,
    step_semantics: str = "strict",
    check_widths: bool = False,
) -> np.ndarray:
    """Execute the circuit on a batch of uint8 inputs (B, n_inputs).

    step_semantics: "strict" — step fires on `acc > 0` (the arithmetic the
    compiled jnp/pallas backends and `quantize.predict_l3` implement);
    "msb" — step is `~acc[msb]`, i.e. fires on `acc >= 0` (the emitted
    Verilog's §V.D MSB trick). check_widths asserts every accumulator
    stays inside its inferred signed bit-width.
    """
    if step_semantics not in ("strict", "msb"):
        raise ValueError(f"unknown step_semantics {step_semantics!r}")
    x = np.asarray(x_uint8)
    if x.ndim != 2 or x.shape[1] != circuit.n_inputs:
        raise ValueError(f"expected (B, {circuit.n_inputs}), got {x.shape}")
    widths = node_widths(circuit) if check_widths else None

    vals: dict[NodeId, np.ndarray] = {}
    out = None
    for n in circuit.nodes:
        if isinstance(n, LAYER_NODES):
            vals[n.id] = eval_layer(n, vals, x)
        elif isinstance(n, InputCompare):
            vals[n.id] = (x[:, n.pixel].astype(np.int64) > n.threshold).astype(np.int64)
        elif isinstance(n, WeightedSum):
            acc = np.zeros(x.shape[0], dtype=np.int64)
            for t in n.terms:
                acc += t.weight * vals[t.src]
            if widths is not None:
                lim = 2 ** (widths[n.id] - 1)
                assert np.all(acc >= -lim) and np.all(acc < lim), (
                    f"sum node {n.id} overflows its {widths[n.id]}-bit width")
            vals[n.id] = acc
        elif isinstance(n, SignStep):
            v = vals[n.src]
            vals[n.id] = (v > 0 if step_semantics == "strict" else v >= 0).astype(np.int64)
        elif isinstance(n, Argmax):
            out = vals[n.id] = np.argmax(argmax_scores(n, vals, x.shape[0]), axis=1)
    if out is None:
        raise ValueError("circuit has no Argmax output node")
    return vals[circuit.output]


def argmax_scores(n: Argmax, vals: dict, batch: int) -> np.ndarray:
    """(B, classes) scores an Argmax ranks: its sources' values side by
    side, a scalar source one column, a Dense source all of its units."""
    return np.concatenate([vals[s].reshape(batch, -1) for s in n.srcs], axis=1)


def eval_layer(n: Node, vals: dict, x: np.ndarray) -> np.ndarray:
    """Exact int64 value of one layer-level node over the batch `x`."""
    if isinstance(n, TensorInput):
        img = x.reshape(x.shape[0], *n.shape).astype(np.int64)
        return img if n.mode == "pixels" else (img > n.threshold).astype(np.int64)
    a = vals[n.src]
    if isinstance(n, MaxPool):
        b, h, w, c = a.shape
        k = n.size
        a = a[:, :h // k * k, :w // k * k]
        return a.reshape(b, h // k, k, w // k, k, c).max(axis=(2, 4))
    if isinstance(n, Conv):
        kh, kw = n.weights.shape[:2]
        ho, wo = a.shape[1] - kh + 1, a.shape[2] - kw + 1
        acc = np.zeros((a.shape[0], ho, wo, n.weights.shape[3]), np.int64)
        for dy in range(kh):
            for dx in range(kw):
                acc += np.einsum("bhwc,cd->bhwd", a[:, dy:dy + ho, dx:dx + wo],
                                 np.asarray(n.weights[dy, dx], np.int64))
        return (acc > n.thresholds).astype(np.int64)
    assert isinstance(n, Dense), type(n)
    acc = a.reshape(a.shape[0], -1) @ np.asarray(n.weights, np.int64)
    return (acc > n.thresholds).astype(np.int64) if n.step else acc - n.thresholds
