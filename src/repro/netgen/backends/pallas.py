"""Pallas backend: execute an ExecutionPlan on the TPU kernels.

Per-layer path (any depth) chains the `binary_matvec` kernels — the
VPU realization of the paper's L5 rewrite — with the step fused into
the layer boundary. Three datapaths, selected by the plan form
(`pallas[packed=true]`, `pallas[planes=true]`):

  dense   — activations travel as int8 {0,1} vectors into
            `binary_matmul` (one byte per wire, int32 weights).
  packed  — activations are bit-packed 32-per-uint32 word END TO END:
            the input binarizer emits packed words, every hidden step
            emits packed words (`step_pack` — no int8 activation ever
            materializes between layers), and `binary_matmul_packed`
            consumes them (one *bit* per wire; weights still int32).
  planes  — the fully bit-packed datapath: weights decomposed into
            packed signed bit-planes (`plan.planes()`) and accumulated
            by `binary_matmul_planes` as
            sum_b 2^b (popcount(x & pos_b) - popcount(x & neg_b)) —
            both operands travel as bits, the paper's selected-addends
            taken to the XNOR/AND+popcount form of the BNN-on-FPGA
            literature. Plane count tracks the post-pass weight
            magnitude range, so a quantized net moves ~2P bits of
            weight per addend instead of 32.

A fourth datapath, `pallas[fusednet=true]`, abandons the per-layer
chain entirely: the whole planes-form net (any depth, single or
stacked) runs as ONE persistent `binary_forward_planes` launch — every
layer's bit-plane weights resident in VMEM, step+repack in-register
between layers, argmax fused — via `plan.megakernel_view()`. It is the
*preferred* planes path for the stacked multi-net dispatch
(`compile_pallas_multi` upgrades `planes=true` to the megakernel,
falling back to the per-layer chain if the plan has no megakernel
view), and each predictor call is exactly one kernel launch, counted in
`netgen_kernel_launches_total{form}`.

A plan lowered from a ConvNet (`plan.conv`) runs the conv predictor
whatever the form options say (the plan chooses it, not a flag): one
jitted program per call of one `binary_conv` kernel (`netgen_conv`) per
conv layer, each with its following 2x2 pool fused, then the dense
tail on the megakernel with per-unit thresholds (`_build_convnet`).

Block sizes (`bm`, `bn`, `bkw`) are declared target options; with
`pallas[tuned=true]` they — and, when no form is forced, the
dense/packed/planes/fusednet choice itself — are grid-searched per
(plan shape x device kind) through `repro.netgen.tune` and persisted,
so a warm process never re-measures (`Session(tune_store=...)`).

The `fused` variant lowers the whole 2-layer paper net into the
single-launch `fused_mlp` kernel, the combinational-circuit analogue
(one "net" per prediction, intermediate activations never leaving
VMEM); `fused[tuned=true]` searches its batch tile.

Kernels run in Pallas interpret mode where JAX's backend is the CPU (the
tests, under `JAX_PLATFORMS=cpu`) and compile through Mosaic on a TPU
(`repro.kernels.resolve_interpret`); `python chip_smoke.py` runs the
compiled kernels on the chip.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.netgen.graph import Circuit, IrregularCircuitError, LayerKindError
from repro.netgen.plan import ExecutionPlan, lower_circuit

__all__ = ["compile_pallas", "compile_pallas_multi", "compile_fused"]

_FORMS = ("dense", "packed", "planes")
# Executable datapaths: the plan forms plus the whole-net megakernel
# (which runs the planes form, but as one persistent launch).
_DATAPATHS = ("dense", "packed", "planes", "fusednet")

# The candidate grid of the tuner and the explorer. The TPU compiler
# takes a lane tile only when it is the whole width or a multiple of 128
# (`analysis.tile_legality` rejects the rest before measuring), so
# bkw=128 is the packed/planes word tile (the whole K of any fan-in up
# to 4096 bits) and bkw=8 the dense kernel's 256-bit K tile and the
# megakernel's in-register word chunk. Every datapath keeps a legal
# candidate at every width.
TUNE_BLOCKS = (
    {"bm": 128, "bn": 128, "bkw": 128},
    {"bm": 256, "bn": 128, "bkw": 128},
    {"bm": 128, "bn": 256, "bkw": 128},
    {"bm": 128, "bn": 128, "bkw": 8},
)
_TUNE_BATCH = 256        # measurement batch: the serve layer's default cap


def _resolve_form(packed: bool, planes: bool,
                  fusednet: bool = False) -> str | None:
    """The explicitly requested datapath, or None when the caller left
    the choice open (tuned=true may then search it). `fusednet` runs
    the planes form, so planes+fusednet means fusednet; packed is a
    different activation encoding and stays exclusive."""
    if packed and (planes or fusednet):
        raise ValueError(
            "pallas: packed=true is exclusive with the bit-plane "
            "datapaths (planes=true / fusednet=true)")
    if fusednet:
        return "fusednet"
    if planes:
        return "planes"
    if packed:
        return "packed"
    return None


def _in_form(plan: ExecutionPlan, form: str) -> ExecutionPlan:
    if form in ("planes", "fusednet"):
        return plan.planes()
    if form == "packed":
        return plan.pack()
    return plan


def _blocks_kw(form: str, blocks: dict) -> dict:
    """Map the declared bm/bn/bkw options onto the kernel entry point's
    keywords (the dense kernel's K tile is in bits, not words)."""
    kw = {}
    for k in ("bm", "bn"):
        if blocks.get(k) is not None:
            kw[k] = int(blocks[k])
    if blocks.get("bkw") is not None:
        if form == "dense":
            kw["bk"] = int(blocks["bkw"]) * 32
        else:
            kw["bkw"] = int(blocks["bkw"])
    return kw


def _chain(plan: ExecutionPlan, kw: dict, blocks: dict):
    """Build one version's layer chain for the plan's form.

    Returns (arrays, run): `arrays` is a flat tuple of per-layer jnp
    arrays (leading model axis when the plan is stacked — `lax.map`
    slices them per version) and `run(x_uint8, *arrays)` maps one
    version's uint8 batch to predicted classes. The packed and planes
    chains are packed END TO END: binarize emits uint32 words, every
    hidden boundary is a fused `step_pack`, and no int8 activation
    exists between layers.
    """
    from repro.kernels.binary_matvec import ops as bmv

    form = plan.form
    thr = plan.input_threshold
    bkw_kw = {**_blocks_kw(form, blocks), **kw}

    if form == "dense":
        arrays = tuple(jnp.asarray(l.weights, jnp.int32) for l in plan.layers)

        def matmul(a, w):
            if w.shape[-2] == 0:     # fully-pruned predecessor: constant 0
                return jnp.zeros((a.shape[0], w.shape[-1]), jnp.int32)
            return bmv.binary_matmul(a, w, **bkw_kw)

        def run(x_uint8, *ws):
            a = (x_uint8.astype(jnp.int32) > thr).astype(jnp.int8)
            for w in ws[:-1]:
                a = (matmul(a, w) > 0).astype(jnp.int8)
            return jnp.argmax(matmul(a, ws[-1]), axis=-1)

        return arrays, run

    words = [l.words for l in plan.layers]

    if form == "packed":
        arrays = tuple(jnp.asarray(l.weights, jnp.int32) for l in plan.layers)

        def matmul(a, w):
            if w.shape[-2] == 0:
                return jnp.zeros((a.shape[0], w.shape[-1]), jnp.int32)
            return bmv.binary_matmul_packed(a, w, **bkw_kw)

        def run(x_uint8, *ws):
            a = bmv.binarize_pack(x_uint8, threshold=thr, words=words[0])
            for w, nxt in zip(ws[:-1], words[1:]):
                a = bmv.step_pack(matmul(a, w), words=nxt)
            return jnp.argmax(matmul(a, ws[-1]), axis=-1)

        return arrays, run

    assert form == "planes", form
    arrays = []
    for layer in plan.layers:
        arrays.append(jnp.asarray(layer.pos_planes, jnp.uint32))
        arrays.append(jnp.asarray(layer.neg_planes, jnp.uint32))
    fan_outs = [l.fan_out for l in plan.layers]

    def plane_matmul(a, pos, neg, fan_out):
        if pos.shape[-2] == 0:       # zero words: fully-pruned fan_in
            return jnp.zeros((a.shape[0], fan_out), jnp.int32)
        return bmv.binary_matmul_planes(a, pos, neg, **bkw_kw)

    def run(x_uint8, *planes):
        a = bmv.binarize_pack(x_uint8, threshold=thr, words=words[0])
        for i in range(len(fan_outs) - 1):
            acc = plane_matmul(
                a, planes[2 * i], planes[2 * i + 1], fan_outs[i])
            a = bmv.step_pack(acc, words=words[i + 1])
        return jnp.argmax(
            plane_matmul(a, planes[-2], planes[-1], fan_outs[-1]), axis=-1)

    return tuple(arrays), run


def _finish_predictor(predict, jitted, *, plan_form: str, datapath: str,
                      blocks: dict, launches: int):
    """Stamp the predictor attributes every caller reads: the executed
    plan form, the datapath name (== form, or "fusednet" for the
    megakernel — surfaces in the `netgen.kernel` span and the launch
    counter), the chosen blocks, launches per call, and the underlying
    jitted fn (lowerable — `telemetry.jit_cost` roofline input)."""
    predict.plan_form = plan_form
    predict.datapath = datapath
    predict.blocks = dict(blocks)
    predict.launches_per_call = launches
    predict.jitted = jitted
    return predict


def _build_single(plan: ExecutionPlan, kw: dict, blocks: dict):
    from repro.netgen import telemetry

    arrays, run = _chain(plan, kw, blocks)
    jitted = jax.jit(lambda x: run(x, *arrays))
    form, depth = plan.form, plan.depth

    def predict(x_uint8):
        telemetry.kernel_launches(form).inc(depth)
        return jitted(x_uint8)

    return _finish_predictor(predict, jitted, plan_form=form, datapath=form,
                             blocks=blocks, launches=depth)


def _build_multi(plan: ExecutionPlan, kw: dict, blocks: dict):
    from repro.netgen import telemetry

    arrays, run = _chain(plan, kw, blocks)
    jitted = jax.jit(lambda block: jax.lax.map(
        lambda s: run(s[0], *s[1:]), (block, *arrays)))
    form = plan.form
    # lax.map sweeps the model axis sequentially: depth launches/model.
    launches = plan.depth * (plan.n_models or 1)

    def predict(x_uint8):                            # (M, B, n_in)
        telemetry.kernel_launches(form).inc(launches)
        return jitted(x_uint8)

    return _finish_predictor(predict, jitted, plan_form=form, datapath=form,
                             blocks=blocks, launches=launches)


def _build_fusednet(plan: ExecutionPlan, kw: dict, blocks: dict):
    """The whole-net megakernel predictor: one persistent
    `binary_forward_planes` launch per call — single (B, n_in) or
    stacked (M, B, n_in) — through `plan.megakernel_view()`. Raises
    ValueError when the plan has no megakernel view (callers that
    merely *prefer* the megakernel fall back to the per-layer chain)."""
    from repro.kernels.binary_matvec import ops as bmv
    from repro.netgen import telemetry

    view = plan.megakernel_view()
    arrays = tuple(jnp.asarray(a, jnp.uint32) for a in view.arrays)
    kkw = _megakernel_kw(view, kw, blocks)
    jitted = jax.jit(lambda x: bmv.binary_forward_planes(
        x, *arrays, threshold=view.input_threshold,
        n_classes=view.n_classes, **kkw))

    def predict(x_uint8):
        telemetry.kernel_launches("fusednet").inc()
        return jitted(x_uint8)

    return _finish_predictor(predict, jitted, plan_form="planes",
                             datapath="fusednet", blocks=blocks, launches=1)


def _megakernel_kw(view, kw: dict, blocks: dict) -> dict:
    """The megakernel's keywords: interpret, the bm/bkw blocks, and the
    view's thresholds only where it has some (none: today's kernel)."""
    kkw = dict(kw)
    if blocks.get("bm") is not None:
        kkw["bm"] = int(blocks["bm"])
    if blocks.get("bkw") is not None:
        kkw["bkw"] = int(blocks["bkw"])
    if view.thresholds is not None:
        kkw["thresholds"] = tuple(jnp.asarray(t, jnp.int32) for t in view.thresholds)
    return kkw


def _build_convnet(plan: ExecutionPlan, kw: dict, blocks: dict):
    """The conv-net predictor: one jitted program a call — the request
    rows laid out as image rows, one `binary_conv` launch per conv layer
    (a following 2x2 pool fused into it), then the dense tail on the
    megakernel with per-unit thresholds. A first layer that reads pixels
    takes `x - 128` in int8; its thresholds absorb `128 * sum(w)` per
    channel. It counts one `convnet` launch a call, so launches and slot
    rounds keep giving the rows a launch ran. Stacked conv plans are not
    served (`stack_plans` refuses them)."""
    from repro.kernels.binary_conv import binary_conv as bc
    from repro.kernels.binary_matvec import ops as bmv
    from repro.netgen import telemetry

    convs = []
    layers = plan.layers
    i = 0
    while i < len(layers) and layers[i].kind != "dense":
        layer = layers[i]
        if layer.kind != "conv":
            raise LayerKindError(f"layer {i}: a {layer.kind} layer must follow a conv layer")
        pool = i + 1 < len(layers) and layers[i + 1].kind == "pool"
        if pool and layers[i + 1].size != 2:
            raise LayerKindError(f"layer {i + 1}: only a 2x2 pool is fused")
        assert layer.in_shape is not None and layer.thresholds is not None
        kh, kwid, cin, cout = layer.weights.shape
        geo = bc.conv_geometry(*layer.in_shape[:2], cin, cout, kh, kwid, pool)
        t = layer.thresholds.astype(np.int64)
        if not convs and plan.input_mode == "pixels":
            t = t - 128 * layer.weights.astype(np.int64).sum(axis=(0, 1, 2))
        convs.append((geo, jnp.asarray(bc.banded_weights(geo, layer.weights)),
                      jnp.asarray(np.tile(t, geo.bo)[None], jnp.int32),
                      jnp.asarray(bc.pool_matrix(geo)) if pool else None,
                      bc.batch_tile(geo)))
        i += 2 if pool else 1
    view = plan.dense_tail().megakernel_view()
    arrays = tuple(jnp.asarray(a, jnp.uint32) for a in view.arrays)
    kkw = _megakernel_kw(view, kw, blocks)
    tile = max((c[4] for c in convs), default=1)

    def forward(x):
        rows = x
        if convs:
            b = x.shape[0]
            a = bc.image_rows(x, plan.input_shape, plan.input_mode,
                              plan.input_threshold, -(-b // tile) * tile)
            for geo, taps, thr, pool, bm in convs:
                a = bc.binary_conv(a, taps, thr, pool, geo=geo, bm=bm, **kw)
            rows = bc.flat_rows(a)[:b]
        return bmv.binary_forward_planes(
            rows, *arrays, threshold=view.input_threshold,
            n_classes=view.n_classes, **kkw)

    jitted = jax.jit(forward)

    def predict(x_uint8):
        telemetry.kernel_launches("convnet").inc()
        return jitted(x_uint8)

    return _finish_predictor(predict, jitted, plan_form="conv",
                             datapath="convnet", blocks=blocks, launches=1)


# ---------------------------------------------------------------------------
# Autotuning (repro.netgen.tune)
# ---------------------------------------------------------------------------

def _plan_signature(plan: ExecutionPlan) -> dict:
    """The JSON-stable shape identity tuning records are keyed on: layer
    geometry plus each layer's bit-plane count (the plane count sets the
    planes kernel's work, so nets of equal shape but different weight
    ranges tune separately). Computed from magnitudes directly — no
    plane decomposition is materialized for keying."""
    plan.require_dense("the kernel tuner and the explorer")
    return {
        "n_inputs": plan.n_inputs,
        "widths": [l.fan_out for l in plan.layers],
        "n_models": plan.n_models,
        "n_planes": [
            max(1, int(np.abs(l.weights).max(initial=0)).bit_length())
            for l in plan.layers],
    }


# ---------------------------------------------------------------------------
# Explored datapath records (repro.netgen.explore)
# ---------------------------------------------------------------------------

# The design-space explorer publishes its winning datapath (form +
# blocks) under this pseudo-target, keyed on the plan signature alone —
# NOT on a candidate grid — so any later compile of the same shape can
# resolve it without knowing how the search was configured.
_EXPLORED_TARGET = "pallas-explored"


def explored_key_fields(signature: dict, *, interpret, multi: bool) -> dict:
    """The JSON-stable identity an explored datapath record is keyed on.
    One home for the scheme: the explorer writes through it and
    `pallas[explored=true]` reads through it."""
    return {
        "target": _EXPLORED_TARGET,
        "device_kind": jax.devices()[0].device_kind,
        "interpret": interpret,
        "multi": bool(multi),
        "signature": signature,
    }


def publish_explored(plan: ExecutionPlan, tuner, best: dict, *,
                     interpret=None, measurements=(), extra=None):
    """Upsert the explored winner's datapath record for this plan shape
    (`best`: form + bm/bn/bkw). Called by `repro.netgen.explore` after a
    search; later `explored=true` compiles of the same signature resolve
    it with zero measurements."""
    from repro.netgen import tune

    tuner = tuner if tuner is not None else tune.default_tuner()
    fields = explored_key_fields(
        _plan_signature(plan), interpret=interpret, multi=plan.stacked)
    return tuner.publish(fields, best, measurements=measurements,
                         extra=extra)


def explored_record(plan: ExecutionPlan, tuner, *, interpret, multi: bool):
    """The resident explored-winner record for this plan shape, or None.
    A stacked lookup that misses falls back to the single-net signature
    (model axis erased): the explorer searches one net at a time, and a
    homogeneous stack executes the same per-model geometry the single
    net was measured on."""
    from repro.netgen import tune

    tuner = tuner if tuner is not None else tune.default_tuner()
    sig = _plan_signature(plan)
    rec = tuner.record_for(tune.tune_key(
        explored_key_fields(sig, interpret=interpret, multi=multi)))
    if rec is None and multi:
        rec = tuner.record_for(tune.tune_key(explored_key_fields(
            {**sig, "n_models": None}, interpret=interpret, multi=False)))
    return rec


def _form_compatible(pinned: str | None, recorded: str) -> bool:
    """May an explored record's form satisfy an explicitly pinned one?
    planes and fusednet are the same bit-plane datapath family (the
    megakernel runs the planes form), so they satisfy each other; any
    other disagreement means the record is ignored."""
    if pinned is None or pinned == recorded:
        return True
    return {pinned, recorded} == {"planes", "fusednet"}


def _tuned_params(plan: ExecutionPlan, kw: dict, blocks: dict,
                  forms, tuner, *, multi: bool):
    """Grid-search (form x block sizes) for this plan through the tuner
    (memory -> store -> measure); returns (winning params, the winner's
    already-built predictor or None on a warm record hit — a cold
    search traced the winner once already, don't trace it twice).
    Explicit block options are pinned, not searched."""
    from repro.netgen import tune

    tuner = tuner if tuner is not None else tune.default_tuner()
    pinned = {k: v for k, v in blocks.items() if v is not None}
    candidates = []
    seen = set()
    for form in forms:
        for grid in TUNE_BLOCKS:
            cand = {"form": form, **grid, **pinned}
            key = tuple(sorted(cand.items()))
            if key not in seen:
                seen.add(key)
                candidates.append(cand)

    batch = _TUNE_BATCH if not multi else max(32, _TUNE_BATCH // 4)
    shape = ((batch, plan.n_inputs) if not multi
             else (plan.n_models, batch, plan.n_inputs))
    x = np.zeros(shape, np.uint8)
    built: dict = {}

    def measure(cand: dict) -> float:
        ckey = tuple(sorted(cand.items()))
        fn = built.get(ckey)
        if fn is None:
            form = cand["form"]
            cblocks = {k: cand[k] for k in ("bm", "bn", "bkw")}
            if form == "fusednet":
                build = _build_fusednet
            else:
                build = _build_multi if multi else _build_single
            fn = build(_in_form(plan, form), kw, cblocks)
            built[ckey] = fn
        import time
        t0 = time.perf_counter()
        np.asarray(fn(x))
        return time.perf_counter() - t0

    key_fields = {
        "target": "pallas",
        "device_kind": jax.devices()[0].device_kind,
        "interpret": kw.get("interpret"),
        "multi": bool(multi),
        "batch": batch,
        "signature": _plan_signature(plan),
        "candidates": candidates,
    }
    # static tile legality: candidates whose blocks are non-positive or
    # clamp to a kernel another candidate already launches are rejected
    # before spending a measurement (repro.netgen.analysis)
    from repro.netgen.analysis import tile_legality
    best = tuner.get_or_tune(
        key_fields, candidates, measure,
        legal=tile_legality(plan, batch=batch, multi=multi))
    return best, built.get(tuple(sorted(best.items())))


def _resolve_datapath(plan: ExecutionPlan, kw: dict, *, packed, planes,
                      fusednet, tuned, bm, bn, bkw, tuner, multi: bool,
                      explored: bool = False):
    """Turn the declared target options into (form, blocks, prebuilt):
    explicit options pin their axis; `tuned=true` searches the rest
    (over every datapath, megakernel included, when no form is forced).
    `prebuilt` is the winning predictor when this process's search just
    built it (None otherwise — the caller builds).

    `explored=true` consults the design-space explorer's persisted
    winner for this plan signature FIRST (see `repro.netgen.explore`):
    a resident record supplies the form and any unpinned block sizes
    with zero measurements; without one (or when it contradicts an
    explicitly pinned form) the option is inert and resolution falls
    through to tuned/default — so the serving layer can request it
    unconditionally."""
    from repro.netgen import telemetry

    form = _resolve_form(packed, planes, fusednet)
    blocks = {"bm": bm, "bn": bn, "bkw": bkw}
    prebuilt = None
    if explored:
        rec = explored_record(plan, tuner, interpret=kw.get("interpret"),
                              multi=multi)
        hit = rec is not None and _form_compatible(form, rec.best.get("form"))
        telemetry.get_registry().counter(
            "netgen_explored_resolved_total",
            outcome="hit" if hit else "miss").inc()
        if hit:
            best = rec.best
            if form is None:
                form = best["form"]
            return form, {k: blocks[k] if blocks[k] is not None
                          else best.get(k) for k in blocks}, None
    if tuned:
        forms = (form,) if form is not None else _DATAPATHS
        best, prebuilt = _tuned_params(
            plan, kw, blocks, forms, tuner, multi=multi)
        form = best["form"]
        blocks = {k: best[k] for k in ("bm", "bn", "bkw")}
    elif form is None:
        form = "dense"
    return form, blocks, prebuilt


# ---------------------------------------------------------------------------
# Target entry points
# ---------------------------------------------------------------------------

def compile_pallas(circuit: Circuit, *, interpret: bool | None = None,
                   packed: bool = False, planes: bool = False,
                   fusednet: bool = False, tuned: bool = False,
                   explored: bool = False,
                   bm: int | None = None, bn: int | None = None,
                   bkw: int | None = None, _tuner=None):
    """Return a jitted fn chaining one kernel launch per plan layer —
    or, with `fusednet=true`, ONE whole-net megakernel launch.

    `interpret` pins Pallas interpret mode on or off; left unset, the
    kernels are interpreted only where JAX's backend is the CPU and
    compile through Mosaic on a TPU. `packed` selects the end-to-end
    bit-packed activation datapath, `planes` the fully bit-packed
    (bit-plane weight) datapath, `fusednet` the single-launch
    planes-form megakernel — all bit-exact with dense. `bm`/`bn`/`bkw` pin kernel
    block sizes; `tuned` grid-searches unpinned block sizes (and the
    datapath, when none is forced) through the persistent autotuner.
    The returned fn carries `.plan_form`, `.datapath` and `.blocks`
    describing what the search (or the flags) chose. `explored=true`
    resolves the design-space explorer's persisted winner for this plan
    shape when one exists (see `repro.netgen.explore`); without a
    record it is inert.

    A ConvNet plan builds the conv predictor (`_build_convnet`); the
    tuner, the explorer and the packed datapath refuse it.
    """
    kw = {} if interpret is None else {"interpret": interpret}
    plan = lower_circuit(circuit)
    if plan.conv:
        if tuned or explored or packed:
            opt = "tuned" if tuned else "explored" if explored else "packed"
            plan.require_dense(f"pallas[{opt}=true]")
        return _build_convnet(plan, kw, {"bm": bm, "bn": bn, "bkw": bkw})
    form, blocks, prebuilt = _resolve_datapath(
        plan, kw, packed=packed, planes=planes, fusednet=fusednet,
        tuned=tuned, bm=bm, bn=bn, bkw=bkw, tuner=_tuner, multi=False,
        explored=explored)
    if prebuilt is not None:
        return prebuilt
    if form == "fusednet":
        return _build_fusednet(plan.planes(), kw, blocks)
    return _build_single(_in_form(plan, form), kw, blocks)


def compile_pallas_multi(plan: ExecutionPlan, *,
                         interpret: bool | None = None,
                         packed: bool = False, planes: bool = False,
                         fusednet: bool = False, tuned: bool = False,
                         explored: bool = False,
                         bm: int | None = None, bn: int | None = None,
                         bkw: int | None = None, _tuner=None):
    """Multi-net dispatch through the binary_matvec kernels.

    `plan` is a *stacked* ExecutionPlan (`repro.netgen.plan.stack_plans`,
    hidden widths pre-padded): per-layer (M, fan_in, fan_out) weights.

    The bit-plane datapath prefers the whole-net megakernel: both
    `fusednet=true` and `planes=true` build ONE persistent
    `binary_forward_planes` launch over grid (M, B/bm) — model axis
    outermost, so each version's resident weights serve a full batch
    sweep before the next version's are brought in. `planes=true`
    falls back to the per-layer chain when the megakernel build fails
    (`fusednet=true` is strict). Everything else sweeps the model axis
    with `lax.map` — a scan whose body is the per-layer kernel chain
    (depth x M launches per dispatch vs the megakernel's 1). All
    declared options behave as in `compile_pallas`; tuning records for
    stacked plans are keyed on the stacked shape (model count
    included), separate from the single-net records.
    """
    if not plan.stacked:
        raise ValueError("compile_pallas_multi needs a stacked ExecutionPlan")
    kw = {} if interpret is None else {"interpret": interpret}
    form, blocks, prebuilt = _resolve_datapath(
        plan, kw, packed=packed, planes=planes, fusednet=fusednet,
        tuned=tuned, bm=bm, bn=bn, bkw=bkw, tuner=_tuner, multi=True,
        explored=explored)
    if prebuilt is not None:
        return prebuilt
    if form == "fusednet":
        return _build_fusednet(plan.planes(), kw, blocks)
    if form == "planes":
        try:
            return _build_fusednet(plan.planes(), kw, blocks)
        except ValueError:
            pass                    # no megakernel view: per-layer chain
    return _build_multi(_in_form(plan, form), kw, blocks)


_FUSED_TUNE_BM = (64, 128, 256)


def compile_fused(circuit: Circuit, *, interpret: bool | None = None,
                  tuned: bool = False, bm: int | None = None, _tuner=None):
    """Whole-net single Pallas launch; 2-layer plans only. `bm` pins the
    batch tile; `fused[tuned=true]` searches it per plan shape through
    the persistent autotuner."""
    from repro.kernels.fused_mlp import ops as fused

    kw = {} if interpret is None else {"interpret": interpret}
    plan = lower_circuit(circuit)
    plan.require_dense("the fused target")
    if plan.depth != 2:
        raise IrregularCircuitError(
            f"fused backend supports exactly 2 layers, got {plan.depth}")
    w1 = jnp.asarray(plan.layers[0].weights, jnp.int32)
    w2 = jnp.asarray(plan.layers[1].weights, jnp.int32)
    thr = plan.input_threshold

    if tuned and bm is None:
        from repro.netgen import tune

        tuner = _tuner if _tuner is not None else tune.default_tuner()
        x = np.zeros((_TUNE_BATCH, plan.n_inputs), np.uint8)
        candidates = [{"bm": b} for b in _FUSED_TUNE_BM]

        def measure(cand):
            import time
            t0 = time.perf_counter()
            np.asarray(fused.fused_mlp_predict(
                x, w1, w2, threshold=thr, bm=cand["bm"], **kw))
            return time.perf_counter() - t0

        best = tuner.get_or_tune({
            "target": "fused",
            "device_kind": jax.devices()[0].device_kind,
            "interpret": kw.get("interpret"),
            "batch": _TUNE_BATCH,
            "signature": _plan_signature(plan),
            "candidates": candidates,
        }, candidates, measure)
        bm = best["bm"]

    bm_kw = {} if bm is None else {"bm": int(bm)}

    @jax.jit
    def _jitted(x_uint8):
        return fused.fused_mlp_predict(
            x_uint8, w1, w2, threshold=thr, **bm_kw, **kw)

    from repro.netgen import telemetry

    def predict(x_uint8):
        telemetry.kernel_launches("fused").inc()
        return _jitted(x_uint8)

    return _finish_predictor(predict, _jitted, plan_form="dense",
                             datapath="fused", blocks=bm_kw, launches=1)
