"""Static analysis tests (ISSUE 8): the `repro.netgen.analysis`
invariant layer — structural verifier, interval/range dataflow, plan
certification, tile legality, stack diagnosis, and the ArtifactStore
linter — plus its wiring through `PipelineSpec.run(verify=True)`, the
Session compile driver, the tuner, and the Verilog backend.

Acceptance spine: a deliberately-corrupting pass is caught at the pass
boundary with a diagnostic naming the pass and the node, across three
invariant classes (structural, range/overflow, plan legality); the
tuner skips statically illegal candidates without changing the winner;
artifacts persist and reload their proof summary.
"""
import dataclasses
import json

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import quantize
from repro import netgen
from repro.netgen import analysis
from repro.netgen.analysis import (
    FUSEDNET_VMEM_BYTES, INT32_MAX, Diagnostic, RangeAnalysis,
    VerificationError, analyze_ranges, check_ranges, diagnose_stack,
    effective_tiles, fusednet_vmem_bytes, lint_store, proof_summary,
    summary_row, tile_legality, verify_circuit, verify_plan,
)
from repro.netgen.graph import (
    InputCompare, Term, WeightedSum, node_widths, signed_width,
    value_bounds,
)
from repro.netgen.pipeline import PipelineSpec
from repro.netgen.plan import lower_circuit
from repro.netgen.tune import KernelTuner

from _netgen_helpers import images, random_net

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    from _hypothesis_stub import given, settings, strategies as st


def _random_net(seed: int, sizes=(12, 9, 4), lo=-5, hi=5):
    return random_net(seed, sizes, lo=lo, hi=hi)


def _images(seed: int, b: int, n_in: int) -> np.ndarray:
    return images(seed, b, n_in, salt=88)


def _ref(net, x):
    return np.asarray(quantize.predict_quantized(net)(jnp.asarray(x)))


def _optimized(seed: int, sizes=(12, 9, 4)):
    c = netgen.lower(_random_net(seed, sizes))
    c, _ = PipelineSpec.parse("zeros,prune").run(c, verify=True)
    return c


# ---------------------------------------------------------------------------
# Deliberately-corrupting passes (module-level: the spec round-trips
# them via their dotted name, so the diagnostic's stage names the pass)
# ---------------------------------------------------------------------------

def drop_used_bit(circuit):
    """Corruption, structural class: deletes an InputCompare that a
    WeightedSum still reads — the survivor dangles."""
    used = {t.src for n in circuit.nodes
            if isinstance(n, WeightedSum) for t in n.terms}
    keep, dropped = [], False
    for n in circuit.nodes:
        if not dropped and isinstance(n, InputCompare) and n.id in used:
            dropped = True
            continue
        keep.append(n)
    assert dropped
    return dataclasses.replace(circuit, nodes=tuple(keep))


def triple_final_weights(circuit):
    """Corruption, range class: scales the output-layer weights 3x —
    structurally fine, but the class score envelope widens, which an
    exact rewrite must never do."""
    out = circuit.node(circuit.output)
    finals = set(out.srcs)
    nodes = []
    for n in circuit.nodes:
        if isinstance(n, WeightedSum) and n.id in finals:
            n = dataclasses.replace(n, terms=tuple(
                Term(t.weight * 3, t.src) for t in n.terms))
        nodes.append(n)
    return dataclasses.replace(circuit, nodes=tuple(nodes))


def test_pipeline_catches_structural_corruption():
    c = netgen.lower(_random_net(0))
    spec = PipelineSpec.coerce([netgen.delete_zero_terms, drop_used_bit])
    with pytest.raises(VerificationError) as ei:
        spec.run(c, verify=True)
    diags = ei.value.diagnostics
    assert any(d.check == "structure.topo-order" for d in diags)
    d = next(d for d in diags if d.check == "structure.topo-order")
    assert d.node is not None                  # names the orphaned reader
    assert "drop_used_bit" in d.stage          # names the offending pass
    assert "drop_used_bit" in str(ei.value)


def test_pipeline_catches_envelope_widening():
    c = netgen.lower(_random_net(1))
    spec = PipelineSpec.coerce([triple_final_weights])
    with pytest.raises(VerificationError) as ei:
        spec.run(c, verify=True)
    diags = ei.value.diagnostics
    assert all(d.check == "range.envelope" for d in diags)
    assert "triple_final_weights" in diags[0].stage
    assert "widened" in diags[0].message


def test_pipeline_verify_off_lets_corruption_through():
    # prod posture: the same broken pipeline completes (the Session
    # driver's pre-backend analysis is the backstop there)
    c = netgen.lower(_random_net(1))
    spec = PipelineSpec.coerce([triple_final_weights])
    out, _ = spec.run(c, verify=False)
    assert isinstance(out, type(c))


def test_verify_default_follows_env(monkeypatch):
    c = netgen.lower(_random_net(1))
    spec = PipelineSpec.coerce([triple_final_weights])
    monkeypatch.setenv("NETGEN_VERIFY", "1")
    with pytest.raises(VerificationError):
        spec.run(c)
    monkeypatch.setenv("NETGEN_VERIFY", "0")
    spec.run(c)


# ---------------------------------------------------------------------------
# Structural verifier + postconditions (unit level)
# ---------------------------------------------------------------------------

def test_verifier_clean_on_every_default_stage():
    c = netgen.lower(_random_net(2))
    assert verify_circuit(c, stage="lowered") == []
    for spec in ("zeros", "zeros,prune", "zeros,prune,addends", "hw"):
        out, _ = PipelineSpec.coerce(spec).run(
            netgen.lower(_random_net(2)), verify=True)
        assert verify_circuit(out) == []


def test_verifier_flags_duplicate_id_and_bad_output():
    c = netgen.lower(_random_net(3))
    dup = dataclasses.replace(c, nodes=c.nodes + (c.nodes[0],))
    checks = {d.check for d in verify_circuit(dup, collect=True)}
    assert "structure.duplicate-id" in checks
    noout = dataclasses.replace(c, output=c.nodes[0].id)
    checks = {d.check for d in verify_circuit(noout, collect=True)}
    assert "structure.output" in checks


def test_postconditions_catch_surviving_work():
    c = netgen.lower(_random_net(4))   # unoptimized: has zero weights
    assert any(t.weight == 0 for n in c.nodes
               if isinstance(n, WeightedSum) for t in n.terms)
    diags = verify_circuit(c, after_pass="zeros", collect=True)
    assert any(d.check == "postcondition.zeros" for d in diags)
    diags = verify_circuit(c, after_pass="addend_rewrite", collect=True)
    assert any(d.check == "postcondition.addends" for d in diags)
    # the real passes discharge their own postconditions
    z = netgen.delete_zero_terms(c)
    assert verify_circuit(z, after_pass="zeros") == []
    a = netgen.addend_rewrite(z)
    assert verify_circuit(a, after_pass="addends") == []


# ---------------------------------------------------------------------------
# Range dataflow: parity, proofs, and width edge cases
# ---------------------------------------------------------------------------

def test_ranges_reproduce_value_bounds_and_node_widths():
    for seed in (5, 6):
        c = _optimized(seed)
        ra = analyze_ranges(c)
        assert ra.bounds() == value_bounds(c)
        assert ra.widths() == node_widths(c)
        assert check_ranges(c, ra) == []


def test_zero_weight_layer_edges():
    w1 = np.zeros((4, 3), dtype=np.int32)
    w2 = np.array([[2, -1], [0, 3], [-2, 2]], dtype=np.int32)
    net = quantize.QuantizedNet(w1=w1, w2=w2)
    c = netgen.lower(net)
    ra = analyze_ranges(c)
    hidden = [n for n in c.nodes
              if isinstance(n, WeightedSum) and n.layer == 1]
    for n in hidden:
        r = ra[n.id]
        assert (r.lo, r.hi, r.bound) == (0, 0, 0)
        assert r.width == signed_width(0) >= 1
    # the full pipeline stays verifiable and exact on the degenerate net
    out, _ = PipelineSpec.parse("zeros,prune").run(c, verify=True)
    x = _images(0, 6, 4)
    analysis.check_observed(out, x)
    np.testing.assert_array_equal(netgen.evaluate(out, x), _ref(net, x))


def test_all_negative_weight_layer_has_zero_hi():
    w1 = -np.abs(np.arange(1, 13).reshape(4, 3)).astype(np.int32)
    w2 = np.array([[1, -2], [-3, 1], [2, 2]], dtype=np.int32)
    net = quantize.QuantizedNet(w1=w1, w2=w2)
    c = netgen.lower(net)
    ra = analyze_ranges(c)
    for n in c.nodes:
        if isinstance(n, WeightedSum) and n.layer == 1:
            r = ra[n.id]
            assert r.hi == 0 and r.lo == -r.bound < 0
            # interval is strictly tighter than the symmetric bound
            assert r.max_abs == r.bound
    analysis.check_observed(c, _images(1, 6, 4), ranges=ra)


def test_fan_in_one_signed_width_boundary():
    # a single +w term reaches hi == 2^(width-1) - 1 exactly: the
    # tightest value signed_width's symmetric sizing admits
    w1 = np.array([[3, -3]], dtype=np.int32)
    w2 = np.array([[1, -1], [-1, 1]], dtype=np.int32)
    c = netgen.lower(quantize.QuantizedNet(w1=w1, w2=w2))
    ra = analyze_ranges(c)
    pos = [ra[n.id] for n in c.nodes
           if isinstance(n, WeightedSum) and n.layer == 1
           and n.terms[0].weight > 0]
    assert pos and pos[0].hi == (1 << (pos[0].width - 1)) - 1
    assert check_ranges(c, ra) == []


def test_check_ranges_flags_tampered_width_and_int32():
    c = _optimized(7)
    ra = analyze_ranges(c)
    sid = next(n.id for n in c.nodes
               if isinstance(n, WeightedSum) and ra[n.id].hi > 0)
    r = ra[sid]
    tampered = RangeAnalysis({**ra.ranges, sid: dataclasses.replace(
        r, width=1)})
    checks = {d.check for d in check_ranges(c, tampered, collect=True)}
    assert "range.width-overflow" in checks
    huge = RangeAnalysis({**ra.ranges, sid: dataclasses.replace(
        r, bound=INT32_MAX + 1)})
    checks = {d.check for d in check_ranges(c, huge, collect=True)}
    assert "range.int32" in checks


def test_check_observed_brackets_and_detects_escape():
    c = _optimized(8)
    x = _images(2, 16, 12)
    analysis.check_observed(c, x)           # interpreter stays inside
    ra = analyze_ranges(c)
    sid = next(n.id for n in c.nodes
               if isinstance(n, WeightedSum) and ra[n.id].hi > 0)
    shrunk = RangeAnalysis({**ra.ranges, sid: dataclasses.replace(
        ra[sid], lo=0, hi=0)})
    with pytest.raises(VerificationError, match="range.observed"):
        analysis.check_observed(c, x, ranges=shrunk)


def test_proof_summary_certifies_the_circuit():
    c = _optimized(9)
    s = proof_summary(c)
    assert s["format"] == "netgen-analysis-v1" and s["verified"]
    assert s["sum_nodes"] == sum(
        isinstance(n, WeightedSum) for n in c.nodes)
    assert s["max_width"] == max(
        r.width for r in analyze_ranges(c).ranges.values())
    assert s["int32_safe"] is True and s["slack_bits"] >= 0
    assert "proved" in summary_row(s)


# ---------------------------------------------------------------------------
# Property: random nets x pipelines verify, intervals bracket execution
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       spec=st.sampled_from(
           ["zeros", "zeros,prune", "zeros,prune,addends", "hw"]))
def test_property_pipelines_verify_and_bracket(seed, spec):
    net = _random_net(seed, sizes=(10, 8, 4))
    c = netgen.lower(net)
    stages = []
    out, _ = PipelineSpec.coerce(spec).run(
        c, verify=True, observe=lambda name, cc: stages.append(cc))
    x = _images(seed, 8, 10)
    for cc in stages:
        analysis.check_observed(cc, x)
    np.testing.assert_array_equal(netgen.evaluate(out, x), _ref(net, x))


# ---------------------------------------------------------------------------
# Plan certification
# ---------------------------------------------------------------------------

def test_verify_plan_clean_on_all_forms():
    c = _optimized(10, sizes=(20, 16, 4))
    for form in ("dense", "packed", "planes"):
        plan = lower_circuit(c, form=form)
        assert plan.verify() == []


def test_verify_plan_catches_pad_and_plane_corruption():
    c = _optimized(11, sizes=(20, 16, 4))
    packed = lower_circuit(c, form="packed")
    layer = packed.layers[0]
    w = layer.weights.copy()
    assert w.shape[0] > 20                 # 20 inputs pad to 32 lanes
    w[-1, 0] = 1                           # poison a zero-pad row
    bad = dataclasses.replace(
        packed, layers=(dataclasses.replace(layer, weights=w),)
        + packed.layers[1:])
    checks = {d.check for d in verify_plan(bad, collect=True)}
    assert "plan.pad-exact" in checks

    planes = lower_circuit(c, form="planes")
    layer = planes.layers[0]
    pos = layer.pos_planes.copy()
    pos[0, 0, 0] ^= np.uint32(1)           # flip one decomposed bit
    bad = dataclasses.replace(
        planes, layers=(dataclasses.replace(layer, pos_planes=pos),)
        + planes.layers[1:])
    checks = {d.check for d in verify_plan(bad, collect=True)}
    assert checks & {"plan.planes-lossless", "plan.planes-disjoint"}


def test_verify_plan_catches_broken_chain():
    c = _optimized(12, sizes=(20, 16, 4))
    plan = lower_circuit(c, form="dense")
    bad = dataclasses.replace(plan, layers=plan.layers[1:])
    checks = {d.check for d in verify_plan(bad, collect=True)}
    assert "plan.chain" in checks
    with pytest.raises(VerificationError, match="plan.chain"):
        verify_plan(bad)


# ---------------------------------------------------------------------------
# Tile legality through the tuner
# ---------------------------------------------------------------------------

def _grid():
    return [{"bm": bm, "bn": bn, "bkw": bkw}
            for bm in (8, 64) for bn in (16, 64) for bkw in (1, 8)]


def test_tuner_legality_skips_duplicates_same_winner():
    c = _optimized(13, sizes=(20, 16, 4))
    plan = lower_circuit(c, form="packed")
    batch = 4
    cands = _grid()

    def make_measure(calls):
        def measure(cand):
            eff = effective_tiles(plan, "packed", cand, batch)
            calls.append(eff)
            # deterministic: cost is a pure function of what actually runs
            return 1e-3 + 1e-4 * sum(sum(t) for t in eff)
        return measure

    full_calls, filt_calls = [], []
    fields = {"target": "t", "device_kind": "cpu", "candidates": cands}
    full = KernelTuner().get_or_tune(
        fields, cands, make_measure(full_calls), reps=1)
    tuner = KernelTuner()
    filtered = tuner.get_or_tune(
        fields, cands, make_measure(filt_calls), reps=1,
        legal=tile_legality(plan, batch=batch))
    # every candidate clamps: batch 4 -> bm 8, 20 inputs -> 1 lane word
    assert len(filt_calls) < len(full_calls)
    assert filtered == full                      # same winner, fewer runs
    assert tuner.stats.rejected > 0
    assert tuner.stats.measurements == len(filt_calls) // 2


def test_tuner_all_candidates_illegal_raises():
    c = _optimized(13, sizes=(20, 16, 4))
    plan = lower_circuit(c, form="packed")
    cands = [{"bm": 0, "bn": 8, "bkw": 1}, {"bm": -8, "bn": 8, "bkw": 1}]
    with pytest.raises(ValueError, match="statically illegal"):
        KernelTuner().get_or_tune(
            {"target": "t", "device_kind": "cpu", "candidates": cands},
            cands, lambda c: 0.0,
            legal=tile_legality(plan, batch=4))


def test_tile_legality_keeps_partial_and_distinct_candidates():
    c = _optimized(14, sizes=(40, 16, 4))
    plan = lower_circuit(c, form="dense")
    legal = tile_legality(plan, batch=64)
    assert legal({"bm": 8, "bn": 16, "bkw": 2}) is None
    assert legal({"bm": 16, "bn": 16, "bkw": 2}) is None  # distinct tiles
    assert "duplicate" in legal({"bm": 8, "bn": 16, "bkw": 2})
    assert legal({"form": "dense"}) is None               # partial: keep


@pytest.mark.parametrize("form,blocks,refused", [
    # 784 inputs pack into KW=25 words: a word tile is all 25 or a
    # multiple of 128, never 8 or 16
    ("packed", {"bm": 128, "bn": 128, "bkw": 8}, "K tile 8"),
    ("planes", {"bm": 128, "bn": 128, "bkw": 16}, "K tile 16"),
    ("planes", {"bm": 128, "bn": 128, "bkw": 25}, None),
    ("packed", {"bm": 128, "bn": 128, "bkw": 128}, None),
    # dense tiles K in bits: 8 words = 256 bits is a lane multiple
    ("dense", {"bm": 128, "bn": 128, "bkw": 8}, None),
    ("dense", {"bm": 128, "bn": 128, "bkw": 2}, "K tile 64"),
    # a fan-out tile of 64 splits the 500-unit hidden layer
    ("planes", {"bm": 128, "bn": 64, "bkw": 128}, "fan-out tile 64"),
    # the megakernel keeps whole arrays resident: no lane tile to refuse
    ("fusednet", {"bm": 32, "bn": 64, "bkw": 8}, None),
])
def test_tile_legality_tpu_lane_rule_at_paper_width(form, blocks, refused):
    """The TPU compiler takes a block's last dim only when it is the
    whole padded dim or a multiple of 128 lanes; the legality check
    refuses the rest before any measurement."""
    plan = lower_circuit(_optimized(19, sizes=(784, 500, 10)))
    reason = tile_legality(plan, batch=256)({"form": form, **blocks})
    if refused is None:
        assert reason is None
    else:
        assert reason is not None and refused in reason
        assert "multiple of 128" in reason


@pytest.mark.parametrize("multi", [False, True])
def test_tune_grid_keeps_a_legal_candidate_per_datapath(multi):
    from repro.netgen.backends.pallas import _DATAPATHS, TUNE_BLOCKS

    plan = lower_circuit(_optimized(19, sizes=(784, 500, 10)))
    batch = 64 if multi else 256
    for form in _DATAPATHS:
        legal = tile_legality(plan, batch=batch, multi=multi)
        kept = [b for b in TUNE_BLOCKS if legal({"form": form, **b}) is None]
        assert kept, form


def test_fusednet_vmem_matches_view_estimate():
    """The analytic per-candidate estimate (no plane decomposition
    materialized) must agree with what the megakernel view actually
    keeps resident — otherwise the tuner's VMEM gate drifts from the
    kernel it is gating."""
    for seed, sizes in ((17, (45, 21, 7)), (18, (64, 33, 10))):
        plan = lower_circuit(_optimized(seed, sizes=sizes))
        view = plan.planes().megakernel_view()
        for bm, bkw in ((8, 1), (32, 4), (256, 16)):
            assert fusednet_vmem_bytes(plan, bm=bm, bkw=bkw) \
                == view.vmem_bytes(bm=bm, bkw=bkw), (sizes, bm, bkw)


def test_fusednet_candidate_over_vmem_budget_rejected():
    """A batch tile that would not fit the megakernel's whole residency
    in VMEM is rejected BEFORE measurement, with the budget named."""
    plan = lower_circuit(_optimized(19, sizes=(784, 500, 10)))
    legal = tile_legality(plan, batch=4096)
    big = {"form": "fusednet", "bm": 2048, "bn": 8, "bkw": 16}
    reason = legal(big)
    assert reason is not None and "VMEM budget" in reason
    assert fusednet_vmem_bytes(plan, bm=2048, bkw=16, batch=4096) \
        > FUSEDNET_VMEM_BYTES
    small = {"form": "fusednet", "bm": 32, "bn": 8, "bkw": 8}
    assert legal(small) is None


def test_fusednet_bn_only_candidates_dedupe():
    """The megakernel has no fan-out tiling: candidates differing only
    in `bn` clamp to the identical kernel, so the second is rejected as
    a duplicate measurement."""
    plan = lower_circuit(_optimized(19, sizes=(40, 16, 4)))
    a = {"form": "fusednet", "bm": 8, "bn": 8, "bkw": 1}
    b = {"form": "fusednet", "bm": 8, "bn": 64, "bkw": 1}
    eff = effective_tiles(plan, "fusednet", a, 4)
    assert eff == effective_tiles(plan, "fusednet", b, 4)
    assert all(len(t) == 2 for t in eff)    # (bm, bkw) pairs, no bn
    legal = tile_legality(plan, batch=4)
    assert legal(a) is None
    assert "duplicate" in legal(b)


# ---------------------------------------------------------------------------
# Stack diagnosis
# ---------------------------------------------------------------------------

def test_diagnose_stack_axes():
    twins = [netgen.lower(_random_net(s)) for s in (20, 21)]
    rep = diagnose_stack(twins)
    assert rep.compatible and rep.reason == "none"
    assert "stack-compatible" in rep.describe()

    odd = diagnose_stack(twins + [netgen.lower(_random_net(22, (12, 9, 5)))])
    assert not odd.compatible and odd.reason == "stack.classes"
    assert "class count" in odd.describe()

    shared, _ = PipelineSpec.coerce("hw").run(netgen.lower(_random_net(23)))
    rep = diagnose_stack([shared])
    assert not rep.compatible and rep.reason == "stack.irregular"

    packed = lower_circuit(_optimized(24), form="packed")
    rep = diagnose_stack([packed])
    assert not rep.compatible and rep.reason == "stack.form"

    assert diagnose_stack([]).reason == "stack.empty"


def test_netserver_stack_report_on_incompatible_versions():
    server = netgen.NetServer(slot_capacity=8)
    server.register("a", _random_net(25))
    server.register("b", _random_net(26, (12, 9, 5)))   # class mismatch
    x = _images(3, 4, 12)
    out = server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["fallback"] >= 1
    reports = server.stack_report()
    assert reports, "incompatible stack must leave a structured report"
    rep = next(iter(reports.values()))
    assert not rep.compatible and rep.reason == "stack.classes"
    # per-version answers stay exact through the fallback
    np.testing.assert_array_equal(out["a"], _ref(_random_net(25), x))


# ---------------------------------------------------------------------------
# Session wiring: proof summary persists, widths come from the analysis
# ---------------------------------------------------------------------------

def test_artifact_persists_and_reloads_proof_summary(tmp_path):
    store_dir = tmp_path / "s"
    net = _random_net(30)
    art = netgen.Session(store=netgen.ArtifactStore(store_dir)).compile(
        net, target="jnp")
    assert art.analysis is not None
    assert art.analysis["format"] == "netgen-analysis-v1"
    assert art.analysis["verified"] and art.analysis["int32_safe"]
    assert art.timings["analysis_s"] >= 0
    assert summary_row(art.analysis) in art.report()
    with open(store_dir / art.key / "meta.json") as f:
        assert json.load(f)["analysis"] == art.analysis
    # a cold session reloads the identical certificate from disk
    cold = netgen.Session(store=netgen.ArtifactStore(store_dir)).compile(
        net, target="jnp")
    assert cold.analysis == art.analysis


def test_verilog_widths_come_from_shared_analysis():
    from repro.netgen.backends.verilog import emit_verilog
    c = _optimized(31)
    precomputed = emit_verilog(c, _analysis=analyze_ranges(c))
    assert precomputed == emit_verilog(c)
    # accumulator declarations are sized from NodeRange.width
    widths = analyze_ranges(c).widths()
    some_sum = next(n for n in c.nodes if isinstance(n, WeightedSum))
    assert f"[{widths[some_sum.id] - 1}:0]" in precomputed


def test_strict_compile_raises_on_corrupt_pipeline(tmp_path, monkeypatch):
    monkeypatch.setenv("NETGEN_VERIFY", "0")   # pass boundary check off...
    session = netgen.Session(store=netgen.ArtifactStore(tmp_path / "s"))
    session.compile(_random_net(32), target="jnp",
                    pipeline=[triple_final_weights])   # ...prod proceeds
    monkeypatch.setenv("NETGEN_VERIFY", "1")
    strict = netgen.Session(store=netgen.ArtifactStore(tmp_path / "s2"))
    with pytest.raises(VerificationError):
        # strict: the driver's own pre-backend analysis still catches a
        # value-changing pipeline even though per-pass checks are the
        # pipeline's (the envelope widening shows as a range violation
        # only across passes; structural corruption is caught here)
        strict.compile(
            _random_net(33), target="jnp", pipeline=[drop_used_bit])
    # the raised compile is a counted failure, keeping the cache-tier
    # telemetry identity (misses == compiles + store_hits + failures)
    # intact for the CI metrics gate
    st = strict.stats()
    assert st.failures == 1
    assert st.misses == st.compiles + st.store_hits + st.failures


# ---------------------------------------------------------------------------
# Store linting + CLI
# ---------------------------------------------------------------------------

def _build_store(tmp_path, n=2):
    store_dir = tmp_path / "store"
    session = netgen.Session(store=netgen.ArtifactStore(store_dir))
    for s in range(n):
        session.compile(_random_net(40 + s), target="jnp")
    return store_dir


def test_lint_store_clean_then_corrupted(tmp_path):
    store_dir = _build_store(tmp_path)
    assert lint_store(store_dir) == {}

    entries = sorted(p for p in store_dir.iterdir() if p.is_dir())
    # corrupt a stored cost: recompute disagrees
    meta_path = entries[0] / "meta.json"
    meta = json.loads(meta_path.read_text())
    meta["cost"]["total"] = meta["cost"]["total"] + 7
    meta_path.write_text(json.dumps(meta))
    # stale content address: rename an entry to a key it cannot hash to
    stale = entries[1].with_name("0" * len(entries[1].name))
    entries[1].rename(stale)

    failures = lint_store(store_dir)
    assert set(failures) == {entries[0].name, stale.name}
    assert any(d.check == "store.cost" for d in failures[entries[0].name])
    assert any(d.check == "store.key" for d in failures[stale.name])


def test_lint_store_unreadable_artifacts(tmp_path):
    store_dir = _build_store(tmp_path, n=1)
    entry = next(p for p in store_dir.iterdir() if p.is_dir())
    (entry / "circuit.npz").write_bytes(b"not a zipfile")
    failures = lint_store(store_dir)
    assert any(d.check == "store.circuit" for d in failures[entry.name])
    (entry / "meta.json").write_text("{broken")
    failures = lint_store(store_dir)
    assert any(d.check == "store.meta" for d in failures[entry.name])


def test_lint_cli_exit_codes(tmp_path, capsys):
    store_dir = _build_store(tmp_path, n=1)
    assert analysis.main([str(store_dir)]) == 0
    out = capsys.readouterr().out
    assert "1 ok, 0 failed" in out

    entry = next(p for p in store_dir.iterdir() if p.is_dir())
    meta = json.loads((entry / "meta.json").read_text())
    meta["cost"]["total"] += 1
    (entry / "meta.json").write_text(json.dumps(meta))
    assert analysis.main([str(store_dir)]) == 1
    out = capsys.readouterr().out
    assert "FAIL" in out and "store.cost" in out

    assert analysis.main([str(tmp_path / "nowhere")]) == 2


# ---------------------------------------------------------------------------
# Diagnostics surface
# ---------------------------------------------------------------------------

def test_diagnostic_rows_and_error_rendering():
    d = Diagnostic(check="structure.topo-order", message="m", node=3,
                   stage="zeros")
    assert "structure.topo-order" in d.row()
    assert "zeros" in d.row() and "3" in d.row()
    err = VerificationError([d, d])
    assert "2 invariant violation" in str(err)
    assert err.diagnostics == (d, d)


def test_public_exports():
    for name in ("Diagnostic", "RangeAnalysis", "StackReport",
                 "VerificationError", "analyze_ranges", "diagnose_stack",
                 "verify_circuit", "verify_plan"):
        assert hasattr(netgen, name)
