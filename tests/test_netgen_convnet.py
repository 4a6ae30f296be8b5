"""Binarized conv nets (`repro.core.convnet.ConvNet`) through netgen.

A tiny net of CNV's shape (12x12x3 -> conv 8 -> conv 8 -> pool -> conv 16
-> dense 16 -> 10, bipolar +-1 weights, per-channel thresholds, 8-bit
pixels into the first layer) is folded into the {0, 1} datapath and
served through `Session.compile`, `NetServer` and `ServingEngine` on the
CPU (Pallas interpret mode). Every served class must equal the plain
float32 jnp reference of the bipolar net and `graph.evaluate`, class for
class; planted faults must change answers on the same seeded inputs.
"""
import numpy as np
import pytest

from repro import netgen
from repro.core.convnet import (
    ConvLayer, ConvNet, DenseLayer, PoolLayer, bipolar_logits,
)
from repro.core.quantize import QuantizedNet, weights_digest
from repro.netgen import frontend, graph
from repro.netgen.graph import LayerKindError
from repro.netgen.plan import lower_circuit, stack_plans

SHAPE = (12, 12, 3)
TARGET = "pallas[fusednet=true]"


def _pm(rng, shape):
    return np.where(rng.standard_normal(shape) >= 0, 1, -1)


def tiny_bipolar(seed: int = 0) -> list:
    """The tiny net's bipolar layers: thresholds near each layer's middle
    so that every layer's units fire on some images and not others."""
    rng = np.random.default_rng(seed)
    w1 = _pm(rng, (3, 3, 3, 8))
    return [ConvLayer(w1, 128 * w1.sum(axis=(0, 1, 2)) + rng.integers(-200, 200, 8)),
            ConvLayer(_pm(rng, (3, 3, 8, 8)), rng.integers(-6, 7, 8)),
            PoolLayer(),
            ConvLayer(_pm(rng, (3, 3, 8, 16)), rng.integers(-6, 7, 16)),
            DenseLayer(_pm(rng, (64, 16)), rng.integers(-4, 5, 16)),
            DenseLayer(_pm(rng, (16, 10)), np.zeros(10, int))]


def images(n: int = 48, seed: int = 1, n_in: int = 432) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, (n, n_in), dtype=np.uint8)


@pytest.fixture(scope="module")
def tiny():
    layers = tiny_bipolar()
    net = ConvNet.from_bipolar(SHAPE, layers)
    x = images()
    ref = np.asarray(bipolar_logits(SHAPE, layers, x)).argmax(axis=1)
    return net, layers, x, ref


def _served(net, x, path: str) -> np.ndarray:
    session = netgen.Session()
    if path == "session":
        return np.asarray(session.compile(net, target=TARGET)(x))
    if path == "server":
        server = netgen.NetServer(session=session, target=TARGET, slot_capacity=16)
        server.register("cnv", net)
        return np.asarray(server.predict_many({"cnv": x})["cnv"])
    engine = session.engine(target=TARGET, slot_capacity=16)
    engine.register("cnv", net)
    try:
        futs = [engine.submit("cnv", row) for row in x]
        return np.array([f.result(timeout=120) for f in futs])
    finally:
        engine.shutdown()


def test_reference_is_not_constant(tiny):
    _, _, _, ref = tiny
    assert np.bincount(ref, minlength=10).max() <= len(ref) // 2


def test_fold_matches_the_bipolar_reference(tiny):
    net, _, x, ref = tiny
    np.testing.assert_array_equal(graph.evaluate(frontend.lower(net), x), ref)


def test_fold_in_compare_mode_matches_the_bipolar_reference():
    layers = tiny_bipolar(3)
    layers[0] = ConvLayer(layers[0].weights, np.random.default_rng(4).integers(-8, 9, 8))
    net = ConvNet.from_bipolar(SHAPE, layers, input_mode="compare", input_threshold=100)
    x = images(seed=5)
    ref = np.asarray(bipolar_logits(SHAPE, layers, x, input_mode="compare",
                                    input_threshold=100)).argmax(axis=1)
    np.testing.assert_array_equal(graph.evaluate(frontend.lower(net), x), ref)
    np.testing.assert_array_equal(np.asarray(netgen.Session().compile(net, target=TARGET)(x)),
                                  ref)


def test_fold_of_an_odd_fan_in_doubles_the_last_layer():
    rng = np.random.default_rng(7)
    w_last = _pm(rng, (400, 3))
    w_last[0] = 0                            # every column now sums to an odd number
    layers = [ConvLayer(_pm(rng, (3, 3, 3, 4)), np.zeros(4, int)),
              DenseLayer(w_last, np.zeros(3, int))]
    net = ConvNet.from_bipolar(SHAPE, layers)
    assert np.array_equal(net.layers[-1].weights, 2 * w_last)
    x = images(seed=8)
    ref = np.asarray(bipolar_logits(SHAPE, layers, x)).argmax(axis=1)
    np.testing.assert_array_equal(graph.evaluate(frontend.lower(net), x), ref)


@pytest.mark.parametrize("path", ["session", "server", "engine"])
def test_served_classes_match_the_references(tiny, path):
    net, _, x, ref = tiny
    np.testing.assert_array_equal(_served(net, x, path), ref)


def test_request_rows_of_3072_bytes_through_server_and_engine():
    rng = np.random.default_rng(11)
    w1 = _pm(rng, (3, 3, 3, 4))
    layers = [ConvLayer(w1, 128 * w1.sum(axis=(0, 1, 2)) + rng.integers(-100, 100, 4)),
              ConvLayer(_pm(rng, (3, 3, 4, 4)), rng.integers(-4, 5, 4)),
              PoolLayer(),
              ConvLayer(_pm(rng, (3, 3, 4, 8)), rng.integers(-4, 5, 8)),
              PoolLayer(),
              DenseLayer(_pm(rng, (6 * 6 * 8, 10)), np.zeros(10, int))]
    net = ConvNet.from_bipolar((32, 32, 3), layers)
    assert net.n_inputs == 3072
    x = images(20, seed=12, n_in=3072)
    ref = np.asarray(bipolar_logits((32, 32, 3), layers, x)).argmax(axis=1)
    for path in ("server", "engine"):
        np.testing.assert_array_equal(_served(net, x, path), ref)


def test_digest_covers_thresholds_kinds_and_input_mode(tiny):
    net = tiny[0]
    base = weights_digest(net, None)
    assert base == net.digest() == ConvNet(net.input_shape, net.layers).digest()
    bumped = list(net.layers)
    t = bumped[1].thresholds.copy()
    t[3] += 1
    bumped[1] = ConvLayer(bumped[1].weights, t)
    assert ConvNet(net.input_shape, bumped).digest() != base
    assert ConvNet(net.input_shape, net.layers, input_mode="compare").digest() != base
    # the same numbers as a 1x1 conv over a 1x1 map and as a dense layer
    w = np.random.default_rng(2).integers(-1, 2, (5, 4))
    last = DenseLayer(np.ones((4, 2), int), np.zeros(2, int))
    as_conv = ConvNet((1, 1, 5), (ConvLayer(w[None, None], np.zeros(4, int)), last))
    as_dense = ConvNet((1, 1, 5), (DenseLayer(w, np.zeros(4, int)), last),
                       input_mode="compare")
    as_conv_compare = ConvNet((1, 1, 5), (ConvLayer(w[None, None], np.zeros(4, int)), last),
                              input_mode="compare")
    assert as_conv_compare.digest() != as_dense.digest() != as_conv.digest()


def _cnv_bipolar(seed: int = 0) -> list:
    rng = np.random.default_rng(seed)
    layers, c = [], 3
    for spec in (64, 64, "pool", 128, 128, "pool", 256, 256):
        if spec == "pool":
            layers.append(PoolLayer())
            continue
        layers.append(ConvLayer(_pm(rng, (3, 3, c, spec)), rng.integers(-8, 9, spec)))
        c = spec
    for k, n in ((256, 512), (512, 512), (512, 10)):
        layers.append(DenseLayer(_pm(rng, (k, n)), rng.integers(-8, 9, n)))
    return layers


def test_published_cnv_has_few_ir_objects_and_round_trips_the_store(tmp_path):
    net = ConvNet.from_bipolar((32, 32, 3), _cnv_bipolar())
    circuit = frontend.lower(net)
    assert len(circuit.nodes) < 1000
    assert sum(int(np.prod(n.weights.shape)) for n in circuit.nodes
               if hasattr(n, "weights")) == 1_542_848
    back = graph.circuit_from_arrays(graph.circuit_to_arrays(circuit))
    assert [type(n) for n in back.nodes] == [type(n) for n in circuit.nodes]
    for a, b in zip(circuit.nodes, back.nodes):
        for f in ("weights", "thresholds"):
            if hasattr(a, f):
                np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    art = netgen.Session(store=tmp_path).compile(net, target=TARGET)
    warm = netgen.Session(store=tmp_path)
    again = warm.compile(net, target=TARGET)
    assert warm.stats().compiles == 0 and again.source == "store"
    assert again.key == art.key and again.plan_form == "conv"
    assert again.plan().describe() == art.plan().describe()
    from repro.netgen.analysis import lint_store
    assert lint_store(tmp_path) == {}
    x = images(2, seed=3, n_in=3072)
    ev = graph.evaluate(again.circuit, x)
    np.testing.assert_array_equal(ev, graph.evaluate(circuit, x))


def test_threshold_free_plan_hands_the_megakernel_no_threshold_operand(monkeypatch, tiny):
    from repro.kernels.binary_matvec import ops as bmv

    seen = []
    real = bmv.binary_forward_planes

    def spy(x, *planes, **kw):
        seen.append(kw)
        return real(x, *planes, **kw)

    monkeypatch.setattr(bmv, "binary_forward_planes", spy)
    rng = np.random.default_rng(0)
    qnet = QuantizedNet(weights=[rng.integers(-3, 4, (64, 40)), rng.integers(-3, 4, (40, 10))])
    xq = images(8, n_in=64)
    art = netgen.Session().compile(qnet, target=TARGET)
    art(xq)
    assert art.plan().planes().megakernel_view().thresholds is None
    assert "thresholds" not in seen[-1]
    net, _, x, _ = tiny
    netgen.Session().compile(net, target=TARGET)(x)
    assert len(seen[-1]["thresholds"]) == 2


def _mismatches(net, x, ref) -> int:
    return int((np.asarray(netgen.Session().compile(net, target=TARGET)(x)) != ref).sum())


def test_planted_threshold_off_by_one_changes_answers(tiny):
    net, _, x, ref = tiny
    layers = list(net.layers)
    layer = layers[3]
    diffs = []
    for ch in range(layer.thresholds.shape[0]):
        t = layer.thresholds.copy()
        t[ch] += 1
        layers[3] = ConvLayer(layer.weights, t)
        diffs.append(int((graph.evaluate(frontend.lower(ConvNet(SHAPE, layers)), x)
                          != ref).sum()))
    ch = int(np.argmax(diffs))
    t = layer.thresholds.copy()
    t[ch] += 1
    layers[3] = ConvLayer(layer.weights, t)
    assert _mismatches(ConvNet(SHAPE, layers), x, ref) > 0


def test_planted_dropped_pool_changes_answers(tiny, monkeypatch):
    from repro.kernels.binary_conv import binary_conv as bc

    real = bc.pool_matrix

    def odd_columns_dropped(geo):
        s = real(geo).copy()
        for x in range(1, geo.group * geo.bo, 2):
            s[x * geo.cout:(x + 1) * geo.cout] = 0
        return s

    net, _, x, ref = tiny
    monkeypatch.setattr(bc, "pool_matrix", odd_columns_dropped)
    assert _mismatches(net, x, ref) > 0


def test_planted_binarized_first_layer_changes_answers(tiny):
    net, _, x, ref = tiny
    layers = list(net.layers)
    layers[0] = ConvLayer(layers[0].weights, np.zeros_like(layers[0].thresholds))
    binarized = ConvNet(SHAPE, layers, input_mode="compare", input_threshold=128)
    assert _mismatches(binarized, x, ref) > 0


def test_layer_kinds_are_refused_by_name(tiny):
    net = tiny[0]
    circuit = frontend.lower(net)
    plan = lower_circuit(circuit)
    session = netgen.Session()
    refusals = {
        "verilog": lambda: session.compile(net, target="verilog"),
        "cost": lambda: session.compile(net, target="cost"),
        "jnp": lambda: session.compile(net, target="jnp"),
        "fused": lambda: session.compile(net, target="fused"),
        "tune": lambda: session.compile(net, target="pallas[tuned=true]"),
        "explore": lambda: session.explore(net, budget=1),
        "stack_plans": lambda: stack_plans([plan, plan]),
        "packed form": lambda: plan.pack(),
        "megakernel view": lambda: plan.megakernel_view(),
        "dense extraction": lambda: graph.as_layered_weights(circuit),
    }
    for what, call in refusals.items():
        with pytest.raises(LayerKindError, match="conv") as e:
            call()
        assert "conv" in str(e.value), what


def test_stacked_dispatch_of_conv_versions_falls_back_with_a_report(tiny):
    net, _, x, ref = tiny
    server = netgen.NetServer(session=netgen.Session(), target=TARGET, slot_capacity=16)
    server.register("a", net)
    server.register("b", net)
    out = server.predict_many({"a": x[:10], "b": x[10:20]})
    np.testing.assert_array_equal(out["a"], ref[:10])
    np.testing.assert_array_equal(out["b"], ref[10:20])
    report = server.stack_report(("a", "b"))
    assert report is not None and report.reason == "stack.layer-kind"


def test_analysis_certifies_the_conv_kernel_products(tiny):
    from repro.netgen import analysis

    circuit = frontend.lower(tiny[0])
    ranges, diags = analysis.analyze(circuit, collect=True)
    assert diags == []
    summary = analysis.proof_summary(circuit, ranges)
    assert summary["int32_safe"] and summary["layer_nodes"] == 5
    assert len(ranges.output_envelope(circuit)) == 10
    analysis.check_observed(circuit, tiny[2], ranges=ranges)
    big = ConvNet(SHAPE, (ConvLayer(np.full((3, 3, 3, 2), 200), np.zeros(2, int)),
                          DenseLayer(np.ones((200, 2), int), np.zeros(2, int))))
    bad = analysis.check_ranges(frontend.lower(big), collect=True)
    assert [d.check for d in bad] == ["range.mxu-int8"]


def test_dense_only_net_in_compare_mode_runs_on_the_megakernel():
    rng = np.random.default_rng(21)
    layers = [DenseLayer(_pm(rng, (432, 32)), rng.integers(-3, 4, 32)),
              DenseLayer(_pm(rng, (32, 10)), np.zeros(10, int))]
    net = ConvNet.from_bipolar(SHAPE, layers, input_mode="compare", input_threshold=90)
    x = images(seed=22)
    ref = np.asarray(bipolar_logits(SHAPE, layers, x, input_mode="compare",
                                    input_threshold=90)).argmax(axis=1)
    np.testing.assert_array_equal(np.asarray(netgen.Session().compile(net, target=TARGET)(x)),
                                  ref)
    pixels = ConvNet(SHAPE, net.layers, input_mode="pixels")
    with pytest.raises(LayerKindError, match="conv first layer"):
        netgen.Session().compile(pixels, target=TARGET)


def test_every_pass_of_the_hardware_pipeline_leaves_layer_nodes_alone(tiny):
    net, _, x, ref = tiny
    art = netgen.Session().compile(net, target=TARGET, pipeline="hw")
    assert [type(n) for n in art.circuit.nodes] == \
        [type(n) for n in frontend.lower(net).nodes]
    assert all(s.after.nodes == s.before.nodes for s in art.pass_stats)
    np.testing.assert_array_equal(np.asarray(art(x)), ref)
