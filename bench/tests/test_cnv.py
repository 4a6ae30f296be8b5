"""FINN's CNV in the benchmark: the work count of its net module, its
plain reference and weight scheme, the readers of `conv_roofline` and
`conv_us_per_launch`, the control, and a CNV-shaped cell run through the
harness on the CPU."""
import hashlib
import json

import numpy as np
import pytest

from bench import generator, run, trace, work
from bench.run import RunData, load_file
from bench.tests.conftest import ROOT, TINY_OFFLINE, run_tiny

CELL = "cnv-offline"
V5E = work.peaks("TPU v5 lite")
METRICS = ROOT / "bench" / "metrics"
LAUNCHES = ("counter", "netgen_kernel_launches_total", (("form", "convnet"),))
ROUNDS = ("counter", "netgen_slot_rounds_total", (("server", "server-1"),))


@pytest.fixture(scope="module")
def cnv():
    spec = run.load_cell(ROOT, CELL)
    config = spec["config"]
    (_, weights), = run.make_versions(ROOT, config, 1)
    ref = load_file(ROOT / "bench" / f"{config['reference']}.py", "t_reference_cnv")
    return spec, config, weights, ref


def test_work_of_the_published_net(cnv):
    spec, config, weights, _ = cnv
    net = spec["net"]
    assert net.row_length(config) == 3072
    assert net.macs(config) == 59_461_376
    assert net.macs(config, ("conv",)) == 59_063_040
    assert net.weight_count(config) == 1_542_848
    assert sum(np.asarray(l["weights"]).size for l in weights if "weights" in l) == 1_542_848
    assert net.ops(config, 8192) == 2 * 59_461_376 * 8192
    least, bound = net.min_seconds(config, 8192, 1, V5E)
    assert bound == "int8" and least == pytest.approx(2 * 59_461_376 * 8192 / 393e12)
    conv, bound = net.conv_min_seconds(config, 8192, 1, V5E)
    assert bound == "int8" and conv == pytest.approx(2 * 59_063_040 * 8192 / 393e12)


def test_reference_refuses_sums_float32_would_round(cnv):
    _, config, weights, ref = cnv
    x = generator.rng(5, generator.STREAM_INPUTS).integers(0, 256, (8, 3072), dtype=np.uint8)
    got = ref.logits(weights, config, x)
    assert np.array_equal(got, np.round(got))
    big = [dict(weights[0], weights=np.full((3, 3, 3, 64), 30_000))] + weights[1:]
    with pytest.raises(ValueError, match="2\\*\\*24"):
        ref.logits(big, config, x)


def test_hidden_layers_fire_between_a_fifth_and_four_fifths(cnv):
    _, config, weights, ref = cnv
    x = generator.rng(9, generator.STREAM_INPUTS).integers(0, 256, (64, 3072), dtype=np.uint8)
    a = ref.image(config, x)
    for layer in weights[:-1]:
        if layer["kind"] == "pool":
            a = ref.maxpool(a, layer["size"])
            continue
        a = np.where(ref.layer_sums(layer, a) >= layer["thresholds"], 1.0, -1.0)
        assert 0.2 <= (a > 0).mean() <= 0.8


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_no_class_takes_half_of_a_seeded_block(cnv, seed):
    _, config, weights, ref = cnv
    x = generator.rng(seed, generator.STREAM_INPUTS).integers(0, 256, (256, 3072),
                                                              dtype=np.uint8)
    counts = np.bincount(ref.logits(weights, config, x).argmax(axis=1), minlength=10)
    assert counts.max() <= 128


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_control_is_not_correct_on_a_small_block(cnv, seed):
    from bench.control import INPUT_SHIFT

    _, config, weights, ref = cnv
    x = generator.rng(seed, generator.STREAM_INPUTS).integers(0, 256, (64, 3072),
                                                              dtype=np.uint8)
    low = ref.logits(weights, config, x, input_shift=INPUT_SHIFT)
    assert ref.widest_gap(ref.logits(weights, config, x), low.argmax(axis=1)) >= 1


def _trace(ops) -> trace.Trace:
    return trace.Trace(window=(0, 2_000_000_000), ops=ops, busy=[], busy_s=0.0, host=[])


def _run(cnv, ops, launches, rounds) -> RunData:
    spec, config, _, _ = cnv
    before = {LAUNCHES: 3, ROUNDS: 96}
    after = {LAUNCHES: 3 + launches, ROUNDS: 96 + rounds}
    return RunData("offline", 2.0, _trace(ops), before, after, [], None, 8192 * launches,
                   config, spec["net"], 1, V5E)


@pytest.mark.parametrize("metric", ["conv_roofline", "conv_us_per_launch"])
def test_conv_metrics_read_a_synthetic_trace(cnv, metric):
    # two launches of 32 rounds, six conv ops each (1 ms a layer), and a
    # tail op that is not the conv kernel
    ops = [(f"netgen_conv.{k}", 10_000_000 * i + 1_000_000 * k, 1_000_000)
           for i in range(2) for k in range(1, 7)]
    ops += [("binary_forward_planes.1", 9_000_000, 500_000), ("netgen_convolution", 0, 7)]
    reader = load_file(METRICS / f"{metric}.py", "t_" + metric)
    got = reader.read(_run(cnv, ops, launches=2, rounds=64))
    if metric == "conv_us_per_launch":
        assert got == pytest.approx(6_000.0)
    else:
        least = 2 * 59_063_040 * 8192 / 393e12
        assert got == pytest.approx(100 * least * 2 / 12e-3)


@pytest.mark.parametrize("metric", ["conv_roofline", "conv_us_per_launch"])
def test_conv_metrics_are_silent_without_the_kernel(cnv, metric):
    reader = load_file(METRICS / f"{metric}.py", "t_" + metric)
    assert reader.read(_run(cnv, [("binary_forward_planes.1", 0, 5)], 2, 64)) is None
    assert reader.read(_run(cnv, [("netgen_conv.1", 0, 5)], 0, 0)) is None


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of the CNV configuration's weights and thresholds, and of a 10 s
# window's inputs at one seed, as first drawn: a later change to the
# scheme or the generator shows here
CNV_DIGESTS = {
    "weights":
        "c069b80d15023fc3c2554f2666a7f7623e59f4f0fe904b2363b33264f1e27631",
    "inputs": "608a1be7a19741d82c587a986a3a782e279c7a799c0d1c3cdecf066370deca3a",
}


def test_cnv_draws_are_pinned(cnv):
    spec, config, weights, _ = cnv
    got = {"weights": _sha([np.asarray(l[k]) for l in weights
                            for k in ("weights", "thresholds") if k in l])}
    inputs = generator.make_inputs(spec["traffic"], 3072, ["cnv"], 10.0, 7)
    got["inputs"] = _sha([b["cnv"] for b in inputs["blocks"]])
    assert got == CNV_DIGESTS


TINY_CNV = {
    "name": "tiny-cnv", "net": "finn_cnv", "input_length": 432, "input_shape": [12, 12, 3],
    "input_mode": "pixels",
    "layers": [{"kind": "conv", "kernel": 3, "channels": 8},
               {"kind": "conv", "kernel": 3, "channels": 8},
               {"kind": "pool", "size": 2},
               {"kind": "conv", "kernel": 3, "channels": 16},
               {"kind": "dense", "units": 16}, {"kind": "dense", "units": 10}],
    "weight_seed": 20170224,
    "versions": [{"name": "cnv", "weights": {"scheme": "cnv_sign_bn", "fire_low": 0.2,
                                             "fire_high": 0.8, "calibration_images": 32}}],
    "reference": "reference_cnv", "target": "pallas[fusednet=true]", "slot_capacity": 64}


@pytest.mark.parametrize("trace_on", [0, 1])
def test_cnv_shaped_cell_runs_correct_through_the_harness(tiny_root, trace_on):
    (tiny_root / "bench/configs/tiny-cnv.json").write_text(json.dumps(TINY_CNV))
    layout = json.loads((tiny_root / "BENCHMARK.json").read_text())
    layout["configs"].append({"name": "tiny-cnv", "source": "https://arxiv.org/abs/1612.07119",
                              "file": "bench/configs/tiny-cnv.json", "reduced": ["layers"],
                              "why": "test size"})
    layout["workloads"].append({"name": "tiny-cnv-offline", "config": "tiny-cnv",
                                "traffic": TINY_OFFLINE, "chips": 1, "why": "test size"})
    for m in layout["end_to_end"] + layout["per_layer"]:
        if "cnv-offline" in m.get("workloads", ()):
            m["workloads"].append("tiny-cnv-offline")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(layout))
    res = run_tiny(tiny_root, "tiny-cnv-offline", trace=trace_on)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    if not trace_on:
        assert set(res["metrics"]) == {"setup_s", "preds_per_s"}
    else:
        # no TPU ops in a CPU trace: the conv readers stay silent
        assert "conv_roofline" not in res["metrics"]
