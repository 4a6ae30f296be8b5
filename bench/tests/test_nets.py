"""A configuration brings the code particular to its net as files of its
own: its net module (`bench/nets/<net>.py`: request length, what the server
registers, the work of a call), its weight scheme and its plain reference.
The configurations already in the benchmark keep drawing the same weights,
making the same inputs and getting the same reference logits."""
import hashlib
import json

import numpy as np
import pytest

from bench import generator, run
from bench.tests.conftest import ROOT, TINY_OFFLINE, TINY_ONLINE, run_tiny
from bench.tests.test_harness import _digests

# A net that is not a dense chain at `widths`: a 3,072-byte request row (a
# 32x32 RGB image) through a `layers` list, ternary weights, its own reference.
LAYERS_NET = '''
from bench import work


def widths(config):
    return [int(config["input_length"])] + [int(layer["units"]) for layer in config["layers"]]


def row_length(config):
    return int(config["input_length"])


def build(config, weights):
    from repro.core.quantize import QuantizedNet

    return QuantizedNet(weights=weights, input_threshold=int(config["input_threshold"]))


def ops(config, rows, versions=1):
    return work.ops(widths(config), rows, versions)


def bytes_moved(config, rows, versions=1):
    return work.bytes_moved(widths(config), rows, versions)


def min_seconds(config, rows, versions, peak):
    return work.least_seconds(ops(config, rows, versions), bytes_moved(config, rows, versions),
                              peak)
'''
TERNARY_SCHEME = '''
import numpy as np


def make(rng, config, params):
    sizes = [int(config["input_length"])] + [int(layer["units"]) for layer in config["layers"]]
    return [rng.integers(-1, 2, (k, n)).astype(np.int32) for k, n in zip(sizes[:-1], sizes[1:])]
'''
LAYERS_REFERENCE = '''
import numpy as np


def logits(weights, config, x, input_shift=0):
    a = (x.astype(np.int64) >> input_shift) > (int(config["input_threshold"]) >> input_shift)
    for w in weights[:-1]:
        a = a.astype(np.int64) @ np.asarray(w, np.int64) > 0
    return a.astype(np.int64) @ np.asarray(weights[-1], np.int64)


def widest_gap(ref, served):
    if served.size == 0:
        return 0.0
    served = np.asarray(served).astype(np.int64)
    if ((served < 0) | (served >= ref.shape[1])).any():
        return float("inf")
    got = np.take_along_axis(ref, served[:, None], axis=1)[:, 0]
    return float((ref.max(axis=1) - got).max())
'''
OPS_PER_PRED = '''
def read(run):
    if not run.completed:
        return None
    return run.net.ops(run.config, run.completed) / run.completed
'''
RGB_CELLS = {"rgb-offline": TINY_OFFLINE, "rgb-online": TINY_ONLINE}


def _add_layers_net(root):
    """Add a configuration whose net is `layers_net`, with its cells, as new
    files and BENCHMARK.json entries only."""
    files = {"bench/nets/layers_net.py": LAYERS_NET,
             "bench/weights/ternary.py": TERNARY_SCHEME,
             "bench/layers_reference.py": LAYERS_REFERENCE,
             "bench/metrics/ops_per_pred.py": OPS_PER_PRED,
             "bench/configs/tiny-rgb.json": json.dumps({
                 "name": "tiny-rgb", "net": "layers_net", "input_length": 3072,
                 "input_threshold": 100, "layers": [{"units": 24}, {"units": 10}],
                 "weight_seed": 20170222,
                 "versions": [{"name": "rgb", "weights": {"scheme": "ternary"}}],
                 "reference": "layers_reference", "target": "jnp", "slot_capacity": 64})}
    for rel, text in files.items():
        assert not (root / rel).exists()
        (root / rel).write_text(text)
    layout = json.loads((root / "BENCHMARK.json").read_text())
    layout["configs"].append({"name": "tiny-rgb", "source": "https://arxiv.org/abs/1612.07119",
                              "file": "bench/configs/tiny-rgb.json", "reduced": [],
                              "why": "test size"})
    for cell, traffic in RGB_CELLS.items():
        layout["workloads"].append({"name": cell, "config": "tiny-rgb", "traffic": traffic,
                                    "chips": 1, "why": "test size"})
    for m in layout["end_to_end"]:
        if m["name"] in ("p50_ms", "preds_per_s"):
            m["workloads"].append("rgb-online" if m["name"] == "p50_ms" else "rgb-offline")
    layout["per_layer"].append({"name": "ops_per_pred", "unit": "ops", "better": "higher",
                                "source": "program_counter", "layer": "model step",
                                "moves": "preds_per_s", "workloads": ["rgb-offline"]})
    (root / "BENCHMARK.json").write_text(json.dumps(layout))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(RGB_CELLS))
def test_net_of_another_shape_goes_in_as_files_alone(tiny_root, cell, trace):
    before = _digests(tiny_root)
    _add_layers_net(tiny_root)
    spec = run.load_cell(tiny_root, cell)
    assert "widths" not in spec["config"]
    assert spec["net"].row_length(spec["config"]) == 3072
    res = run_tiny(tiny_root, cell, trace=trace)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    e2e = "preds_per_s" if cell == "rgb-offline" else "p50_ms"
    if not trace:
        assert set(res["metrics"]) == {"setup_s", e2e}
    elif cell == "rgb-offline":
        # a reader prices the net's work through RunData's config and net module
        assert res["metrics"]["ops_per_pred"]["value"] == 2 * (3072 * 24 + 24 * 10)
    after = _digests(tiny_root)
    assert {p: d for p, d in after.items() if p in before} == before


def test_new_net_reference_and_control_see_the_configuration(tiny_root):
    from bench.control import control_gap

    _add_layers_net(tiny_root)
    got = control_gap(tiny_root, "rgb-offline", 3, 1.0)
    assert got["rows"] == 400 and not got["correct"]


@pytest.mark.parametrize("net", ["no_such_net", "../run", "nets/dense_chain", ""])
def test_net_naming_no_module_is_a_layout_error(tiny_root, net):
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    (tiny_root / "bench/configs/tiny.json").write_text(json.dumps(dict(cfg, net=net)))
    with pytest.raises(run.LayoutError):
        run.load_cell(tiny_root, TINY_OFFLINE)


# sha256 of what the benchmark drew for its existing configurations before
# configurations named their nets: each version's weights, the inputs of a
# 10 s window at two seeds, and the reference's integer logits on them.
SEEDS = (7, 2 ** 31 + 11)
DIGESTS = {
    "finn-lfc-784-1024x3-10/1/weights":
        "9f9daa1ddfd409ae66a21fc35450d2f70acd8831647cdf5742c5e5cd2daa51df",
    "mnist-fpga-784-500-10/1/weights":
        "151c00ce3ddfba83b8562caa99b58609c7ddbf8aaceb7e8ba4fac175bce9dee6",
    "mnist-fpga-784-500-10/2/weights":
        "e6b1b3439a0682b055c55b590473ed9e2887fd27422ccf876f1138d374803090",
    "lfc-offline/7/inputs": "e4182d9970a4f50121705a610659ea677b9f11ef4a4e627e28ff44dacab59fa9",
    "lfc-offline/7/logits": "37d274979cb3b1154da3e304acec3fa5599aa3a19e8fe49bd350bbd0934ed0d4",
    "lfc-offline/2147483659/inputs":
        "4b696b19934d6d927b035be0c986c56641d0146ffcd49324eacab4733ecc6215",
    "lfc-offline/2147483659/logits":
        "5ad51a2edec6e79d8aba43d381bda92fd5074c00a4ba83c6781b78ca8b82dbe3",
    "paper-offline-stacked/7/inputs":
        "e8f36ea9b610f944e556e6abb603a9d70abcad151d95b61422db651bffed09b5",
    "paper-offline-stacked/7/logits":
        "d53de34b645ba60e48aec3b4b61d3fcb47372832eaa23c94494cf6a79fdda1b6",
    "paper-offline-stacked/2147483659/inputs":
        "b31638726a95057b28f98d0a54b5d1956187936e891ca718acca86744664f163",
    "paper-offline-stacked/2147483659/logits":
        "7de03d6a2e44436f586bbd06770866d4bc83daebe744f214a5d51b9587de5379",
    "paper-online-poisson/7/inputs":
        "fcb7e1d16b9387ac53ca6b227fa091dab7db0bffde9f744f629064bd46bfa313",
    "paper-online-poisson/7/logits":
        "b9e5529dac87fef713363cf14a24093c3b95fd4808cc68fa28fa6401d67a72ad",
    "paper-online-poisson/2147483659/inputs":
        "3e129c696dba6501bbf6b8cbe87a9736d46243e534938cdfda6e315dd9932b0b",
    "paper-online-poisson/2147483659/logits":
        "6f0478e37cc687eed960ca59e20ce61135a2ad22d17c9ada31c766da164202f1",
    "lfc-online-poisson/7/inputs":
        "d5b9a911cb8025a633ff0d46017b83a2c1ff85abb930a764e924ab17f9b52eaa",
    "lfc-online-poisson/7/logits":
        "d5a20c91c02ab33a5c8b83af424f863f9f90d68d40709f965f847c90a7d68b0a",
    "lfc-online-poisson/2147483659/inputs":
        "e9f42b6fc4a1a8e27e98f26d058d7b5504fc1525375d56a2a1eca4444c183ce7",
    "lfc-online-poisson/2147483659/logits":
        "8591592d9ddf83565e033feb7d85619f070f7a6fd40d7cc13017fd30aa8caa8a",
}


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", ["lfc-offline", "paper-offline-stacked",
                                  "paper-online-poisson", "lfc-online-poisson"])
def test_existing_configurations_draw_the_same_weights_inputs_and_logits(cell):
    spec = run.load_cell(ROOT, cell)
    config, traffic, net = spec["config"], spec["traffic"], spec["net"]
    assert net.__name__ == "bench_net_" + run.DEFAULT_NET
    versions = run.make_versions(ROOT, config, int(traffic["versions"]))
    names = [v for v, _ in versions]
    got = {f"{config['name']}/{len(versions)}/weights":
           _sha([w for _, ws in versions for w in ws])}
    ref = run.load_file(ROOT / "bench" / f"{config['reference']}.py", "t_reference")

    def logits(ws, x):
        return ref.logits(ws, config, x).astype(np.int64)

    for seed in SEEDS:
        inputs = generator.make_inputs(traffic, net.row_length(config), names, 10.0, seed)
        if traffic["mode"] == "offline":
            got[f"{cell}/{seed}/inputs"] = _sha([b[v] for b in inputs["blocks"] for v in names])
            got[f"{cell}/{seed}/logits"] = _sha([logits(ws, b[v]) for b in inputs["blocks"]
                                                 for v, ws in versions])
        else:
            got[f"{cell}/{seed}/inputs"] = _sha([inputs[k] for k in ("pool", "idx", "due", "ver")])
            got[f"{cell}/{seed}/logits"] = _sha([logits(ws, inputs["pool"]) for _, ws in versions])
    assert got == {k: DIGESTS[k] for k in got}
