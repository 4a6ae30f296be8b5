"""Helpers for the benchmark's own tests (run them with an explicit path:
`python -m pytest bench/tests`). They run on the CPU at a small size."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_ONLINE, TINY_OFFLINE, TINY_STACKED = "tiny-online", "tiny-offline", "tiny-stacked"


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    """A copy of the benchmark's layout with a small configuration (784-64-10
    over the `jnp` target) and three small cells: online Poisson, offline one
    version, offline two stacked versions. The program is linked in."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(ROOT / "src", root / "src")
    layout = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/mnist-fpga-784-500-10.json").read_text())
    cfg.update(name="tiny", widths=[784, 64, 10], target="jnp", slot_capacity=64)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    layout["configs"].append({"name": "tiny", "source": "https://arxiv.org/abs/2012.08071",
                              "file": "bench/configs/tiny.json", "reduced": ["widths"],
                              "why": "test size"})
    traffic = {
        TINY_ONLINE: {"mode": "online", "versions": 1, "rate_per_s": 400, "image_pool": 256,
                      "max_batch_delay_s": 0.002, "max_queue_depth": 4096},
        TINY_OFFLINE: {"mode": "offline", "versions": 1, "rows_per_version": 200, "blocks": 2},
        TINY_STACKED: {"mode": "offline", "versions": 2, "rows_per_version": 200, "blocks": 2},
    }
    for name, mix in traffic.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
        layout["workloads"].append({"name": name, "config": "tiny", "traffic": name,
                                    "chips": 1, "why": "test size"})
    for m in layout["end_to_end"] + layout["per_layer"]:
        wl = m.get("workloads")
        if wl is not None and "paper-online-poisson" in wl:
            wl.append(TINY_ONLINE)
        if wl is not None and "lfc-offline" in wl:
            wl += [TINY_OFFLINE, TINY_STACKED]
    (root / "BENCHMARK.json").write_text(json.dumps(layout, indent=1))
    return root


def run_tiny(root: Path, workload: str, seed: int = 7, seconds: float = 1.0,
             trace: int = 0) -> dict:
    """One run of a cell of `root`, on the CPU, skipping the look for a chip."""
    from bench import run

    args = run.parse_args(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
    return run.run_cell(args, root=root, require_chip=False)


def dense_chain(widths, slot_capacity: int = 256) -> tuple:
    """(config, net module) of a dense chain at `widths`, as a per-layer
    reader gets them on `RunData`."""
    from bench import run

    config = {"widths": list(widths), "input_threshold": 128, "slot_capacity": slot_capacity}
    return config, run.load_net(ROOT, config)
