"""The harness is driven by data: a new configuration, traffic mix and
per-layer metric are found from files and BENCHMARK.json entries alone, and
a run without a chip prints no result."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import generator, run
from bench.tests.conftest import ROOT, TINY_OFFLINE, TINY_ONLINE, run_tiny


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_config_traffic_and_metric_from_files_alone(tiny_root):
    before = _digests(tiny_root)
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    cfg.update(name="tiny-deep", widths=[784, 48, 48, 10],
               versions=[{"name": "deep", "weights": {"scheme": "sign_normal"}}])
    (tiny_root / "bench/configs/tiny-deep.json").write_text(json.dumps(cfg))
    cfg2 = dict(cfg, name="tiny-ab", versions=[
        {"name": "a", "weights": {"scheme": "sign_normal"}},
        {"name": "b", "weights": {"scheme": "int_cast_normal", "bound": 3}}])
    (tiny_root / "bench/configs/tiny-ab.json").write_text(json.dumps(cfg2))
    (tiny_root / "bench/traffic/tiny-slow.json").write_text(json.dumps(
        {"mode": "online", "versions": 1, "rate_per_s": 150, "image_pool": 64,
         "max_batch_delay_s": 0.001, "max_queue_depth": 64}))
    # a new schedule from data alone: on/off bursts to two versions, 3:1
    (tiny_root / "bench/traffic/tiny-bursty-ab.json").write_text(json.dumps(
        {"mode": "online", "versions": 2, "popularity": [3, 1], "image_pool": 64,
         "phases": [{"seconds": 0.25, "rate_per_s": 400}, {"seconds": 0.25, "rate_per_s": 40}],
         "max_batch_delay_s": 0.001, "max_queue_depth": 256}))
    (tiny_root / "bench/metrics/answers_per_s.py").write_text(
        "def read(run):\n    return run.completed / run.window_s\n")
    layout = json.loads((tiny_root / "BENCHMARK.json").read_text())
    layout["configs"].append({"name": "tiny-deep", "source": "https://arxiv.org/abs/1612.07119",
                              "file": "bench/configs/tiny-deep.json", "reduced": ["widths"],
                              "why": "test size"})
    layout["configs"].append({"name": "tiny-ab", "source": "https://arxiv.org/abs/2012.08071",
                              "file": "bench/configs/tiny-ab.json", "reduced": ["widths"],
                              "why": "test size"})
    layout["workloads"] += [
        {"name": "deep-slow", "config": "tiny-deep", "traffic": "tiny-slow", "chips": 1,
         "why": "test size"},
        {"name": "ab-bursty", "config": "tiny-ab", "traffic": "tiny-bursty-ab", "chips": 1,
         "why": "test size"}]
    for m in layout["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"] += ["deep-slow", "ab-bursty"]
    layout["per_layer"] += [
        {"name": "answers_per_s", "unit": "preds/s", "better": "higher", "source": "host_clock",
         "layer": "load generator", "moves": "p50_ms", "workloads": ["deep-slow"]},
        {"name": "answers_per_s.bursty", "unit": "preds/s", "better": "higher",
         "source": "host_clock", "layer": "load generator", "moves": "p50_ms",
         "workloads": ["ab-bursty"]}]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(layout))

    spec = run.load_cell(tiny_root, "deep-slow")
    assert spec["config"]["widths"] == [784, 48, 48, 10]
    assert spec["traffic"]["rate_per_s"] == 150
    assert [n for n, _, _ in spec["per_layer"]] == ["answers_per_s"]
    assert [n for n, _ in spec["end_to_end"]] == ["setup_s", "p50_ms"]

    timed = run_tiny(tiny_root, "deep-slow")
    assert timed["correct"] and timed["attempted"] == 150
    assert set(timed["metrics"]) == {"setup_s", "p50_ms"}
    traced = run_tiny(tiny_root, "deep-slow", trace=1)
    assert traced["correct"]
    assert set(traced["metrics"]) == {"answers_per_s"}
    assert traced["metrics"]["answers_per_s"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}

    bursty = run_tiny(tiny_root, "ab-bursty", seconds=1.0)
    assert bursty["correct"], bursty["checks"]
    assert bursty["attempted"] == 220   # two cycles of 0.25 s at 400/s and 0.25 s at 40/s
    assert set(bursty["metrics"]) == {"setup_s", "p50_ms"}
    traced = run_tiny(tiny_root, "ab-bursty", trace=1)
    assert set(traced["metrics"]) == {"answers_per_s.bursty"}
    after = _digests(tiny_root)
    assert {p: d for p, d in after.items() if p in before} == before


def test_result_line_keys_and_metrics(tiny_root):
    res = run_tiny(tiny_root, TINY_OFFLINE)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "window_compiles", "checks"]
    assert res["window_compiles"] == 0
    assert set(res["metrics"]) == {"setup_s", "preds_per_s"}
    assert res["metrics"]["preds_per_s"]["unit"] == "preds/s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    assert res["checks"] == {k: {"value": 0.0, "limit": v} for k, v in run.LIMITS.items()}


def test_seed_fixes_inputs_and_every_seed_sends_the_same_load(tiny_root):
    spec = run.load_cell(tiny_root, TINY_ONLINE)
    a = generator.make_inputs(spec["traffic"], 784, ["paper"], 2.0, 2 ** 31 + 11)
    b = generator.make_inputs(spec["traffic"], 784, ["paper"], 2.0, 2 ** 31 + 11)
    c = generator.make_inputs(spec["traffic"], 784, ["paper"], 2.0, 5)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["pool"], c["pool"])
    # one set of gaps in another order: the same count and nearly the same span
    assert len(a["due"]) == len(c["due"]) == 800
    assert abs(a["due"][-1] - c["due"][-1]) <= np.diff(c["due"]).max()


def test_phases_shape_the_schedule_and_versions_get_fixed_shares():
    mix = {"mode": "online", "versions": 3, "popularity": {"zipf_s": 1.0}, "image_pool": 8,
           "phases": [{"seconds": 0.5, "rate_per_s": 2000}, {"seconds": 1.5, "rate_per_s": 200}]}
    runs = [generator.make_inputs(mix, 4, ["a", "b", "c"], 4.0, seed) for seed in (1, 2 ** 31 + 3)]
    for got in runs:
        due = got["due"]
        assert len(due) == 2 * (1000 + 300)
        assert np.all(np.diff(due) >= 0) and due[-1] <= 4.0
        on = ((due % 2.0) < 0.5).sum()           # the bursts hold 1000 of every 1300
        assert abs(on - 2000) < 60
        counts = np.bincount(got["ver"], minlength=3)
        np.testing.assert_array_equal(counts, [1418, 709, 473])   # 6:3:2 of 2600
    assert not np.array_equal(runs[0]["ver"], runs[1]["ver"])


def test_unknown_mix_keys_are_refused():
    with pytest.raises(generator.MixError):
        generator.make_inputs({"mode": "trickle", "versions": 1}, 4, ["a"], 1.0, 1)
    with pytest.raises(generator.MixError):
        generator.make_inputs({"mode": "online", "versions": 1, "image_pool": 4,
                               "phases": [{"seconds": 1, "rate_per_s": 0}]}, 4, ["a"], 1.0, 1)
    with pytest.raises(generator.MixError):
        generator.make_inputs({"mode": "online", "versions": 2, "image_pool": 4,
                               "rate_per_s": 10, "popularity": [1]}, 4, ["a", "b"], 1.0, 1)
    with pytest.raises(generator.MixError):
        generator.make_inputs({"mode": "offline", "versions": 2, "rows_per_version": [8],
                               "blocks": 1}, 4, ["a", "b"], 1.0, 1)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-online-poisson", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _has_result(stdout: str) -> bool:
    return any(line.lstrip().startswith("{") for line in stdout.splitlines())


def test_run_without_a_chip_exits_nonzero_with_no_result():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "needs 1 TPU chip" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
