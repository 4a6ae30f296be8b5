"""The comparison that decides `correct` fails where it must: the control
(the reference one precision below the configuration) and runs of the
harness with the timed path broken underneath, on the CPU at a small size."""
import numpy as np
import pytest

from bench import generator, run
from bench.control import control_gap
from bench.tests.conftest import TINY_OFFLINE, TINY_ONLINE, TINY_STACKED, run_tiny

CELLS = [TINY_ONLINE, TINY_OFFLINE, TINY_STACKED]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell, seed):
    got = control_gap(tiny_root, cell, seed, 1.0)
    assert got["max_gap"] > run.LIMITS["max_gap"]
    assert not got["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    res = run_tiny(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


def _altered(orig, kind):
    """Wrap a NetServer method so that the answers it produces are wrong
    ("alter") or half of them never come ("drop")."""
    def slots(self, compiled, x):
        out = orig(self, compiled, x)
        return (out + 1) % 10 if kind == "alter" else out[: (len(out) + 1) // 2]

    def stacked(self, fn, chunks, round=0):
        preds, valid = orig(self, fn, chunks, round=round)
        if kind == "alter":
            return (preds + 1) % 10, valid
        return preds, [(v + 1) // 2 for v in valid]

    return slots if orig.__name__ == "_run_slots" else stacked


@pytest.mark.parametrize("kind", ["alter", "drop"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell, kind):
    from repro.netgen.serve import NetServer

    monkeypatch.setattr(generator, "DRAIN_S", 1.0)
    for name in ("_run_slots", "_stacked_round"):
        monkeypatch.setattr(NetServer, name, _altered(getattr(NetServer, name), kind))
    res = run_tiny(tiny_root, cell)
    assert not res["correct"]
    key = "max_gap" if kind == "alter" else "unanswered"
    assert res["checks"][key]["value"] > res["checks"][key]["limit"]


def test_dispatch_that_raises_is_not_correct(tiny_root, monkeypatch):
    """Every batch of the window fails: the engine fails each future with
    the exception, which must count against `correct`, not only in `failed`."""
    from repro.netgen.serve import NetServer

    window_open = []
    predict_many, run_online = NetServer.predict_many, generator.run_online

    def broken(self, requests):
        if window_open:
            raise RuntimeError("planted dispatch failure")
        return predict_many(self, requests)

    def opened(*args, **kwargs):
        window_open.append(True)
        return run_online(*args, **kwargs)

    monkeypatch.setattr(NetServer, "predict_many", broken)
    monkeypatch.setattr(generator, "run_online", opened)
    res = run_tiny(tiny_root, TINY_ONLINE)
    assert not res["correct"]
    assert res["checks"]["errored"]["value"] == res["attempted"] > 0
    assert res["checks"]["max_gap"]["value"] == 0 and res["checks"]["unanswered"]["value"] == 0


def test_reference_is_exact_integer_arithmetic():
    from bench import reference

    rng = np.random.default_rng(0)
    ws = [rng.integers(-9, 10, (784, 50)), rng.integers(-9, 10, (50, 10))]
    x = rng.integers(0, 256, (64, 784), dtype=np.uint8)
    a = (x > 128).astype(np.int64)
    h = (a @ ws[0] > 0).astype(np.int64)
    config = {"input_threshold": 128}
    np.testing.assert_array_equal(reference.logits(ws, config, x), h @ ws[1])
    assert reference.widest_gap(reference.logits(ws, config, x),
                                (h @ ws[1]).argmax(axis=1)) == 0
    assert reference.widest_gap(np.zeros((2, 10)), np.array([0, 10])) == float("inf")
