"""The one traffic generator. A mix is a data file, `bench/traffic/<mix>.json`.

Every mix names `"mode"` and `"versions"`, how many of the configuration's
versions it sends to (the first ones).

Online mixes (`"mode": "online"`) are open loop: requests are due on a
schedule fixed before the window opens and are sent whether or not earlier
ones finished. Each request is timed from its due time, so a late generator
or a stalled server shows in the latency. Keys:

- `rate_per_s`: the offered rate; or `phases`, a list of
  `{"seconds": s, "rate_per_s": r}` (every r > 0) repeated in turn over the
  window: on/off bursts, ramps, any piecewise-constant rate;
- `arrivals`: `"poisson"`: a Poisson stream of that rate, built from one
  fixed set of gaps (the exponential distribution's quantiles, in
  operational time) that the seed only puts in order, so every seed sends
  the same number of requests over the same span;
- `popularity` (optional, default equal): the share of requests each version
  gets, a list of weights or `{"zipf_s": s}` (weight 1 / k**s for the k-th
  version). The count per version is fixed; the seed orders them;
- `image_pool`: the seed's images that requests draw their pixels from;
- `max_batch_delay_s`, `max_queue_depth`: the engine's settings.

Offline mixes (`"mode": "offline"`) are one caller sending `blocks` seeded
calls in turn, back to back, for the whole window; a call holds
`rows_per_version` rows for each version (one number, or one per version).
"""
from __future__ import annotations

import contextlib
import time
from functools import partial

import numpy as np

DRAIN_S = 60.0   # how long past the window's close an answer may still come
STREAM_INPUTS, STREAM_ORDER, STREAM_VERSIONS = 2, 3, 4
ANSWERED_NONE, FAILED, REFUSED = -1, -2, -3   # `served` codes other than a class


class MixError(ValueError):
    """A traffic mix names a key or a value the generator does not know."""


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, int(seed) % 2 ** 64])


def phases(mix: dict) -> list:
    """[(seconds, rate)] of one cycle of the mix's offered rate."""
    if "phases" in mix:
        out = [(float(p["seconds"]), float(p["rate_per_s"])) for p in mix["phases"]]
    else:
        out = [(float("inf"), float(mix["rate_per_s"]))]
    if not out or any(s <= 0 or r <= 0 for s, r in out):
        raise MixError(f"every phase needs seconds > 0 and rate_per_s > 0: {out}")
    return out


def offered(cycle: list, seconds: float) -> float:
    """Requests offered over `seconds` by the rate `cycle` (the integral of its rate)."""
    total, t = 0.0, 0.0
    while t < seconds:
        for s, r in cycle:
            d = min(s, seconds - t)
            total, t = total + d * r, t + d
            if t >= seconds:
                break
    return total


def warp(cycle: list, u: np.ndarray) -> np.ndarray:
    """Times at which the rate `cycle` has offered `u` requests: the inverse
    of its integral, which maps a unit-rate stream onto the cycle."""
    if len(cycle) == 1:
        return u / cycle[0][1]
    knots_t, knots_n = [0.0], [0.0]
    while knots_n[-1] < u[-1]:
        for s, r in cycle:
            knots_t.append(knots_t[-1] + s)
            knots_n.append(knots_n[-1] + s * r)
    return np.interp(u, knots_n, knots_t)


def due_times(mix: dict, seconds: float, order: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of the mix's requests."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise MixError(f"unknown arrivals {mix['arrivals']!r}")
    cycle = phases(mix)
    n = max(1, int(round(offered(cycle, seconds))))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)           # unit-rate exponential gaps
    order.shuffle(gaps)
    return warp(cycle, np.cumsum(gaps) - gaps[0])


def shares(mix: dict, n_versions: int) -> np.ndarray:
    pop = mix.get("popularity")
    if pop is None:
        w = np.ones(n_versions)
    elif isinstance(pop, dict) and set(pop) == {"zipf_s"}:
        w = 1.0 / np.arange(1, n_versions + 1) ** float(pop["zipf_s"])
    elif isinstance(pop, list) and len(pop) == n_versions:
        w = np.asarray(pop, float)
    else:
        raise MixError(f"popularity {pop!r} for {n_versions} versions")
    if (w <= 0).any():
        raise MixError(f"popularity {pop!r}: every weight must be > 0")
    return w / w.sum()


def version_of_each(mix: dict, n_versions: int, n: int, seed: int) -> np.ndarray:
    """The version index of each of `n` requests: a fixed count per version
    (largest remainder of its share), in an order drawn from the seed."""
    want = shares(mix, n_versions) * n
    counts = np.floor(want).astype(int)
    counts[np.argsort(counts - want)[: n - counts.sum()]] += 1
    ver = np.repeat(np.arange(n_versions), counts)
    if n_versions > 1:
        rng(seed, STREAM_VERSIONS).shuffle(ver)
    return ver


def make_inputs(mix: dict, n_in: int, names: list, seconds: float, seed: int) -> dict:
    """The window's inputs from the seed. Online: a pool of images, and the
    due time, pool row and version index of each request. Offline: `blocks`
    calls' worth of {version: rows}."""
    pixels = rng(seed, STREAM_INPUTS)
    if mix["mode"] == "online":
        order = rng(seed, STREAM_ORDER)
        due = due_times(mix, seconds, order)
        pool = pixels.integers(0, 256, (mix["image_pool"], n_in), dtype=np.uint8)
        return {"pool": pool, "idx": order.integers(0, len(pool), len(due)), "due": due,
                "ver": version_of_each(mix, len(names), len(due), seed)}
    if mix["mode"] == "offline":
        rows = mix["rows_per_version"]
        rows = rows if isinstance(rows, list) else [rows] * len(names)
        if len(rows) != len(names):
            raise MixError(f"rows_per_version {rows} for {len(names)} versions")
        rows = dict(zip(names, rows))
        return {"blocks": [{v: pixels.integers(0, 256, (rows[v], n_in), dtype=np.uint8)
                            for v in names} for _ in range(mix["blocks"])]}
    raise MixError(f"unknown traffic mode {mix['mode']!r}")


def _done(t_done: np.ndarray, served: np.ndarray, i: int, fut) -> None:
    t_done[i] = time.perf_counter()
    served[i] = fut.result() if fut.exception() is None else FAILED


def run_online(submit, names: list, rows: list, inputs: dict, reject_errors: tuple,
               annotate=contextlib.nullcontext) -> dict:
    """Send request i (version `names[ver[i]]`, pixels `rows[idx[i]]`) at
    `due[i]`; wait for every answer.

    Returns the absolute due, send and answer times (perf_counter seconds,
    NaN where absent), the class each request was answered with (or
    ANSWERED_NONE, FAILED, REFUSED), the window's start, the time the last
    request was sent and the time the wait for answers ended. A future is
    held only until it resolves, so the harness keeps no garbage alive."""
    due = inputs["due"]
    n = len(due)
    t_sent = np.full(n, np.nan)
    t_done = np.full(n, np.nan)
    served = np.full(n, ANSWERED_NONE, np.int64)
    idx_l, ver_l = inputs["idx"].tolist(), inputs["ver"].tolist()
    pc = time.perf_counter
    t0 = pc()
    due_l = (t0 + due).tolist()
    i = 0
    with annotate("bench.window"):
        while i < n:
            wait_s = due_l[i] - pc()
            if wait_s > 0:
                time.sleep(wait_s)
                continue
            with annotate("bench.submit"):
                while i < n and due_l[i] <= pc():
                    t_sent[i] = pc()
                    try:
                        fut = submit(names[ver_l[i]], rows[idx_l[i]])
                    except reject_errors:
                        served[i] = REFUSED
                    else:
                        fut.add_done_callback(partial(_done, t_done, served, i))
                    i += 1
        t_closed = pc()
        while (served == ANSWERED_NONE).any() and pc() < t_closed + DRAIN_S:
            time.sleep(0.001)
    return {"t0": t0, "due": t0 + due, "t_sent": t_sent, "t_done": t_done,
            "served": served, "t_closed": t_closed, "t_end": pc()}


def run_offline(predict_many, blocks: list, seconds: float,
                annotate=contextlib.nullcontext) -> dict:
    """Call `predict_many(blocks[k % len(blocks)])` back to back until
    `seconds` have passed; the window ends when the last call returns."""
    outs = []
    pc = time.perf_counter
    t0 = pc()
    with annotate("bench.window"):
        while True:
            b = len(outs) % len(blocks)
            with annotate("bench.offline_call"):
                outs.append((b, predict_many(blocks[b])))
            if pc() - t0 >= seconds:
                break
    return {"t0": t0, "t_end": pc(), "outs": outs}
