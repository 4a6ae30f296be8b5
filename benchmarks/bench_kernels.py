"""Kernel micro-benchmarks: Pallas (interpret) vs jnp oracle timings and
arithmetic checks. Interpret mode is Python emulation — the derived column
reports correctness/op-counts, not TPU speed (`bench/run.py` measures that).
"""
from __future__ import annotations

import numpy as np

from repro.netgen import telemetry


def _time(fn, *args, reps=3):
    fn(*args)  # warm
    with telemetry.timed("bench_kernel_seconds") as t:
        for _ in range(reps):
            r = fn(*args)
            if hasattr(r, "block_until_ready"):
                r.block_until_ready()
            elif isinstance(r, tuple):
                r[0].block_until_ready()
    return t.elapsed / reps


def run(full: bool = False) -> list[str]:
    import jax.numpy as jnp
    from repro.kernels.binary_matvec import ops as bops, ref as bref
    from repro.kernels.quant_matmul import ops as qops, ref as qref

    rows = []
    rng = np.random.default_rng(0)

    # binary matvec: paper-sized layer 784 -> 500
    x = jnp.asarray(rng.integers(0, 2, size=(64, 784)).astype(np.int8))
    w = jnp.asarray(rng.integers(-9, 10, size=(784, 500)).astype(np.int32))
    t_ref = _time(lambda: bref.binary_matmul_ref(x, w))
    got = bops.binary_matmul(x, w)
    ok = int(np.array_equal(np.asarray(got),
                            np.asarray(bref.binary_matmul_ref(x, w))))
    rows.append(f"kern_binary_matmul_ref,{t_ref*1e6:.1f},exact={ok}")

    # the three pallas datapaths on the same layer: int8 activations vs
    # packed activations vs fully bit-packed (bit-plane weights, popcount)
    from repro.netgen.plan import decompose_planes
    want = np.asarray(x).astype(np.int64) @ np.asarray(w).astype(np.int64)
    xp = bops.pack_bits(x)
    kp = xp.shape[1] * 32
    wp = jnp.zeros((kp, 500), jnp.int32).at[:784].set(w)
    pos, neg, n_planes = decompose_planes(np.asarray(wp))
    pos, neg = jnp.asarray(pos), jnp.asarray(neg)
    for name, fn in (
            ("dense", lambda: bops.binary_matmul(x, w)),
            ("packed", lambda: bops.binary_matmul_packed(xp, wp)),
            ("planes", lambda: bops.binary_matmul_planes(xp, pos, neg))):
        t_k = _time(fn)
        ok = int(np.array_equal(np.asarray(fn()), want))
        detail = f"exact={ok}" + (f";planes={n_planes}" if name == "planes"
                                  else "")
        rows.append(f"kern_binary_matmul_{name},{t_k*1e6:.1f},{detail}")

    # quant matmul
    xq = jnp.asarray(rng.integers(-127, 128, size=(64, 512)).astype(np.int8))
    wq = jnp.asarray(rng.integers(-127, 128, size=(512, 256)).astype(np.int8))
    sw = jnp.ones((256,), jnp.float32)
    t_ref = _time(lambda: qref.quant_matmul_ref(xq, wq, np.float32(1), sw))
    got = qops.quant_matmul(xq, wq, np.float32(1), sw)
    ok = int(np.allclose(np.asarray(got),
                         np.asarray(qref.quant_matmul_ref(xq, wq, np.float32(1), sw))))
    rows.append(f"kern_quant_matmul_ref,{t_ref*1e6:.1f},exact={ok}")
    return rows
