"""Paper §V.E table: predictions/second, software vs specialized.

Paper's numbers: ~1,000/s for the devectorized CPU script (Intel i7) vs
5x10^8/s for the clockless FPGA (clock-bound). Our measured analogues on
this container's CPU:

  devectorized  — the paper's expanded Python script (explicit scalar
                  arithmetic per node), the honest software baseline
  vectorized    — numpy matmul version
  specialized   — netgen-compiled jitted masked-add network (weights
                  constant-folded)
  fused-kernel  — whole-net single Pallas launch (interpret mode: Python
                  emulation, NOT TPU speed; reported for completeness)

plus the projected TPU v5e bound for the fused int kernel from the
hardware model (the analogue of the paper's 500 MHz clock bound).
"""
from __future__ import annotations

import time

import numpy as np


def _devectorized_predict(w1, w2, img, threshold=128):
    """The paper's §IV expanded script: pure Python scalar ops, zero
    vectorization (their ~1000 predictions/s artifact)."""
    n_in, n_h = w1.shape
    n_out = w2.shape[1]
    inb = [1 if img[i] > threshold else 0 for i in range(n_in)]
    ho = [0] * n_h
    for j in range(n_h):
        acc = 0
        col = w1[:, j]
        for i in range(n_in):
            if inb[i]:
                acc += col[i]
        ho[j] = 1 if acc > 0 else 0
    best, best_v = 0, None
    for k in range(n_out):
        acc = 0
        col = w2[:, k]
        for j in range(n_h):
            if ho[j]:
                acc += col[j]
        if best_v is None or acc > best_v:
            best, best_v = k, acc
    return best


def run(full: bool = False) -> list[str]:
    import jax.numpy as jnp
    from repro.core import dataset, mlp, netgen, quantize

    n_hidden = 500 if full else 128
    xtr, ytr, xte, _ = dataset.train_test_split(600, 256, seed=2)
    cfg = mlp.MLPConfig(n_hidden=n_hidden, epochs=30, seed=5)
    params = mlp.train(cfg, xtr, ytr)
    qnet = quantize.quantize(params)
    qp, _ = netgen.prune(qnet)
    rows = []

    # 1) devectorized python (paper baseline)
    n_dev = 20 if full else 10
    t0 = time.perf_counter()
    for i in range(n_dev):
        _devectorized_predict(qp.w1, qp.w2, xte[i])
    dt = (time.perf_counter() - t0) / n_dev
    rows.append(f"throughput_devectorized_python,{dt*1e6:.0f},{1.0/dt:.1f}")

    # 2) vectorized numpy
    t0 = time.perf_counter()
    reps = 50
    for _ in range(reps):
        xb = (xte > 128).astype(np.int32)
        hi = xb @ qp.w1
        fi = (hi > 0).astype(np.int32) @ qp.w2
        fi.argmax(axis=1)
    dt = (time.perf_counter() - t0) / (reps * xte.shape[0])
    rows.append(f"throughput_vectorized_numpy,{dt*1e6:.2f},{1.0/dt:.0f}")

    # 3) specialized jitted (netgen, weights constant-folded)
    fn = netgen.specialize(qnet, backend="jnp")
    xj = jnp.asarray(xte)
    fn(xj).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn(xj).block_until_ready()
    dt = (time.perf_counter() - t0) / (reps * xte.shape[0])
    rows.append(f"throughput_specialized_jit,{dt*1e6:.2f},{1.0/dt:.0f}")

    # 4) fused Pallas kernel (interpret mode — correctness, not TPU speed)
    fnf = netgen.specialize(qnet, backend="fused")
    small = xj[:32]
    fnf(small).block_until_ready()
    t0 = time.perf_counter()
    fnf(small).block_until_ready()
    dt = (time.perf_counter() - t0) / small.shape[0]
    rows.append(f"throughput_fused_interpret,{dt*1e6:.2f},{1.0/dt:.1f}")

    # 5) projected TPU bound (hardware-model analogue of the paper's
    #    500 MHz clock bound): int8 ops at the v5e's published MXU rate
    #    (the benchmark's peak table), whole net in VMEM
    import json
    from pathlib import Path
    peaks = json.loads(
        (Path(__file__).resolve().parents[1] / "bench" / "peaks.json").read_text())
    int8_ops_per_s = peaks["devices"]["TPU v5 lite"]["int8_ops_per_s"]
    ops = 2 * (qp.w1.shape[0] * qp.w1.shape[1] + qp.w2.shape[0] * qp.w2.shape[1])
    t_pred = ops / int8_ops_per_s
    rows.append(f"throughput_tpu_v5e_bound,{t_pred*1e6:.4f},{1.0/t_pred:.0f}")
    return rows
