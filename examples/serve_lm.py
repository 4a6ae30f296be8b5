"""Batched serving demo: prefill + decode with the serving engine, plus
the paper's technique applied to the checkpoint (int8 weight
specialization) with quality and size deltas.

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.data.pipeline import make_batch
from repro.models import api, base
from repro.quantized import apply as qapply
from repro.serve.engine import Engine, ServeConfig


def main():
    enable_compile_cache()
    cfg = configs.smoke("qwen1.5-4b")
    params = base.tree_init(api.abstract_params(cfg), jax.random.PRNGKey(0))

    print("== batched generation ==")
    eng = Engine(cfg, params, ServeConfig(max_len=128, max_new_tokens=16))
    prompts = (np.arange(32, dtype=np.int32).reshape(8, 4) * 13) % cfg.vocab
    t0 = time.time()
    out = eng.generate(prompts)
    dt = time.time() - t0
    total_new = out.size
    print(f"batch={prompts.shape[0]} prompt_len={prompts.shape[1]} "
          f"new_tokens={out.shape[1]} -> {total_new/dt:.1f} tok/s (CPU)")
    print("sample:", out[0].tolist())

    print("\n== paper technique on the LM checkpoint (W8 specialization) ==")
    shape = base.ShapeConfig("eval", 64, 4, "train")
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, 0).items()}
    loss_fp, _ = api.loss_fn(cfg, params, batch)
    qt, stats = qapply.quantize_tree(params, min_size=0)
    loss_q, _ = api.loss_fn(cfg, qapply.dequantize_tree(qt), batch)
    print(f"storage: {stats['bytes_before']/1e6:.2f} MB -> "
          f"{stats['bytes_after']/1e6:.2f} MB "
          f"({stats['compression']:.2f}x, {stats['n_quantized']} tensors)")
    print(f"loss: fp32={float(loss_fp):.4f}  int8-weights={float(loss_q):.4f} "
          f"(delta {abs(float(loss_q)-float(loss_fp))/float(loss_fp):.2%})")
    ps = qapply.prune_stats(params)
    print(f"structurally dead channels: {ps['dead_fraction']:.2%} "
          "(netgen would delete these at specialization)")


if __name__ == "__main__":
    main()
