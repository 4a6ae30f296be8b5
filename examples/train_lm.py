"""End-to-end driver: train a ~120M-parameter LM for a few hundred steps
on the synthetic pipeline, with checkpointing and fault-tolerant resume.

  PYTHONPATH=src python examples/train_lm.py [--steps 200] [--resume]

The model is a llama-style dense decoder (12L x 768d, GQA 12/4, 32k
vocab ~ 121M params). On this CPU container a step takes seconds; the
same driver, pointed at the production mesh via repro.launch, is the
multi-pod entry point.
"""
import argparse
import time

from repro.compile_cache import enable_compile_cache
from repro.models.base import ArchConfig, ShapeConfig
from repro.optim import adamw
from repro.train import trainer

CFG_100M = ArchConfig(
    name="repro-120m",
    family="dense",
    n_layers=12,
    d_model=768,
    n_heads=12,
    n_kv_heads=4,
    head_dim=64,
    d_ff=2048,
    vocab=32768,
    act="swiglu",
    norm="rmsnorm",
    rope_theta=1e4,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    shape = ShapeConfig("e2e", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    oc = adamw.OptConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
    tc = trainer.TrainerConfig(
        total_steps=args.steps, ckpt_every=50, ckpt_dir=args.ckpt_dir,
        log_every=10)

    from repro.models import api
    from repro.models.base import count_params
    n = count_params(api.abstract_params(CFG_100M))
    print(f"model: {CFG_100M.name}, {n/1e6:.1f}M params, "
          f"{args.batch}x{args.seq} tokens/step")

    t0 = time.time()
    state, hist = trainer.run(CFG_100M, shape, oc, tc, resume=args.resume)
    dt = time.time() - t0
    losses = hist["loss"]
    print(f"\ntrained {len(losses)} steps in {dt:.0f}s "
          f"({dt/max(len(losses),1):.1f}s/step)")
    if losses:
        k = min(10, len(losses))
        print(f"loss: first{k}={sum(losses[:k])/k:.4f} "
              f"last{k}={sum(losses[-k:])/k:.4f}")
        assert sum(losses[-k:]) < sum(losses[:k]), "loss did not improve"
        print("loss improved ✓  (checkpoints in", tc.ckpt_dir + ")")


if __name__ == "__main__":
    main()
