"""Quickstart: the paper's pipeline end-to-end in ~1 minute.

Trains the 784-500-10 classifier, walks the optimization ladder
(sigmoid -> step -> binary input -> integer weights), then "generates
hardware": the netgen specializer emits (a) a clockless Verilog module in
the paper's Figure-6 style and (b) a TPU-ready specialized inference
function, and verifies both are exact rewrites.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import netgen, quantize
from repro.core.ladder import run_ladder


def main():
    enable_compile_cache()
    print("== paper ladder (reduced size for speed; benchmarks run full) ==")
    r = run_ladder(n_train=600, n_test=400, epochs=30, seed=0,
                   backends=("jnp", "pallas"))
    print(r.table())
    print(f"\nL4/L5 exact rewrites of L3: {r.exact_l4_l5}")
    print(f"zero weights pruned at generation: {r.stats.zero_fraction:.1%}")
    print(f"multiplies after addend rewrite:  {r.stats.mults_addend}")

    print("\n== hardware generation (paper Figure 6 artifact) ==")
    rng = np.random.default_rng(0)
    demo = quantize.QuantizedNet(
        w1=rng.integers(-9, 10, size=(3, 3)).astype(np.int32),
        w2=rng.integers(-9, 10, size=(3, 3)).astype(np.int32))
    verilog = netgen.emit_verilog(demo, addend=True)
    print(verilog)
    out = "/tmp/nn_inference_3x3.v"
    with open(out, "w") as f:
        f.write(verilog)
    print(f"[written to {out}]")


if __name__ == "__main__":
    main()
