"""The control of the comparison that decides `correct`: the plain reference
put in the program's place, one precision below what the configuration
states. Every configuration states uint8 pixels; the control holds them in
4 bits, the step that would halve the bytes each request sends: the
configuration's reference computes with `input_shift=4` (for a dense chain,
`x >> 4` against `threshold >> 4`). At each request of a run's window (the
same weights and the same images as that run of the seed) it reads the
widest gap by which the class the control puts first lies below the
reference's best.
A sound comparison must find the control not correct.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Prints one JSON line per seed. It runs no part of the program and needs no
chip; the benchmark's own runs do not run it.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)

import numpy as np  # noqa: E402

from bench import generator, run  # noqa: E402

INPUT_SHIFT = 4   # uint8 -> uint4


def control_gap(root: Path, workload: str, seed: int, seconds: float) -> dict:
    spec = run.load_cell(root, workload)
    config, traffic = spec["config"], spec["traffic"]
    versions = run.make_versions(root, config, int(traffic["versions"]))
    names = [v for v, _ in versions]
    inputs = generator.make_inputs(traffic, spec["net"].row_length(config), names, seconds,
                                   seed)
    ref_mod = run.load_file(root / "bench" / f"{config['reference']}.py", "bench_reference")
    if traffic["mode"] == "online":
        xs = [(ws, inputs["pool"][np.unique(inputs["idx"][inputs["ver"] == k])])
              for k, (_, ws) in enumerate(versions)]
    else:
        xs = [(ws, blk[v]) for blk in inputs["blocks"] for v, ws in versions]
    gap, n = 0.0, 0
    for ws, x in xs:
        ref = ref_mod.logits(ws, config, x)
        low = ref_mod.logits(ws, config, x, input_shift=INPUT_SHIFT)
        gap = max(gap, ref_mod.widest_gap(ref, low.argmax(axis=1)))
        n += x.shape[0]
    return {"workload": workload, "seed": seed, "rows": n, "max_gap": gap,
            "limit": run.LIMITS["max_gap"], "correct": gap <= run.LIMITS["max_gap"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(control_gap(ROOT, args.workload, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
