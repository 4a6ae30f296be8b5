"""Chip smoke test: the netgen serving path on a TPU at the paper's width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh-sharded stacked dispatch only

One process, which touches JAX once and starts no child. It trains the
784-500-10 net of `examples/mnist_fpga_pipeline.py` (a few epochs: the
width is what matters), quantizes it, and makes a second version that
stacks with it. Then, on one chip:

  * every callable target compiles through `Session.compile`, predicts
    the 1000 test images bit-exactly against `quantize.predict_quantized`,
    and each Pallas target's compiled HLO holds a `tpu_custom_call` (the
    kernel went through Mosaic, not the interpreter);
  * `ServingEngine` over `NetServer(target="pallas[fusednet=true]")`
    serves single requests of both versions; every future must resolve
    to the reference class, through at least one stacked dispatch, no
    fallback, and counted megakernel launches.

With `--chips 4` it runs only the stacked dispatch under a four-device
data mesh (`shard_map` over the slot axis) and compares it with the
one-device dispatch and the reference in the same process.

Without a TPU it exits non-zero and prints no result. On success the
last line of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

TARGETS = ("jnp", "pallas", "pallas[packed=true]", "pallas[planes=true]",
           "pallas[fusednet=true]", "fused")
SERVE_TARGET = "pallas[fusednet=true]"
SLOT_CAPACITY = 256
N_REQUESTS = 1000          # single requests through the engine, both versions
RESULT_TIMEOUT_S = 600.0


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def train_versions(n_hidden=500, epochs: int = 5, n_train: int = 1000,
                   n_test: int = 1000):
    """The paper net and a same-shape coarser version that stacks with
    it (as `examples/mnist_fpga_pipeline.py` builds them). Returns
    ({name: QuantizedNet}, uint8 test images)."""
    from repro.core import dataset, mlp, quantize

    xtr, ytr, xte, _ = dataset.train_test_split(n_train, n_test, seed=0)
    cfg = mlp.MLPConfig(n_hidden=n_hidden, epochs=epochs, lr=2.0, seed=42)
    t0 = time.perf_counter()
    params = mlp.train(cfg, xtr, ytr)
    print(f"trained {mlp.layer_sizes(cfg)} for {epochs} epochs in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    nets = {
        "paper": quantize.quantize(params),
        "paper-b5": quantize.QuantizedNet(weights=[
            quantize.int_cast_weights(w, bound=5)
            for w in quantize.param_weights(params)]),
    }
    return nets, xte


def reference(net, x):
    import numpy as np
    from repro.core import quantize

    return np.asarray(quantize.predict_quantized(net)(x))


def has_kernel(artifact, x) -> bool:
    """Whether the predictor's compiled HLO holds a Mosaic kernel."""
    return "tpu_custom_call" in artifact.jitted.lower(x).compile().as_text()


def phase_targets(session, net, x) -> None:
    import numpy as np

    print("== targets: Session.compile -> predict 1000 images ==",
          flush=True)
    ref = reference(net, x)
    for target in TARGETS:
        t0 = time.perf_counter()
        art = session.compile(net, target=target)
        preds = np.asarray(art(x))
        compile_s = time.perf_counter() - t0
        exact = bool(np.array_equal(preds, ref))
        line = f"target={target:24s} exact={exact} compile_s={compile_s:.3f}"
        if target != "jnp":
            kernel = has_kernel(art.artifact, x)
            line += f" tpu_custom_call={kernel}"
        print(line, flush=True)
        check(exact, f"{target} bit-exact with predict_quantized")
        if target != "jnp":
            check(kernel, f"{target} compiled to a Mosaic kernel")


def phase_serving(session, nets, x) -> None:
    from repro import netgen
    from repro.netgen import telemetry

    print(f"== serving: ServingEngine over NetServer({SERVE_TARGET}) ==",
          flush=True)
    refs = {name: reference(net, x) for name, net in nets.items()}
    server = netgen.NetServer(session=session, target=SERVE_TARGET,
                              slot_capacity=SLOT_CAPACITY)
    for name, net in nets.items():
        server.register(name, net)
    launches = telemetry.kernel_launches("fusednet")
    launches_before = launches.value
    names = list(nets)
    engine = netgen.ServingEngine(server)
    try:
        t0 = time.perf_counter()
        futures = []
        for i in range(N_REQUESTS):
            name = names[i % len(names)]
            row = i % x.shape[0]
            futures.append((name, row, engine.submit(name, x[row])))
        wrong = 0
        for name, row, fut in futures:
            if fut.result(timeout=RESULT_TIMEOUT_S) != int(refs[name][row]):
                wrong += 1
        dt = time.perf_counter() - t0
    finally:
        engine.shutdown()
    counts = server.dispatch_counts
    grew = launches.value - launches_before
    print(f"requests={len(futures)} wrong={wrong} wall_s={dt:.3f} "
          f"dispatch={counts} fusednet_launches={grew} "
          f"engine={engine.stats().row()}", flush=True)
    check(wrong == 0, f"all {len(futures)} engine futures match the reference")
    check(counts["stacked"] >= 1, "stacked dispatch >= 1")
    check(counts["fallback"] == 0, "fallback dispatch == 0")
    check(grew > 0, 'netgen_kernel_launches_total{form="fusednet"} grew')


def phase_sharded(session, nets, x, n_devices: int = 4) -> None:
    import numpy as np
    from repro import netgen
    from repro.netgen.mesh import make_host_mesh, use_mesh

    print(f"== sharded: stacked {SERVE_TARGET} over a {n_devices}-device "
          "data mesh ==", flush=True)
    refs = {name: reference(net, x) for name, net in nets.items()}
    server = netgen.NetServer(session=session, target=SERVE_TARGET,
                              slot_capacity=SLOT_CAPACITY)
    for name, net in nets.items():
        server.register(name, net)
    batch = {name: x for name in nets}
    one = server.predict_many(batch)
    mesh = make_host_mesh(data=n_devices)
    ids = sorted(d.id for d in mesh.devices.flat)
    print(f"mesh={dict(mesh.shape)} devices={ids}", flush=True)
    check(mesh.shape["data"] == n_devices and len(set(ids)) == n_devices,
          f"mesh data axis spans {n_devices} distinct devices")
    with use_mesh(mesh):
        many = server.predict_many(batch)
        fn, sharded = server._stacked_fn(tuple(sorted(nets)))
        block = np.zeros((len(nets), SLOT_CAPACITY, x.shape[1]), np.uint8)
        placed = fn(block).sharding.device_set
    counts = server.dispatch_counts
    print(f"dispatch={counts} output_devices={len(placed)}", flush=True)
    check(sharded and counts["sharded"] >= 1, "sharded dispatch >= 1")
    check(len(placed) == n_devices,
          f"sharded output lives on {n_devices} devices")
    for name in nets:
        check(np.array_equal(many[name], one[name]),
              f"{name}: {n_devices}-device dispatch == one-device dispatch")
        check(np.array_equal(many[name], refs[name]),
              f"{name}: {n_devices}-device dispatch == predict_quantized")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh-sharded stacked dispatch")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; devices: {len(devices)} x "
          f"{dev.platform} ({dev.device_kind})", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {dev.platform!r}); "
              "this test runs the compiled kernels on a TPU only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    from repro import netgen

    try:
        nets, x = train_versions()
        session = netgen.Session()
        if args.chips == 4:
            phase_sharded(session, nets, x, n_devices=4)
        else:
            phase_targets(session, nets["paper"], x)
            phase_serving(session, nets, x)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
