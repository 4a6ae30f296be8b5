"""Compile rehearsals for the TPU: every Pallas datapath of the serving
path, at the paper's 784-500-10 width and batch 256 (the megakernel also
at NetServer's largest multi-round launch), compiled by the TPU
compiler for a described (not attached) v5e chip.

Interpret mode accepts block shapes, reductions and dots that Mosaic
refuses; these compiles catch such a kernel before it reaches a chip.
Nothing runs, so exactness stays with the interpret-mode kernel tests.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one given this file
loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.binary_matvec import binary_matvec as bmv
from repro.kernels.fused_mlp import fused_mlp as fm
from repro.netgen.serve import MAX_LAUNCH_BYTES, MAX_ROUNDS_PER_LAUNCH

N_IN, N_HIDDEN, N_OUT, PLANES = 784, 500, 10, 3
IN_WORDS = -(-N_IN // 32)               # 25 packed input words
HIDDEN_WORDS = -(-N_HIDDEN // 32)       # 16: the megakernel pads 500 -> 512
BATCH = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    """Compile for the described chip with the persistent cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _megakernel_planes(sharding, lead=()):
    """pos/neg bit-planes per layer as `megakernel_view` lays them out."""
    shapes = [(PLANES, IN_WORDS, HIDDEN_WORDS * 32),
              (PLANES, HIDDEN_WORDS, N_OUT)]
    return [_spec(sharding, lead + s, jnp.uint32)
            for s in shapes for _ in ("pos", "neg")]


@pytest.mark.parametrize("models,batch", [
    (None, BATCH),       # single net
    (2, BATCH),          # stacked M=2 (NetServer's stacked dispatch)
    (2, BATCH // 4),     # stacked, one shard of the four-chip data mesh
    # NetServer's largest launch: MAX_ROUNDS_PER_LAUNCH slot rounds
    (None, MAX_ROUNDS_PER_LAUNCH * BATCH),
    (2, MAX_ROUNDS_PER_LAUNCH * BATCH),
])
def test_fusednet_compiles_for_v5e(one_chip, models, batch):
    lead = () if models is None else (models,)
    x = _spec(one_chip, lead + (batch, N_IN), jnp.uint8)
    text = _compiled_text(
        lambda x, *p: bmv.binary_forward_planes(
            x, *p, threshold=128, n_classes=N_OUT, interpret=False),
        x, *_megakernel_planes(one_chip, lead))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fan_in,fan_out", [
    (N_IN, N_HIDDEN), (HIDDEN_WORDS * 32, N_OUT)])
def test_planes_kernel_compiles_for_v5e(one_chip, fan_in, fan_out):
    words = -(-fan_in // 32)
    planes = _spec(one_chip, (PLANES, words, fan_out), jnp.uint32)
    text = _compiled_text(
        lambda x, p, n: bmv.binary_matmul_planes(x, p, n, interpret=False),
        _spec(one_chip, (BATCH, words), jnp.uint32), planes, planes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fan_in,fan_out", [
    (IN_WORDS * 32, N_HIDDEN), (HIDDEN_WORDS * 32, N_OUT)])
def test_packed_kernel_compiles_for_v5e(one_chip, fan_in, fan_out):
    text = _compiled_text(
        lambda x, w: bmv.binary_matmul_packed(x, w, interpret=False),
        _spec(one_chip, (BATCH, fan_in // 32), jnp.uint32),
        _spec(one_chip, (fan_in, fan_out), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fan_in,fan_out", [(N_IN, N_HIDDEN),
                                            (N_HIDDEN, N_OUT)])
def test_dense_kernel_compiles_for_v5e(one_chip, fan_in, fan_out):
    text = _compiled_text(
        lambda x, w: bmv.binary_matmul(x, w, interpret=False),
        _spec(one_chip, (BATCH, fan_in), jnp.int8),
        _spec(one_chip, (fan_in, fan_out), jnp.int32))
    assert "tpu_custom_call" in text


def test_fused_mlp_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda x, w1, w2: fm.fused_mlp_predict(x, w1, w2, interpret=False),
        _spec(one_chip, (BATCH, N_IN), jnp.uint8),
        _spec(one_chip, (N_IN, N_HIDDEN), jnp.int32),
        _spec(one_chip, (N_HIDDEN, N_OUT), jnp.int32))
    assert "tpu_custom_call" in text


# FINN CNV's six conv layers at its published widths, each as the conv
# predictor launches it: (map H, W, C_in), C_out, whether a pool follows
CNV_CONVS = [((32, 32, 3), 64, False), ((30, 30, 64), 64, True),
             ((14, 14, 64), 128, False), ((12, 12, 128), 128, True),
             ((5, 5, 128), 256, False), ((3, 3, 256), 256, False)]
CNV_ROWS = MAX_LAUNCH_BYTES // (32 * 32 * 3)  # NetServer's largest CNV launch


@pytest.mark.parametrize("shape,cout,pool", CNV_CONVS,
                         ids=[f"{s[0]}x{s[1]}x{s[2]}-{c}" for s, c, _ in CNV_CONVS])
def test_conv_kernel_compiles_for_v5e(one_chip, shape, cout, pool):
    import numpy as np

    from repro.kernels.binary_conv import binary_conv as bc

    h, w, cin = shape
    geo = bc.conv_geometry(h, w, cin, cout, 3, 3, pool)
    taps = bc.banded_weights(geo, np.ones((3, 3, cin, cout), np.int64))
    args = [_spec(one_chip, (h, CNV_ROWS, w * cin), jnp.int8),
            _spec(one_chip, taps.shape, jnp.int8),
            _spec(one_chip, (1, geo.block_cols), jnp.int32)]
    if pool:
        args.append(_spec(one_chip, bc.pool_matrix(geo).shape, jnp.int8))
    text = _compiled_text(
        lambda *a: bc.binary_conv(*a, geo=geo, bm=bc.batch_tile(geo), interpret=False),
        *args)
    assert "tpu_custom_call" in text and "netgen_conv" in text


def test_megakernel_with_unit_thresholds_compiles_for_v5e(one_chip):
    # CNV's dense tail, 256-512-512-10, one plane a layer
    shapes = [(1, 8, 512), (1, 16, 512), (1, 16, 10)]
    planes = [_spec(one_chip, s, jnp.uint32) for s in shapes for _ in ("pos", "neg")]
    thresholds = tuple(_spec(one_chip, (1, s[-1]), jnp.int32) for s in shapes)
    text = _compiled_text(
        lambda x, t, *p: bmv.binary_forward_planes(
            x, *p, threshold=0, n_classes=10, thresholds=t, interpret=False),
        _spec(one_chip, (CNV_ROWS, 256), jnp.uint8), thresholds, *planes)
    assert "tpu_custom_call" in text
