"""binary_matvec kernel vs jnp oracle: shape/dtype sweeps + properties."""
import numpy as np
import pytest
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep (requirements.txt); stub keeps suite collectable
    from _hypothesis_stub import given, settings, strategies as st

from repro.kernels.binary_matvec import ops, ref

SHAPES = [
    (1, 784, 500),     # the paper's layer-1 shape
    (4, 500, 10),      # the paper's layer-2 shape
    (8, 128, 128),
    (3, 200, 77),      # ragged, forces padding
    (16, 64, 256),
    (2, 1024, 32),
]


@pytest.mark.parametrize("b,k,n", SHAPES)
@pytest.mark.parametrize("wdtype", [jnp.int32, jnp.int8])
def test_binary_matmul_matches_oracle(b, k, n, wdtype):
    rng = np.random.default_rng(b * 1000 + k + n)
    x = rng.integers(0, 2, size=(b, k)).astype(np.int8)
    w = rng.integers(-9, 10, size=(k, n)).astype(np.int32)
    got = ops.binary_matmul(jnp.asarray(x), jnp.asarray(w).astype(wdtype))
    want = ref.binary_matmul_ref(jnp.asarray(x), jnp.asarray(w).astype(wdtype))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,k,n", [(4, 256, 64), (2, 784, 500), (5, 96, 40)])
def test_binary_matmul_packed_matches_oracle(b, k, n):
    rng = np.random.default_rng(k + n)
    x = rng.integers(0, 2, size=(b, k)).astype(np.int8)
    w = rng.integers(-9, 10, size=(k, n)).astype(np.int32)
    xp = ops.pack_bits(jnp.asarray(x))
    kp = xp.shape[1] * 32
    wp = jnp.zeros((kp, n), jnp.int32).at[:k].set(jnp.asarray(w))
    got = ops.binary_matmul_packed(xp, wp)
    want = np.asarray(x.astype(np.int64) @ w.astype(np.int64))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2, size=(7, 130)).astype(np.int8)
    xp = ops.pack_bits(jnp.asarray(x))
    back = ref.unpack_bits_ref(xp, 130)
    np.testing.assert_array_equal(np.asarray(back)[:, :130], x)


def test_masked_form_equals_matmul():
    """The paper's L5 identity: masked column-sum == matmul for binary x."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 2, size=(9, 61)).astype(np.int8))
    w = jnp.asarray(rng.integers(-5, 6, size=(61, 13)).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ref.binary_matmul_masked_ref(x, w)),
        np.asarray(ref.binary_matmul_ref(x, w)),
    )


@settings(max_examples=25, deadline=None)
@given(
    b=st.integers(1, 8),
    k=st.integers(1, 200),
    n=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_binary_matmul_property(b, k, n, seed):
    """Property: kernel == int matmul for any binary input / int weights."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2, size=(b, k)).astype(np.int8)
    w = rng.integers(-9, 10, size=(k, n)).astype(np.int32)
    got = np.asarray(ops.binary_matmul(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_array_equal(got, x.astype(np.int64) @ w.astype(np.int64))


# ---------------------------------------------------------------------------
# Bit-plane kernel: both operands packed, popcount accumulation
# ---------------------------------------------------------------------------

def _planes_for(w: np.ndarray):
    """Decompose a dense int32 (K, N) into packed signed bit-planes,
    zero-padding K up to a lane multiple (what `plan.planes()` does)."""
    from repro.netgen.plan import decompose_planes
    k, n = w.shape
    kp = ((k + 31) // 32) * 32
    if kp != k:
        w = np.pad(w, ((0, kp - k), (0, 0)))
    return decompose_planes(w.astype(np.int32))


@pytest.mark.parametrize("b,k,n,lo,hi", [
    (4, 256, 64, -9, 9),
    (2, 784, 500, -5, 5),      # the paper's layer-1 shape
    (5, 96, 40, -1, 1),        # single plane (pure BNN case)
    (3, 77, 13, -300, 300),    # 9 planes: wide post-pass magnitudes
    (1, 33, 3, 0, 0),          # all-zero weights: one zero plane
])
def test_binary_matmul_planes_matches_matmul(b, k, n, lo, hi):
    rng = np.random.default_rng(k * 31 + n)
    x = rng.integers(0, 2, size=(b, k)).astype(np.int8)
    w = rng.integers(lo, hi + 1, size=(k, n)).astype(np.int32)
    xp = ops.pack_bits(jnp.asarray(x))
    pos, neg, p = _planes_for(w)
    assert p == max(1, int(np.abs(w).max(initial=0)).bit_length())
    got = np.asarray(ops.binary_matmul_planes(
        xp, jnp.asarray(pos), jnp.asarray(neg)))
    np.testing.assert_array_equal(got, x.astype(np.int64) @ w.astype(np.int64))


def test_binary_matmul_planes_matches_plane_oracle():
    """Kernel vs the unpack-and-matmul oracle on the same plane arrays
    (isolates kernel arithmetic from the decomposition)."""
    rng = np.random.default_rng(5)
    x = rng.integers(0, 2, size=(6, 64)).astype(np.int8)
    w = rng.integers(-7, 8, size=(64, 20)).astype(np.int32)
    xp = ops.pack_bits(jnp.asarray(x))
    pos, neg, _ = _planes_for(w)
    pos, neg = jnp.asarray(pos), jnp.asarray(neg)
    np.testing.assert_array_equal(
        np.asarray(ops.binary_matmul_planes(xp, pos, neg)),
        np.asarray(ref.plane_matmul_ref(xp, pos, neg)))


@pytest.mark.parametrize("bm,bn,bkw", [(64, 64, 4), (128, 32, 2), (8, 8, 1)])
def test_binary_matmul_planes_block_sizes(bm, bn, bkw):
    """The tuner's search axes: every block-size choice is exact (ragged
    shapes force padding on all three grid axes)."""
    rng = np.random.default_rng(bm + bn + bkw)
    x = rng.integers(0, 2, size=(9, 200)).astype(np.int8)
    w = rng.integers(-6, 7, size=(200, 77)).astype(np.int32)
    xp = ops.pack_bits(jnp.asarray(x))
    pos, neg, _ = _planes_for(w)
    got = np.asarray(ops.binary_matmul_planes(
        xp, jnp.asarray(pos), jnp.asarray(neg), bm=bm, bn=bn, bkw=bkw))
    np.testing.assert_array_equal(got, x.astype(np.int64) @ w.astype(np.int64))


def test_step_pack_fuses_step_and_pack():
    """step_pack == strict step then pack_bits, without the int8 hop
    (the packed chains' layer boundary)."""
    rng = np.random.default_rng(3)
    acc = rng.integers(-40, 41, size=(7, 45)).astype(np.int32)
    got = ops.step_pack(jnp.asarray(acc), words=2)
    want = ops.pack_bits(jnp.asarray((acc > 0).astype(np.int8)))
    assert got.dtype == jnp.uint32 and got.shape == (7, 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # extra padded words stay zero (next layer's wider padded fan_in)
    wide = np.asarray(ops.step_pack(jnp.asarray(acc), words=4))
    np.testing.assert_array_equal(wide[:, :2], np.asarray(want))
    assert not wide[:, 2:].any()


def test_binarize_pack_matches_threshold_then_pack():
    rng = np.random.default_rng(4)
    x = rng.integers(0, 256, size=(5, 70)).astype(np.uint8)
    thr = 128
    got = ops.binarize_pack(jnp.asarray(x), threshold=thr, words=3)
    want = ops.pack_bits(jnp.asarray((x > thr).astype(np.int8)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 6),
    k=st.integers(1, 150),
    n=st.integers(1, 40),
    mag=st.integers(0, 500),
    seed=st.integers(0, 2**31 - 1),
)
def test_binary_matmul_planes_property(b, k, n, mag, seed):
    """Property: the bit-plane kernel == int matmul for any binary input
    and any signed weight magnitude range (plane count adapts)."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2, size=(b, k)).astype(np.int8)
    w = rng.integers(-mag, mag + 1, size=(k, n)).astype(np.int32)
    xp = ops.pack_bits(jnp.asarray(x))
    pos, neg, _ = _planes_for(w)
    got = np.asarray(ops.binary_matmul_planes(
        xp, jnp.asarray(pos), jnp.asarray(neg)))
    np.testing.assert_array_equal(got, x.astype(np.int64) @ w.astype(np.int64))


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 40),
    n_in=st.integers(1, 80),
    n_h=st.integers(1, 40),
    n_out=st.integers(2, 8),
    mag=st.integers(1, 60),
    seed=st.integers(0, 2**31 - 1),
)
def test_binary_forward_planes_property(b, n_in, n_h, n_out, mag, seed):
    """Property: the whole-net megakernel == the layer-by-layer numpy
    forward (binarize, matmul, strict step, matmul, argmax) for any
    widths, batch, and weight magnitude — the in-register repack and
    all padding seams must be exact."""
    from repro.netgen.plan import lower_circuit
    from repro.core import quantize
    from repro import netgen

    rng = np.random.default_rng(seed)
    w1 = rng.integers(-mag, mag + 1, size=(n_in, n_h)).astype(np.int32)
    w2 = rng.integers(-mag, mag + 1, size=(n_h, n_out)).astype(np.int32)
    net = quantize.QuantizedNet(weights=[w1, w2])
    x = rng.integers(0, 256, size=(b, n_in)).astype(np.uint8)

    a = (x.astype(np.int64) > net.input_threshold).astype(np.int64)
    acc = ((a @ w1 > 0).astype(np.int64)) @ w2
    want = np.argmax(acc, axis=-1).astype(np.int32)

    view = lower_circuit(netgen.lower(net)).megakernel_view()
    got = np.asarray(ops.binary_forward_planes(
        jnp.asarray(x), *[jnp.asarray(p) for p in view.arrays],
        threshold=net.input_threshold, n_classes=view.n_classes))
    np.testing.assert_array_equal(got, want)


def test_resolve_interpret_follows_platform():
    """Interpret mode only where JAX's backend is the CPU; an explicit
    flag always wins."""
    import jax
    from repro.kernels import resolve_interpret

    assert resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


@pytest.mark.parametrize("mag", [5, 1 << 12, 1 << 24])
def test_fused_mlp_exact_for_wide_int32_weights(mag):
    """The fused kernel's integer dots run as four exact byte dots on the
    MXU: every byte of a negative or large weight must reach the sum."""
    from repro.kernels.fused_mlp import ops as fused

    rng = np.random.default_rng(mag)
    w1 = rng.integers(-mag, mag + 1, size=(70, 33)).astype(np.int32)
    w2 = rng.integers(-mag, mag + 1, size=(33, 6)).astype(np.int32)
    x = rng.integers(0, 256, size=(40, 70)).astype(np.uint8)
    a = (x.astype(np.int64) > 128).astype(np.int64)
    hidden = (a @ w1).astype(np.int32) > 0          # int32 wraps as the kernel
    want = np.argmax((hidden.astype(np.int64) @ w2).astype(np.int32), axis=-1)
    got = np.asarray(fused.fused_mlp_predict(
        jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2), threshold=128))
    np.testing.assert_array_equal(got, want)
