"""Pallas TPU kernel: exact binary convolution on the MXU.

One `pallas_call` (named `netgen_conv`) computes one valid kh x kw
convolution of a batch of feature maps, applies a per-channel threshold
(`acc > t` -> {0,1}) and, where a 2x2 max-pool follows, fuses it (over
{0,1} values a max-pool is an OR).

Layout. A batch of H x W x C maps travels as int8 rows `(H, B, W*C)`:
image row y of image b is the lane vector of its W*C values, x-major
(the HWC row-major order of a request row). With the image index in
the sublanes and the map row in a leading dimension, the dy taps of the
convolution are slices of the leading dimension (free), and a tile of
`bm` images reshapes to a `(rows * bm, lanes)` matrix at no cost when
`bm` is a multiple of int8's 32-row sublane tile.

Arithmetic. Along x the convolution is a matrix product with a banded
(Toeplitz) weight: for the block of `bo` output positions starting at
x0, its `(bo + kw - 1) * Cin` input lanes times a
`((bo + kw - 1) * Cin, bo * Cout)` matrix give the block's `bo * Cout`
outputs, one product per dy, summed. The matrix is the same for every
block, so where `bo * Cin` and `bo * Cout` are multiples of 128 lanes
the window slides over the row at lane-aligned offsets (`sliding`);
otherwise every block reads the whole row through its own slice of the
banded matrix. im2col is never built, in HBM or in VMEM.

Exactness. Operands are int8 ({0,1} activations, integer weights with
|w| <= 127) and the MXU accumulates in int32, exact while every partial
sum fits (`repro.netgen.analysis` certifies it per plan). A first layer
that reads 8-bit pixels takes them as `x - 128` in int8; the constant
`128 * sum(w)` of each channel (the conv has no padding) is folded into
that channel's threshold by the caller.

Pooling. The 2x2 max-pool takes the max of row pairs (a leading-dim
max) and then ORs column pairs with one more exact int8 product: a
0/1 matrix sums each pair of positions into one pooled position, and a
sum > 0 is the OR. Pooled groups of blocks are stored lane-aligned.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

__all__ = ["ConvGeometry", "KERNEL_NAME", "ROW_TILE", "banded_weights",
           "batch_tile", "binary_conv", "conv_geometry", "flat_rows",
           "image_rows", "pool_matrix"]

KERNEL_NAME = "netgen_conv"   # the pallas_call's name, as a trace shows it
LANES = 128
ROW_TILE = 32                 # int8 sublane tile: images per batch tile step
MAX_TILE = 128                # the most images a grid step takes
MIN_ROWS = 512                # product rows (map rows x images) worth a step
VMEM_BUDGET = 12 * 2 ** 20    # of the 16 MiB a kernel may use by default


@dataclasses.dataclass(frozen=True)
class ConvGeometry:
    """Static shape of one conv layer's kernel (see module doc)."""
    h: int
    w: int
    cin: int
    cout: int
    kh: int
    kw: int
    pool: bool
    bo: int             # output positions per block
    sliding: bool       # shared banded matrix at lane-aligned offsets
    group: int          # blocks stored (and pooled) together

    @property
    def ho(self) -> int:
        return self.h - self.kh + 1

    @property
    def wo(self) -> int:
        return self.w - self.kw + 1

    @property
    def blocks(self) -> int:
        return self.wo // self.bo

    @property
    def window(self) -> int:
        """Input lanes one block reads."""
        return (self.bo + self.kw - 1) * self.cin if self.sliding else self.w * self.cin

    @property
    def block_cols(self) -> int:
        return self.bo * self.cout

    @property
    def out_shape(self) -> tuple[int, int, int]:
        """(rows, positions, channels) of the stored output map."""
        if self.pool:
            return self.ho // 2, self.wo // 2, self.cout
        return self.ho, self.wo, self.cout


def _aligned(n: int) -> bool:
    return n % LANES == 0


def conv_geometry(h: int, w: int, cin: int, cout: int, kh: int, kw: int,
                  pool: bool) -> ConvGeometry:
    """Choose the block layout of one layer: the fewest output positions
    a block whose input and output lanes are both 128-aligned (a sliding
    window), else blocks of whole 128-lane output columns over the whole
    row, else the whole row as one block; pooled groups store aligned."""
    ho, wo = h - kh + 1, w - kw + 1
    if ho < 1 or wo < 1:
        raise ValueError(f"a {kh}x{kw} kernel does not fit a {h}x{w} map")
    if pool and (ho % 2 or wo % 2):
        raise ValueError(f"a 2x2 pool needs an even {ho}x{wo} conv output")
    divisors = [b for b in range(1, wo + 1) if wo % b == 0]
    slide = [b for b in divisors if _aligned(b * cin) and _aligned(b * cout)]
    if slide:
        bo, sliding = slide[0], True
    else:
        cols = [b for b in divisors if _aligned(b * cout)]
        bo, sliding = (cols[0] if cols else wo), False
    blocks = wo // bo
    group = 1
    if pool:
        group = next((g for g in range(1, blocks + 1)
                      if blocks % g == 0 and (g * bo) % 2 == 0
                      and _aligned(g * bo // 2 * cout)), blocks)
    return ConvGeometry(h=h, w=w, cin=cin, cout=cout, kh=kh, kw=kw, pool=pool,
                        bo=bo, sliding=sliding, group=group)


def _lanes(n: int) -> int:
    return -(-n // LANES) * LANES


def vmem_bytes(geo: ConvGeometry, bm: int) -> int:
    """Estimated VMEM a grid step of `bm` images holds: the input and
    output blocks (double-buffered), the banded weights and one group's
    int32 products."""
    ho, wo, cout = geo.out_shape
    blocks_in = 2 * geo.h * bm * _lanes(geo.w * geo.cin)
    blocks_out = 2 * ho * bm * _lanes(wo * cout)
    n = 1 if geo.sliding else geo.blocks
    taps = 2 * geo.kh * n * -(-geo.window // ROW_TILE) * ROW_TILE * _lanes(geo.block_cols)
    work = 3 * geo.ho * bm * geo.group * _lanes(geo.block_cols) * 4
    return blocks_in + blocks_out + taps + work


def batch_tile(geo: ConvGeometry) -> int:
    """Images a grid step takes: enough that a product has MIN_ROWS rows
    (ROW_TILE multiples, at most MAX_TILE), fewer where VMEM binds."""
    bm = min(MAX_TILE, max(ROW_TILE, -(-MIN_ROWS // geo.ho // ROW_TILE) * ROW_TILE))
    while bm > ROW_TILE and vmem_bytes(geo, bm) > VMEM_BUDGET:
        bm //= 2
    return bm


def image_rows(x: jnp.ndarray, shape: tuple, mode: str, threshold: int,
               batch: int) -> jnp.ndarray:
    """uint8 request rows (B, H*W*C) -> the first layer's int8 rows
    (H, batch, W*C), zero-padded to `batch` images: "compare" gives
    `x > threshold` bits, "pixels" gives `x - 128`."""
    h, w, c = shape
    b = x.shape[0]
    x = jnp.pad(x, ((0, batch - b), (0, 0))).reshape(batch, h, w * c)
    x = jnp.transpose(x, (1, 0, 2))
    if mode == "pixels":
        return (x.astype(jnp.int32) - 128).astype(jnp.int8)
    return (x > threshold).astype(jnp.int8)


def flat_rows(a: jnp.ndarray) -> jnp.ndarray:
    """int8 {0,1} rows (H, B, W*C) -> uint8 (B, H*W*C), HWC-flattened."""
    h, b, lanes = a.shape
    return jnp.transpose(a, (1, 0, 2)).reshape(b, h * lanes).astype(jnp.uint8)


def banded_weights(geo: ConvGeometry, weights: np.ndarray) -> np.ndarray:
    """The int8 banded matrices of a layer, (kh, n, window, block_cols):
    n is 1 for a sliding window, else one per block. `weights` is the
    (kh, kw, cin, cout) integer kernel."""
    w = np.asarray(weights, np.int64)
    if np.abs(w).max(initial=0) > 127:
        raise ValueError("conv weights must fit int8 (|w| <= 127) for the MXU")
    kh, kw, cin, cout, bo = geo.kh, geo.kw, geo.cin, geo.cout, geo.bo
    n = 1 if geo.sliding else geo.blocks
    out = np.zeros((kh, n, geo.window, geo.block_cols), np.int8)
    for j in range(n):
        x0 = 0 if geo.sliding else j * bo      # window-relative first output
        for q in range(bo):
            for dx in range(kw):
                p = x0 + q + dx                # input position in the window
                out[:, j, p * cin:(p + 1) * cin, q * cout:(q + 1) * cout] = w[:, dx]
    return out


def pool_matrix(geo: ConvGeometry) -> np.ndarray:
    """0/1 int8 (group cols, group cols / 2): sums positions 2i and 2i+1
    of a stored group into pooled position i, channel by channel."""
    n = geo.group * geo.bo
    s = np.zeros((n * geo.cout, n // 2 * geo.cout), np.int8)
    eye = np.eye(geo.cout, dtype=np.int8)
    for x in range(n):
        s[x * geo.cout:(x + 1) * geo.cout, (x // 2) * geo.cout:(x // 2 + 1) * geo.cout] = eye
    return s


def _conv_kernel(x_ref, t_ref, thr_ref, *rest, geo: ConvGeometry, bm: int):
    if geo.pool:
        s_ref, o_ref = rest
    else:
        (o_ref,) = rest
    ho, cols = geo.ho, geo.block_cols
    thr = thr_ref[...]                                  # (1, block_cols)
    for grp in range(geo.blocks // geo.group):
        parts = []
        for b in range(geo.group):
            j = grp * geo.group + b
            lo = j * geo.bo * geo.cin if geo.sliding else 0
            acc = None
            for dy in range(geo.kh):                   # taps along y: leading dim
                xs = x_ref[dy:dy + ho, :, lo:lo + geo.window].reshape(ho * bm, geo.window)
                d = jnp.dot(xs, t_ref[dy, 0 if geo.sliding else j],
                            preferred_element_type=jnp.int32)
                acc = d if acc is None else acc + d
            parts.append((acc > thr).astype(jnp.int32))
        bits = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        n = geo.group * cols
        if geo.pool:
            rows = bits.reshape(ho // 2, 2, bm, n)
            rows = jnp.maximum(rows[:, 0], rows[:, 1]).reshape(ho // 2 * bm, n)
            pooled = jnp.dot(rows.astype(jnp.int8), s_ref[...],
                             preferred_element_type=jnp.int32) > 0
            n //= 2
            out = pooled.astype(jnp.int32).astype(jnp.int8).reshape(ho // 2, bm, n)
        else:
            out = bits.astype(jnp.int8).reshape(ho, bm, n)
        o_ref[:, :, grp * n:(grp + 1) * n] = out


@functools.partial(jax.jit, static_argnames=("geo", "bm", "interpret"))
def binary_conv(x: jnp.ndarray, taps: jnp.ndarray, thr: jnp.ndarray,
                pool: jnp.ndarray | None = None, *, geo: ConvGeometry,
                bm: int = ROW_TILE, interpret: bool | None = None) -> jnp.ndarray:
    """One conv layer: int8 rows (H, B, W*Cin) -> {0,1} int8 rows
    (H', B, W'*Cout), B a multiple of `bm`. `taps` is `banded_weights`,
    `thr` the int32 (1, block_cols) per-column threshold (the channel
    thresholds tiled over a block's positions), `pool` the
    `pool_matrix` when a 2x2 pool follows (else None)."""
    h, b, lanes = x.shape
    assert (h, lanes) == (geo.h, geo.w * geo.cin), (x.shape, geo)
    assert b % bm == 0 and bm % ROW_TILE == 0, (b, bm)
    assert (pool is not None) == geo.pool
    ho, wo, cout = geo.out_shape
    in_specs = [pl.BlockSpec((h, bm, lanes), lambda i: (0, i, 0)),
                pl.BlockSpec(taps.shape, lambda i: (0, 0, 0, 0)),
                pl.BlockSpec(thr.shape, lambda i: (0, 0))]
    args = [x, taps, thr]
    if pool is not None:
        in_specs.append(pl.BlockSpec(pool.shape, lambda i: (0, 0)))
        args.append(pool)
    return pl.pallas_call(
        functools.partial(_conv_kernel, geo=geo, bm=bm),
        grid=(b // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ho, bm, wo * cout), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((ho, b, wo * cout), jnp.int8),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(*args)
