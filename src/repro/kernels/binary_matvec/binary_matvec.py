"""Pallas TPU kernel: multiplication-free binary-activation matmul.

TPU adaptation of the paper's L5 "selected addends" rewrite: with
activations x in {0,1}, a dense layer is a *masked column sum*

    y[b, :] = sum_{k : x[b,k] == 1} w[k, :]

i.e. adds only — the select/accumulate runs on the VPU; no multiplier
(MXU) is engaged, mirroring the paper's removal of multiplier logic.

Three datapaths, in increasing bit-economy:
  * int8 activations (B, K)           — `binary_matmul_kernel`
  * bitpacked uint32 (B, K//32)       — `binary_matmul_packed_kernel`
    (32 activations per word: 8x less HBM->VMEM traffic than int8; the
    TPU analogue of the paper's single-bit wires — but the weights
    still travel as full int32 and each word is unpacked in-register
    into a (bm, 32, bn) select)
  * fully bit-packed                  — `binary_matmul_planes_kernel`
    BOTH operands travel as bits: the int32 weight matrix is decomposed
    into signed bit-planes w = sum_b 2^b (pos_b - neg_b), each plane
    packed 32-lanes-per-uint32 along fan_in, and each output tile is

        y = sum_b 2^b (popcount(x & pos_b) - popcount(x & neg_b))

    — the XNOR/AND+popcount form of the BNN-on-FPGA line of work
    (Ertörer & Ünsalan). No in-register unpack: the inner reduction is
    over uint32 *words* (32x fewer elements than the packed kernel's
    bit-level select), and a P-plane layer moves 2P bits of weight per
    addend instead of 32.

Tiling: grid (B/bm, N/bn, K/bk) with the K axis innermost (sequential on
TPU), accumulating into the output block, which stays resident in VMEM
across the K sweep (revisited blocks are not re-fetched). Block sizes
are keyword knobs on every entry point so `repro.netgen.tune` can
search them per workload instead of trusting the defaults. On a TPU a
block's last dim must be the whole padded dim or a multiple of 128
lanes (`repro.netgen.analysis.tile_legality` rejects other tiles), so
the word-tile default bkw=128 takes the whole K of any fan-in up to
4096 bits.

A fourth datapath, `binary_forward_planes`, fuses an ENTIRE planes-form
network — every layer's bit-plane weights resident in VMEM at once —
into one persistent launch: binarize+pack on entry, per-layer popcount
accumulate, strict step + repack *in-kernel* between layers (the
inter-layer activations never touch HBM), argmax fused at the end. The
grid runs over batch tiles only (and a leading model axis when the
input is a stacked (M, B, K) block), so Pallas's grid pipeline
double-buffers the input DMA while weights stay put.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


# --------------------------------------------------------------------------
# int8-activation kernel
# --------------------------------------------------------------------------

def _binary_matmul_kernel(x_ref, w_ref, o_ref):
    """x: (bm, bk) int8 {0,1}; w: (bk, bn) int32; o: (bm, bn) int32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    w = w_ref[...]
    # Masked accumulate: select rows of w where the activation bit is set,
    # then reduce over k inside the tile. (bm, bk, bn) never materializes in
    # HBM — it is a VPU select feeding an add-reduce within VMEM.
    sel = jnp.where(x.astype(jnp.int32)[:, :, None] != 0, w[None, :, :], 0)
    o_ref[...] += jnp.sum(sel, axis=1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def binary_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = x @ w with x in {0,1}. Pads to tile multiples; returns int32 (B, N)."""
    B, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bn, bk = min(bm, _rup(B)), min(bn, _rup(N)), min(bk, _rup(K))
    Bp, Np, Kp = _pad_to(B, bm), _pad_to(N, bn), _pad_to(K, bk)
    xp = jnp.zeros((Bp, Kp), jnp.int8).at[:B, :K].set(x.astype(jnp.int8))
    wp = jnp.zeros((Kp, Np), jnp.int32).at[:K, :N].set(w.astype(jnp.int32))

    out = pl.pallas_call(
        _binary_matmul_kernel,
        grid=(Bp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(xp, wp)
    return out[:B, :N]


# --------------------------------------------------------------------------
# bitpacked kernel: 32 activations per uint32 word
# --------------------------------------------------------------------------

def _binary_matmul_packed_kernel(xp_ref, w_ref, o_ref, *, bkw: int):
    """xp: (bm, bkw) uint32; w: (bkw, 32, bn) int32, row j of word k
    holding the weights of activation bit j; o: (bm, bn) int32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xp = jax.lax.bitcast_convert_type(xp_ref[...], jnp.int32)  # (bm, bkw)
    lane = jax.lax.broadcasted_iota(jnp.int32, xp.shape, 1)
    shifts = jnp.arange(32, dtype=jnp.int32)

    # One word per step: Mosaic cannot merge (bkw, 32) into one lane
    # axis, so the select runs per word over its 32 weight rows. The
    # word is picked by a masked lane sum (one nonzero term), which
    # takes a loop index where a dynamic lane slice would not.
    def word(c, acc):
        col = jnp.sum(jnp.where(lane == c, xp, 0), axis=1, keepdims=True)
        bits = (col >> shifts[None, :]) & 1             # (bm, 32)
        sel = jnp.where(bits[:, :, None] != 0, w_ref[c][None], 0)
        return acc + jnp.sum(sel, axis=1)

    acc = jax.lax.fori_loop(0, bkw, word, jnp.zeros(o_ref.shape, jnp.int32))
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkw", "interpret"))
def binary_matmul_packed(
    xp: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bkw: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = unpack(xp) @ w. xp: uint32 (B, K//32); w: (K, N) int32."""
    B, KW = xp.shape
    K, N = w.shape
    assert KW * 32 == K, (xp.shape, w.shape)
    bm = min(bm, _rup(B))
    bn = min(bn, _rup(N))
    bkw = min(bkw, KW)
    Bp, Np, KWp = _pad_to(B, bm), _pad_to(N, bn), _pad_to(KW, bkw)
    xpp = jnp.zeros((Bp, KWp), jnp.uint32).at[:B, :KW].set(xp)
    wp = jnp.zeros((KWp * 32, Np), jnp.int32).at[:K, :N].set(w.astype(jnp.int32))
    wp = wp.reshape(KWp, 32, Np)

    out = pl.pallas_call(
        functools.partial(_binary_matmul_packed_kernel, bkw=bkw),
        grid=(Bp // bm, Np // bn, KWp // bkw),
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkw, 32, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(xpp, wp)
    return out[:B, :N]


# --------------------------------------------------------------------------
# bit-plane kernel: both operands packed, popcount accumulation
# --------------------------------------------------------------------------

def _binary_matmul_planes_kernel(xp_ref, pos_ref, neg_ref, o_ref, *,
                                 planes: int):
    """xp: (bm, bkw) uint32; pos/neg: (P, bkw, bn) uint32 bit-planes;
    o: (bm, bn) int32. Accumulates sum_b 2^b (popcount(x & pos_b) -
    popcount(x & neg_b)) over the word tile — the inner loop runs on
    words, never unpacking activations or weights to individual bits."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = xp_ref[...]                        # (bm, bkw) uint32
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for b in range(planes):                # static unroll: P is tiny
        pos = pos_ref[b]                   # (bkw, bn) uint32
        neg = neg_ref[b]
        cp = jax.lax.population_count(x[:, :, None] & pos[None, :, :])
        cn = jax.lax.population_count(x[:, :, None] & neg[None, :, :])
        d = jnp.sum(cp.astype(jnp.int32) - cn.astype(jnp.int32), axis=1)
        acc = acc + (d << b)
    o_ref[...] += acc


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "interpret"))
def binary_matmul_planes(
    xp: jnp.ndarray,
    pos: jnp.ndarray,
    neg: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bkw: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = unpack(xp) @ w for w = sum_b 2^b (unpack(pos_b) - unpack(neg_b)).

    xp: uint32 (B, KW); pos/neg: uint32 (P, KW, N) packed bit-planes
    (see `repro.netgen.plan.decompose_planes`). Returns int32 (B, N).
    Zero-padding any operand to tile multiples is exact: a zero word
    contributes zero popcount.
    """
    B, KW = xp.shape
    P, KW2, N = pos.shape
    assert KW == KW2 and pos.shape == neg.shape, (
        xp.shape, pos.shape, neg.shape)
    bm = min(bm, _rup(B))
    bn = min(bn, _rup(N))
    bkw = min(bkw, max(KW, 1))
    Bp, Np, KWp = _pad_to(B, bm), _pad_to(N, bn), _pad_to(KW, bkw)
    xpp = jnp.zeros((Bp, KWp), jnp.uint32).at[:B, :KW].set(xp)
    posp = jnp.zeros((P, KWp, Np), jnp.uint32).at[:, :KW, :N].set(pos)
    negp = jnp.zeros((P, KWp, Np), jnp.uint32).at[:, :KW, :N].set(neg)

    out = pl.pallas_call(
        functools.partial(_binary_matmul_planes_kernel, planes=P),
        grid=(Bp // bm, Np // bn, KWp // bkw),
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda i, j, k: (i, k)),
            pl.BlockSpec((P, bkw, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((P, bkw, bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(xpp, posp, negp)
    return out[:B, :N]


# --------------------------------------------------------------------------
# whole-net megakernel: every layer fused into one persistent launch
# --------------------------------------------------------------------------

def _pack_bits_block(bits: jnp.ndarray, words: int) -> jnp.ndarray:
    """In-kernel repack: bool (bm, n) -> uint32 words (bm, words),
    zero-padding n up to words*32 (strict step: padding bits are 0).

    Mosaic cannot split the lane axis into (words, 32), so the packing
    runs on the MXU: bit j of the row lands in word j // 32 through a
    (n, words) matrix of powers of two, one matrix per 16-bit half. Each
    half-word is a sum of distinct powers below 2^16, exact in bf16
    operands with float32 accumulation."""
    n = bits.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, words), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, words), 1)
    off = row - col * 32                  # bit position inside word col
    x = bits.astype(jnp.float32).astype(jnp.bfloat16)

    def half(lo: int) -> jnp.ndarray:
        inside = (off >= lo) & (off < lo + 16)
        weight = jnp.where(inside, 1 << jnp.clip(off - lo, 0, 15), 0)
        m = weight.astype(jnp.float32).astype(jnp.bfloat16)
        return jnp.dot(x, m, preferred_element_type=jnp.float32
                       ).astype(jnp.int32)

    return jax.lax.bitcast_convert_type(
        (half(16) << 16) | half(0), jnp.uint32)


def argmax_lanes(scores: jnp.ndarray) -> jnp.ndarray:
    """int32 (bm, n) -> (bm, 1) index of the first maximum, as
    `jnp.argmax` picks it. Mosaic lowers argmax for float32 only, and a
    float cast is inexact past 2^24, so take the max and then the least
    index that attains it."""
    best = jnp.max(scores, axis=-1, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return jnp.min(jnp.where(scores == best, idx, scores.shape[-1]),
                   axis=-1, keepdims=True)


def _forward_planes_kernel(x_ref, *refs, threshold: int, layers, n_classes: int,
                           bkw, stacked: bool, unit_thresholds: bool = False):
    """One batch tile through the whole net. x: (bm, K) raw uint8 (leading
    model axis of size 1 when stacked); per layer l, refs hold pos_l then
    neg_l uint32 (P_l, W_l, N_l) bit-planes, fully resident; with
    `unit_thresholds`, then one int32 (1, N_l) threshold row per layer;
    o: (bm, 1) int32 predicted class (a column: Mosaic refuses a rank-1
    block under 128 rows). Activations live in registers/VMEM for the
    whole sweep — the only HBM traffic per grid step is the input tile
    and the predictions."""
    o_ref = refs[-1]
    plane_refs = refs[:2 * len(layers)]
    thr_refs = refs[2 * len(layers):-1] if unit_thresholds else None
    x = x_ref[...]
    if stacked:
        x = x[0]
    a = _pack_bits_block(x.astype(jnp.int32) > threshold, layers[0][1])
    acc = None
    for li, (P, W, N, out_words) in enumerate(layers):
        pos = plane_refs[2 * li][...]
        neg = plane_refs[2 * li + 1][...]
        if stacked:
            pos, neg = pos[0], neg[0]
        acc = jnp.zeros((a.shape[0], N), jnp.int32)
        ck = min(bkw, W) if bkw else W
        for c in range(0, W, ck):       # static lane tiling over words
            xw = a[:, c:c + ck]
            pw = pos[:, c:c + ck]
            nw = neg[:, c:c + ck]
            for b in range(P):          # static unroll: P is tiny
                cp = jax.lax.population_count(xw[:, :, None] & pw[b][None])
                cn = jax.lax.population_count(xw[:, :, None] & nw[b][None])
                d = jnp.sum(cp.astype(jnp.int32) - cn.astype(jnp.int32),
                            axis=1)
                acc = acc + (d << b)
        if thr_refs is not None:        # step at acc > t; scores acc - t
            t = thr_refs[li][...]
            if out_words is not None:
                a = _pack_bits_block(acc > t, out_words)
            else:
                acc = acc - t
        elif out_words is not None:     # strict step + repack, in-register
            a = _pack_bits_block(acc > 0, out_words)
    # Slice to the real class count before argmax: a zero-padded class
    # column must never win when every real score is negative.
    out = argmax_lanes(acc[:, :n_classes])           # (bm, 1)
    o_ref[...] = out[None] if stacked else out


@functools.partial(
    jax.jit, static_argnames=("threshold", "n_classes", "bm", "bkw",
                              "interpret"))
def binary_forward_planes(
    x: jnp.ndarray,
    *planes: jnp.ndarray,
    threshold: int,
    n_classes: int,
    bm: int = 32,
    bkw: int | None = 8,
    interpret: bool | None = None,
    thresholds: tuple | None = None,
) -> jnp.ndarray:
    """Whole-net forward in ONE pallas_call: raw uint8 images -> class ids.

    x: uint8 (B, K), or (M, B, K) for a stacked M-model plan. `planes`
    interleaves pos_0, neg_0, pos_1, neg_1, ... — uint32
    (P_l, W_l, N_l) packed bit-planes per layer ((M, P_l, W_l, N_l)
    when stacked), as produced by `ExecutionPlan.megakernel_view()`:
    each hidden fan_out is pre-padded so N_l == W_{l+1} * 32 and the
    in-kernel repack needs no bit shuffling. Returns int32 (B,) /
    (M, B).

    Grid is (B/bm,) (stacked: (M, B/bm), batch innermost so one model's
    weights stay resident across its batch sweep); the grid pipeline
    double-buffers the input-tile DMA against compute. `bkw` chunks the
    word axis of each popcount (bounding the (bm, ck, N) intermediate);
    None means whole-width.

    `thresholds` (single net only): one int32 (1, N_l) row per layer;
    hidden layers then step at `acc > t` and the argmax ranks `acc - t`.
    None (every threshold 0) adds no operand: the kernel is the one a
    net without thresholds always ran.
    """
    assert planes and len(planes) % 2 == 0, len(planes)
    stacked = x.ndim == 3
    if stacked:
        M, B, K = x.shape
    else:
        B, K = x.shape
    pairs = list(zip(planes[0::2], planes[1::2]))
    layers = []
    for li, (pos, neg) in enumerate(pairs):
        assert pos.shape == neg.shape, (li, pos.shape, neg.shape)
        assert pos.ndim == (4 if stacked else 3), (li, pos.shape)
        P, W, N = pos.shape[-3:]
        if li + 1 < len(pairs):
            out_words = pairs[li + 1][0].shape[-2]
            assert N == out_words * 32, (li, N, out_words)
        else:
            out_words = None
            assert 1 <= n_classes <= N, (n_classes, N)
        layers.append((P, W, N, out_words))
    assert layers[0][1] * 32 >= K, (layers[0], K)
    bm = min(bm, _rup(B))
    Bp = _pad_to(B, bm)
    extra = {}
    if thresholds is not None:
        assert not stacked, "per-unit thresholds serve a single net"
        assert len(thresholds) == len(layers), (len(thresholds), len(layers))
        extra["unit_thresholds"] = True
    kern = functools.partial(
        _forward_planes_kernel, threshold=threshold, layers=tuple(layers),
        n_classes=n_classes, bkw=bkw, stacked=stacked, **extra)
    if stacked:
        xp = jnp.zeros((M, Bp, K), jnp.uint8).at[:, :B].set(
            x.astype(jnp.uint8))
        in_specs = [pl.BlockSpec((1, bm, K), lambda m, i: (m, i, 0))]
        for P, W, N, _ in layers:
            spec = pl.BlockSpec((1, P, W, N), lambda m, i: (m, 0, 0, 0))
            in_specs += [spec, spec]
        out = pl.pallas_call(
            kern,
            grid=(M, Bp // bm),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bm, 1), lambda m, i: (m, i, 0)),
            out_shape=jax.ShapeDtypeStruct((M, Bp, 1), jnp.int32),
            interpret=resolve_interpret(interpret),
        )(xp, *planes)
        return out[:, :B, 0]
    xp = jnp.zeros((Bp, K), jnp.uint8).at[:B].set(x.astype(jnp.uint8))
    in_specs = [pl.BlockSpec((bm, K), lambda i: (i, 0))]
    for P, W, N, _ in layers:
        spec = pl.BlockSpec((P, W, N), lambda i: (0, 0, 0))
        in_specs += [spec, spec]
    thr_args = ()
    if thresholds is not None:
        thr_args = tuple(jnp.asarray(t, jnp.int32) for t in thresholds)
        in_specs += [pl.BlockSpec(t.shape, lambda i: (0, 0)) for t in thr_args]
    out = pl.pallas_call(
        kern,
        grid=(Bp // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(xp, *planes, *thr_args)
    return out[:B, 0]


def _rup(x: int, m: int = 8) -> int:
    """Round up to a small hardware-friendly multiple for tiny dims."""
    return max(m, ((x + m - 1) // m) * m)


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
