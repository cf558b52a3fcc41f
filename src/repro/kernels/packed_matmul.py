"""Pallas TPU kernels: matmul against 2-bit (ternary) / 1-bit (binary) packed
weights, and fused stochastic quantize+pack.

This is the TPU-native translation of the paper's MAC-free ASIC engine
(DESIGN.md §2): the ±1/0 weights live PACKED in HBM (16x / 32x fewer weight
bytes than fp32), are decoded to bf16 inside VMEM by the VPU (shift/and/
select — no cross-lane work since packing is along the contraction axis), and
the MXU consumes the decoded tile.  Decode-bound GEMV/GEMM arithmetic
intensity rises by the packing factor, which is exactly where the paper's
"12x memory bandwidth" claim lands on a TPU.

Tiling: grid (M/bm, N/bn, K/bk), K innermost so the fp32 VMEM accumulator
carries across the K loop; all dims MXU-aligned (multiples of 8/128).  The
packed operand's K axis is bk/GROUP uint32 rows per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quantize import BINARY_GROUP, TERNARY_GROUP
from repro.kernels import dispatch

Array = jax.Array


def _unpack_ternary_tile(packed: Array, bk: int) -> Array:
    """(bk/16, bn) uint32 -> (bk, bn) float32 in {-1, 0, +1}."""
    shifts = (2 * jnp.arange(TERNARY_GROUP, dtype=jnp.uint32))[None, :, None]
    codes = (packed[:, None, :] >> shifts) & jnp.uint32(3)
    vals = jnp.where(codes == 1, 1.0, jnp.where(codes == 3, -1.0, 0.0))
    return vals.reshape(bk, packed.shape[-1])


def _unpack_binary_tile(packed: Array, bk: int) -> Array:
    """(bk/32, bn) uint32 -> (bk, bn) float32 in {-1, +1}.  A select, not a
    uint32 -> float cast: Mosaic has no such cast."""
    shifts = jnp.arange(BINARY_GROUP, dtype=jnp.uint32)[None, :, None]
    bits = (packed[:, None, :] >> shifts) & jnp.uint32(1)
    vals = jnp.where(bits == 1, 1.0, -1.0)
    return vals.reshape(bk, packed.shape[-1])


def group_of(mode: str) -> int:
    return TERNARY_GROUP if mode == "ternary" else BINARY_GROUP


def slot_major(x: Array, group: int) -> Array:
    """x (B, K) -> (G, K/G, B): entry [j, r, b] is x[b, r*G + j], the
    activation that code slot j of packed row r selects.  Contiguous packing
    (core/quantize.py) puts weights r*G .. r*G+G-1 in word r; this layout
    hands the accumulation loop each slot's activations as one
    sublane-major plane."""
    b, k = x.shape
    return x.reshape(b, k // group, group).transpose(2, 1, 0)


def _slot_masks(packed: Array, j, mode: str):
    """(plus, minus) boolean masks of code slot `j` (traced int32) of every
    packed word.  Binary codes have no zero, so minus is `not plus` and is
    returned as None."""
    if mode == "ternary":
        code = (packed >> (j << 1).astype(jnp.uint32)) & jnp.uint32(3)
        return code == 1, code == 3
    bit = (packed >> j.astype(jnp.uint32)) & jnp.uint32(1)
    return bit == 1, None


def accumulate_planes(xs, packed: Array, *, mode: str) -> Array:
    """y = x @ unpack(packed) with ZERO multiplies — the paper's MAC-free
    inner loop (DESIGN.md §11).  xs: (G, R, B) slot-major activations (an
    array or a VMEM ref); packed: (R, N) uint32; returns (B, N) fp32.

    A loop over the G code slots: slot j's codes decode to (plus, minus)
    boolean masks over the (R, N) word plane, and each batch row adds
    `select(plus, x, select(minus, -x, 0))` of its activation column
    xs[j, :, b] broadcast across the N lanes — shift/and/compare/select/add
    only, so the ±1/0 weights never become floats and the jaxpr holds no
    `mul`/`dot_general` (`dispatch.assert_accumulation_only`).  Nothing
    larger than one (R, N) plane is live, and per row the partial sums fold
    into one (8, N) sublane tile, so VMEM stays small at any K.

    Binary pad safety: a ZERO pad code decodes to minus (−1), but pad
    activation lanes are zero-padded by every caller, so the pad adds -0.0.
    """
    group, (R, N) = group_of(mode), packed.shape
    B = xs.shape[-1]
    fold = 8 if R % 8 == 0 else R

    def body(j, acc):
        plus, minus = _slot_masks(packed, j, mode)
        xj = xs[j]                                  # (R, B)
        out = []
        for b in range(B):
            xc = xj[:, b:b + 1]                     # (R, 1) -> N lanes
            neg = -xc if minus is None else jnp.where(minus, -xc, 0.0)
            t = jnp.where(plus, xc, neg)
            out.append(acc[b] + jnp.sum(t.reshape(R // fold, fold, N), axis=0))
        return tuple(out)

    acc = jax.lax.fori_loop(
        0, group, body,
        tuple(jnp.zeros((fold, N), jnp.float32) for _ in range(B)))
    return jnp.concatenate([jnp.sum(a, axis=0, keepdims=True) for a in acc])


def accumulate_gemv(x: Array, packed: Array, *, mode: str) -> Array:
    """`accumulate_planes` on a plain (B, K) activation against contiguously
    packed (K/G, N) codes: y = x @ unpack(packed), (B, N) fp32."""
    xs = slot_major(x.astype(jnp.float32), group_of(mode))
    return accumulate_planes(xs, packed, mode=mode)


# rows of the MXU GEMV's activation operand: three bfloat16 parts of 8 rows,
# padded to two whole (16, 128) bfloat16 tiles
MXU_ROWS = 32


def bf16_parts(x: Array) -> list:
    """float32 x as three bfloat16 parts, each the remainder of the ones
    before; their sum is x exactly (8 significand bits a part), so their
    products with +-1/0 weights are exact and float32 sums of them give
    x @ w in float32.  Each part is cut by a bit mask, not rounded by a
    cast: outside a kernel the TPU compiler may keep a float32 value through
    a float32 -> bfloat16 -> float32 round trip (excess precision), and with
    casts the remainders came out zero on a v5e, as one bfloat16 pass."""
    parts = []
    for _ in range(3):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                          jnp.float32)
        parts.append(hi.astype(jnp.bfloat16))    # exact: 16 low bits zero
        x = x - hi                                # exact
    return parts


def pair_major(x: Array, group: int) -> Array:
    """x (B, K) -> (B, K) with columns in the row order of the GEMV's
    decoded tile: pair p (slots p and p + G/2) takes rows p*2R .. p*2R+2R-1,
    and row p*2R + 2r + h of it is the weight of code slot p + h*G/2 of
    packed row r, whose activation is x[:, r*G + h*G/2 + p]."""
    b, k = x.shape
    half = group // 2
    return x.reshape(b, k // group, 2, half).transpose(0, 3, 1, 2).reshape(b, k)


def _pair_plane(words: Array, p: int, mode: str) -> Array:
    """(R, bn) uint32 words -> (2R, bn) bfloat16 holding 2*w for code slots
    p (row 2r) and p + G/2 (row 2r + 1) of word r: +-2 or 0, exact.

    One shift and one mask decode two weights: the two slots' codes land on
    the top bits of the word's low and high 16-bit halves, which are then
    read as two bfloat16 numbers (2.0 is 0x4000, -2.0 is 0xC000: a single
    exponent bit and the sign).  Ternary code 1 (+1) becomes 0x4000, code 3
    (-1) 0xC000, code 0 zero; a binary bit of 1 becomes 0x4000, of 0 0xC000."""
    if mode == "ternary":
        t = (words << jnp.uint32(14 - 2 * p)) & jnp.uint32(0xC000C000)
    else:
        t = (((words << jnp.uint32(15 - p)) & jnp.uint32(0x80008000))
             ^ jnp.uint32(0xC000C000))
    return pltpu.bitcast(t, jnp.bfloat16)


def _gemv_kernel(xt_ref, wp_ref, o_ref, w_ref, *, mode: str):
    words = wp_ref[...]                                  # (R, bn) uint32
    rows2 = 2 * words.shape[0]
    for p in range(group_of(mode) // 2):
        w_ref[p * rows2:(p + 1) * rows2, :] = _pair_plane(words, p, mode)
    acc = jnp.dot(xt_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)    # (MXU_ROWS, bn)
    # the three parts' row groups, and the decoded weights' factor of 2
    o_ref[...] = (acc[0:8] + acc[8:16] + acc[16:24]) * 0.5


def packed_gemv(x: Array, wp: Array, k: int, *, mode: str,
                block_n: int = 128, interpret: bool | None = None) -> Array:
    """Decode-shape matmul against packed codes: x (M, K) with M <= 8 rows,
    packed wp (K/G, N) -> (M, N) fp32, one launch named
    `<mode>_packed_gemv`, grid over N tiles.  K must cover whole 8-row tiles
    of packed words (`ops.packed_matmul` pads it).

    Inside each N tile the codes decode once, whatever the row count: each
    pair of code planes, with one shift and one mask, to exact +-2/0
    bfloat16 (`_pair_plane`), into a (K, bn) VMEM tile that one dot
    contracts on the MXU.  x, padded to 8 rows, goes in as its three
    bfloat16 parts (`bf16_parts`) stacked as 24 rows of one operand in
    `pair_major` column order, so every product is exact and the tile
    passes the MXU once; the float32 sums of the three row groups add up to
    the float32 result.  The paper's select-and-add loop
    (`accumulate_planes`) pays its vector work once per row, padding rows
    included, so it is slower here even at one row (DESIGN.md §11); the
    fused decode tick keeps it.  The decoded tile holds all of K: the
    widest contraction of the repo's configurations, K = 28,672, compiles
    for a v5e (tests/test_tpu_compile.py)."""
    group = group_of(mode)
    m, K = x.shape
    N = wp.shape[1]
    if m > 8:
        raise ValueError(f"packed_gemv takes at most 8 rows, got {m}")
    if K != k or wp.shape[0] * group != K:
        raise ValueError(f"packed K mismatch: {wp.shape[0]}*{group} != {K}")
    if N % block_n:
        raise ValueError(f"N={N} must be a multiple of block_n={block_n}")
    if wp.shape[0] % 8:
        raise ValueError(f"packed rows {wp.shape[0]} must be a multiple "
                         "of 8 (whole bfloat16 tiles once decoded)")
    interpret = dispatch.resolve_interpret(interpret)
    x8 = jnp.pad(x.astype(jnp.float32), ((0, 8 - m), (0, 0)))
    xt = pair_major(jnp.concatenate(bf16_parts(x8)), group)    # (24, K)
    xt = jnp.pad(xt, ((0, MXU_ROWS - xt.shape[0]), (0, 0)))
    name = f"{mode}_packed_gemv"
    dispatch.count_launch(name)
    y = pl.pallas_call(
        functools.partial(_gemv_kernel, mode=mode),
        grid=(N // block_n,),
        in_specs=[pl.BlockSpec(xt.shape, lambda j: (0, 0)),
                  pl.BlockSpec((K // group, block_n), lambda j: (0, j))],
        out_specs=pl.BlockSpec((8, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((8, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, block_n), jnp.bfloat16)],
        interpret=interpret,
        name=name,
    )(xt, wp)
    return y[:m]


def _matmul_kernel(x_ref, wp_ref, o_ref, acc_ref, *, bk: int, mode: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    unpack = _unpack_ternary_tile if mode == "ternary" else _unpack_binary_tile
    w = unpack(wp_ref[...], bk).astype(jnp.bfloat16)   # +-1/0: exact
    x = x_ref[...]
    # float32 activations in float32, where one bfloat16 pass would round x
    # to 8 significand bits
    parts = (bf16_parts(x) if x.dtype == jnp.float32
             else [x.astype(jnp.bfloat16)])
    for xp in parts:
        acc_ref[...] += jnp.dot(xp, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def packed_matmul(x: Array, wp: Array, k: int, *, mode: str,
                  block: tuple[int, int, int] = (128, 128, 256),
                  interpret: bool | None = None) -> Array:
    """x: (M, K) fp; wp: (K/G, N) uint32 packed -> (M, N) fp32 (unscaled).

    M, N, K must already be multiples of the block dims (ops.py pads).
    """
    group = group_of(mode)
    M, K = x.shape
    N = wp.shape[1]
    if K != k or wp.shape[0] * group != K:
        raise ValueError(f"packed K mismatch: {wp.shape[0]}*{group} != {K}")
    bm, bn, bk = block
    if M % bm or N % bn or K % bk or bk % group:
        raise ValueError(f"blocks {block} must divide {(M, N, K)} (bk % {group} == 0)")
    interpret = dispatch.resolve_interpret(interpret)

    kernel = functools.partial(_matmul_kernel, bk=bk, mode=mode)
    dispatch.count_launch(f"{mode}_packed_matmul")
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // group, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name=f"{mode}_packed_matmul",
    )(x, wp)


# ---------------------------------------------------------------------------
# fused stochastic quantize + pack
# ---------------------------------------------------------------------------


def _qpack_kernel(w_ref, u_ref, alpha_ref, o_ref, *, mode: str):
    a = alpha_ref[0, 0]
    wn = jnp.clip(w_ref[...] / a, -1.0, 1.0)
    bk, bn = wn.shape
    if mode == "ternary":
        nz = u_ref[...] < jnp.abs(wn)
        t = jnp.where(nz, jnp.sign(wn), 0.0)
        codes = jnp.where(t > 0, 1, jnp.where(t < 0, 3, 0)).astype(jnp.uint32)
        g = TERNARY_GROUP
        shifts = (2 * jnp.arange(g, dtype=jnp.uint32))[None, :, None]
    else:
        p_one = (wn + 1.0) * 0.5
        codes = (u_ref[...] < p_one).astype(jnp.uint32)
        g = BINARY_GROUP
        shifts = jnp.arange(g, dtype=jnp.uint32)[None, :, None]
    c = codes.reshape(bk // g, g, bn)
    o_ref[...] = jnp.sum(c << shifts, axis=1, dtype=jnp.uint32)


def quantize_pack(w: Array, u: Array, alpha, *, mode: str,
                  block: tuple[int, int] = (256, 256),
                  interpret: bool | None = None) -> Array:
    """Fused Eq.(4-6) sampling + bit-packing.  w, u: (K, N); returns packed
    uint32 (K/G, N).  Noise is an explicit operand (Pallas-portable PRNG)."""
    group = TERNARY_GROUP if mode == "ternary" else BINARY_GROUP
    K, N = w.shape
    bk, bn = block
    if K % bk or N % bn or bk % group:
        raise ValueError(f"blocks {block} must divide {(K, N)} (bk % {group} == 0)")
    interpret = dispatch.resolve_interpret(interpret)
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1, 1)

    kernel = functools.partial(_qpack_kernel, mode=mode)
    dispatch.count_launch(f"{mode}_quantize_pack")
    return pl.pallas_call(
        kernel,
        grid=(K // bk, N // bn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bk // group, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K // group, N), jnp.uint32),
        interpret=interpret,
        name=f"{mode}_quantize_pack",
    )(w, u, alpha)
