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
    hands the GEMV each slot's activations as one sublane-major plane."""
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


def _gemv_kernel(xs_ref, wp_ref, o_ref, *, mode: str):
    o_ref[...] = accumulate_planes(xs_ref, wp_ref[...], mode=mode)


def packed_gemv(x: Array, wp: Array, k: int, *, mode: str,
                block_n: int = 128, interpret: bool | None = None) -> Array:
    """Accumulation-only decode-shape matmul: x (Bp, K) with Bp <= 8, packed
    wp (K/G, N) -> (Bp, N) fp32, one launch, grid over N tiles.

    This is the mul-free sibling of `packed_matmul`: where the MXU path
    decodes codes to ±1 floats and feeds a dense dot (right for prefill
    GEMM, M large), this kernel selects/accumulates activations through the
    code masks — the arithmetic the paper's ASIC does.  `ops.packed_matmul`
    routes M <= 8 here and larger M to the MXU path.  x goes in slot-major
    (`slot_major`, an XLA transpose of a few KB), so the kernel reads each
    slot's activations as a sublane plane."""
    group = group_of(mode)
    bp, K = x.shape
    N = wp.shape[1]
    if K != k or wp.shape[0] * group != K:
        raise ValueError(f"packed K mismatch: {wp.shape[0]}*{group} != {K}")
    if N % block_n:
        raise ValueError(f"N={N} must be a multiple of block_n={block_n}")
    interpret = dispatch.resolve_interpret(interpret)
    xs = slot_major(x.astype(jnp.float32), group)

    kernel = functools.partial(_gemv_kernel, mode=mode)
    dispatch.count_launch(f"{mode}_packed_gemv")
    return pl.pallas_call(
        kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec(xs.shape, lambda j: (0, 0, 0)),
            pl.BlockSpec((K // group, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bp, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((bp, N), jnp.float32),
        interpret=interpret,
        name=f"{mode}_packed_gemv",
    )(xs, wp)


def _matmul_kernel(x_ref, wp_ref, o_ref, acc_ref, *, bk: int, mode: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    unpack = _unpack_ternary_tile if mode == "ternary" else _unpack_binary_tile
    w = unpack(wp_ref[...], bk).astype(jnp.bfloat16)   # +-1/0: exact
    x = x_ref[...]
    if x.dtype == jnp.float32:
        # float32 activations in float32: x is the sum of three bfloat16
        # parts (8 significand bits each), every product with a +-1/0
        # weight is exact, and the sums accumulate in float32 — where one
        # bfloat16 pass would round x to 8 bits
        parts = []
        for _ in range(3):
            hi = x.astype(jnp.bfloat16)
            parts.append(hi)
            x = x - hi.astype(jnp.float32)
    else:
        parts = [x.astype(jnp.bfloat16)]
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
