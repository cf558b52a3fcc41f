"""Pallas TPU kernels: matmul against 2-bit (ternary) / 1-bit (binary) packed
weights, and fused stochastic quantize+pack.

This is the TPU-native translation of the paper's MAC-free ASIC engine
(DESIGN.md §2): the ±1/0 weights live PACKED in HBM (16x / 32x fewer weight
bytes than fp32), are decoded to bf16 inside VMEM by the VPU (one shift and
one mask put two weights' bit patterns into a word's two halves — no
cross-lane work since packing is along the contraction axis), and the MXU
consumes the decoded tile.  Decode-bound GEMV/GEMM arithmetic intensity
rises by the packing factor, which is exactly where the paper's "12x memory
bandwidth" claim lands on a TPU.

Tiling: the GEMV (`packed_gemv`, at most 8 rows) and the GEMM
(`packed_matmul`, more) grid over N tiles.  Each step decodes its (K, bn)
code tile once into a bfloat16 VMEM tile and contracts it in one dot
against every row; the activation, split once per call into exact
bfloat16 parts, is one block that stays in VMEM across the N tiles.  Only
the GEMM's edges add grid axes, chosen from M, K and N (`_gemm_plan`):
row tiles beyond 256 rows, innermost, reusing the first one's decode, and
K blocks, innermost, where all of K does not fit its VMEM budget.
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


# the GEMM's tiles: rows in one tile (more rows take tiles of this many),
# lanes of an N tile, and the VMEM its tiles are planned within.  The v5e
# has 128 MiB of VMEM; the plan's own count (`_gemm_vmem`) sets the
# compiler's limit per call
GEMM_ROWS = 256
GEMM_COLS = 512
GEMM_VMEM_BYTES = 48 * 2**20


def _up(a: int, m: int) -> int:
    return -(-a // m) * m


def _gemm_vmem(parts: int, bm: int, bn: int, bk: int, kp: int, ni: int,
               nk: int, group: int) -> int:
    """VMEM bytes of one plan of the GEMM: the activation block (one buffer
    when it is the whole operand, two otherwise), two code blocks, the
    decoded tile (all of K when row tiles reuse it), the dot's result, the
    accumulator, two output blocks, and the decode's word temporaries."""
    x = parts * bm * bk * 2 * (1 if ni == nk == 1 else 2)
    codes = bk // group * bn * 4
    w = (kp if ni > 1 else bk) * bn * 2
    acc = parts * bm * bn * 4 + (bm * bn * 4 if nk > 1 else 0)
    return x + 4 * codes + w + acc + 2 * bm * bn * 4


def _gemm_plan(m: int, kp: int, n: int, parts: int, group: int):
    """(mp, bm, bn, bk) of the GEMM, from its shapes alone: every row in one
    tile up to GEMM_ROWS (mp is m rounded up to 16, one bfloat16 sublane
    tile), the widest N tile up to GEMM_COLS, and all of K in one block
    unless that cannot fit GEMM_VMEM_BYTES; then the fewest K blocks that
    do, each whole 8-word tiles of codes."""
    mp = _up(m, 16)
    bm = min(mp, GEMM_ROWS)
    mp = _up(mp, bm)
    unit = 8 * group
    cols = [n] if n <= 128 else [c for c in (GEMM_COLS, 256, 128)
                                 if c <= _up(n, 128)]
    for nk in range(1, kp // unit + 1):
        if kp % (nk * unit):
            continue
        for bn in cols:
            if _gemm_vmem(parts, bm, bn, kp // nk, kp, mp // bm, nk,
                          group) <= GEMM_VMEM_BYTES:
                return mp, bm, bn, kp // nk
    raise ValueError(f"no GEMM tiles fit {GEMM_VMEM_BYTES} bytes of VMEM "
                     f"at {(m, kp, n)}")


def _matmul_kernel(x_ref, wp_ref, o_ref, w_ref, *acc_ref, parts: int,
                   keep: bool, mode: str):
    i, kk = pl.program_id(1), pl.program_id(2)
    rows2 = 2 * wp_ref.shape[0]
    bk = rows2 * group_of(mode) // 2
    off = pl.multiple_of(kk * bk, bk) if keep else 0

    @pl.when(i == 0)
    def _decode():
        words = wp_ref[...]                               # (bk/G, bn) uint32
        for p in range(group_of(mode) // 2):
            w_ref[pl.ds(off + p * rows2, rows2), :] = _pair_plane(words, p,
                                                                  mode)

    y = jnp.dot(x_ref[...], w_ref[pl.ds(off, bk), :],
                preferred_element_type=jnp.float32)      # (parts*bm, bn)
    bm = o_ref.shape[0]
    y = functools.reduce(jnp.add, [y[q * bm:(q + 1) * bm]
                                   for q in range(parts)])
    if not acc_ref:
        o_ref[...] = y * 0.5        # the decoded weights' factor of 2
        return
    acc, = acc_ref

    @pl.when(kk == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += y

    @pl.when(kk == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc[...] * 0.5


def packed_matmul(x: Array, wp: Array, k: int, *, mode: str,
                  interpret: bool | None = None) -> Array:
    """Prefill-shape matmul against packed codes: x (M, K), packed wp
    (K/G, N) -> (M, N) fp32 (unscaled), one launch named
    `<mode>_packed_matmul`.  Any M, K and N: padding is done here, of the
    activation only.

    The GEMV's body (`packed_gemv`) at many rows.  x is split once, outside
    the grid, into its three bfloat16 parts (`bf16_parts`; one part for
    bfloat16 x), stacked as rows of one operand in `pair_major` column
    order.  The grid runs over N tiles, and each step decodes its (K, bn)
    code tile once, a pair of code slots per shift and mask
    (`_pair_plane`), into a bfloat16 VMEM tile that one dot contracts
    against every row and part; the parts' float32 sums add up to the
    float32 result.  With every row in one tile and all of K in one block
    (`_gemm_plan`: every configuration's prefill at up to 256 rows but the
    widest K in float32), the activation's block never changes, so Pallas
    reads it from HBM once a call, and the codes once.  The last N tile
    may run past N: its columns past N decode whatever lies there, always
    to finite +-2/0, and their writes are dropped, so the codes are never
    copied to pad N.  Their K rows are not padded either: a code block
    past the codes' rows meets zero-padded activations.

    Beyond one row tile (above GEMM_ROWS rows) the rows run innermost, the
    first row tile decodes, and the rest reuse the decoded tile.  Where all
    of K would not fit the VMEM budget, K runs in blocks innermost into a
    float32 accumulator over all of a tile's rows; each code word is still
    decoded once."""
    group = group_of(mode)
    M = x.shape[0]
    R, N = wp.shape
    if k != x.shape[1] or k > R * group:
        raise ValueError(f"packed K mismatch: x {x.shape}, k={k}, codes "
                         f"cover {R}*{group}")
    kp = _up(R * group, 8 * group)
    f32 = x.dtype == jnp.float32
    parts = 3 if f32 else 1
    mp, bm, bn, bk = _gemm_plan(M, kp, N, parts, group)
    ni, nj, nk = mp // bm, pl.cdiv(N, bn), kp // bk
    interpret = dispatch.resolve_interpret(interpret)

    xp = jnp.pad(x, ((0, mp - M), (0, kp - k)))
    # each K block in its decoded tile's row order; each row tile's parts
    # as consecutive row groups
    xt = jnp.stack([pair_major(v.reshape(mp * nk, bk), group).reshape(mp, kp)
                    for v in (bf16_parts(xp) if f32
                              else [xp.astype(jnp.bfloat16)])])
    xt = xt.reshape(parts, ni, bm, kp).transpose(1, 0, 2, 3).reshape(
        ni * parts * bm, kp)
    if ni == nk == 1:       # the whole operand, read once: one buffer
        x_spec = pl.BlockSpec(xt.shape, lambda j, i, kk: (0, 0),
                              pipeline_mode=pl.Buffered(1))
    else:
        x_spec = pl.BlockSpec((parts * bm, bk), lambda j, i, kk: (i, kk))
    # row tiles past the first reuse the decoded tile: their code block
    # stays the last one fetched, so none is fetched again
    w_spec = pl.BlockSpec((bk // group, bn), lambda j, i, kk: (
        jnp.where(i == 0, kk, nk - 1), j))
    keep = ni > 1
    scratch = [pltpu.VMEM((kp if keep else bk, bn), jnp.bfloat16)]
    if nk > 1:
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))
    vmem = _gemm_vmem(parts, bm, bn, bk, kp, ni, nk, group)
    name = f"{mode}_packed_matmul"
    dispatch.count_launch(name)
    y = pl.pallas_call(
        functools.partial(_matmul_kernel, parts=parts, keep=keep, mode=mode),
        grid=(nj, ni, nk),
        in_specs=[x_spec, w_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, N), jnp.float32),
        scratch_shapes=scratch,
        # row tiles after the first read the tile the first one decoded
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 * 2**20, vmem * 5 // 4 + 4 * 2**20)),
        interpret=interpret,
        name=name,
    )(xt, wp)
    return y[:M]


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
