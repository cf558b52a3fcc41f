"""Whole-tick fused Pallas kernel for BN-LSTM / BN-GRU serving (DESIGN.md
§11).

One batched decode tick — ALL layers, the logits head, and greedy argmax —
is ONE Pallas launch.  Unfused, a tick is ~6 ops per layer plus the head:
every one round-trips the tiny (B, H) activations through HBM, and at
decode the GEMVs are pure memory traffic, so launch overhead and HBM hops
dominate.  This kernel keeps h and c for every layer in VMEM across the
whole tick:

  * the h-side GEMV per layer runs ACCUMULATION-ONLY against gate-aligned
    packed codes (`packed_matmul.accumulate_planes`: codes decode to boolean
    plus/minus masks, activations are selected and summed — zero multiplies
    on the weight path, asserted statically in tier-1),
  * the per-column frozen-BN affine (scale folds the QTensor alpha) and the
    gate nonlinearities + cell update (LSTM or GRU) follow in-register,
  * layers >= 1 compute their input-side pre-activation in-kernel, the same
    accumulation-only GEMV against the stacked x-side codes (scale folds
    alpha, shift folds the BN shift AND the bias); layer 0's token gather
    happens outside (it is an XLA gather, not a launch),
  * the `live` mask freezes dead continuous-batching rows in-kernel — a
    select, not a lerp, so dead-row garbage (possibly non-finite) never
    propagates,
  * optionally (static `with_head`, on when the padded head fits VMEM) the
    fp logits head and a greedy argmax run in the same launch.

Everything arrives padded from `ops.fused_decode_tick`: B to a sublane
multiple, H per gate to the 128-lane tile, codes' K rows to Kc/GROUP.  Pad
lanes carry zero activations, zero affine scale/shift and zero bias, so
pad h/c stay exactly 0.0 across layers (binary's pad-code-decodes-to-−1
quirk contributes select(minus, 0, 0) = 0) and pad logits columns sit at
finfo.min via the padded bias, below any real logit the argmax could pick.

Tiling (so VMEM holds one column tile of codes, not whole (Hp, Hp) gate
matrices): the launch has a grid over (layer, gate column tile), run in
order.  Step (l, j) computes columns j*128 .. j*128+127 of every gate of
layer l; Pallas streams that tile's codes from HBM while the previous step
computes.  The full h of layer l (the GEMV input) comes in whole, h_new of
layer l collects tile by tile in scratch, and the next layer's input-side
GEMV reads it from there.  The codes are STRIDED-packed (`ops.
prepare_gate_codes`): slot j of word r holds row j*R + r, so slot j's
activations are rows j*R .. j*R+R-1 of h transposed — one aligned sublane
slice, no gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import dispatch
from repro.kernels.packed_matmul import accumulate_planes, group_of

Array = jax.Array

BN_TILE = 128  # lane tile the gate width is padded to


def _to_slot_major(xs_ref, h: Array) -> None:
    """Store h (Bp, Hp) into xs_ref (G, R, Bp) as strided-packing planes:
    xs[j, r, b] = h[b, j*R + r], zero past Hp."""
    G, R, bp = xs_ref.shape
    ht = h.T
    if G * R > ht.shape[0]:
        ht = jnp.concatenate(
            [ht, jnp.zeros((G * R - ht.shape[0], bp), ht.dtype)])
    xs_ref[...] = ht.reshape(G, R, bp)


def _tick_kernel(ax0_ref, h_ref, ht_ref, c_ref, live_ref, ch_ref, cx_ref,
                 sh_ref, th_ref, sx_ref, tx_ref, sc_ref, tc_ref, *refs,
                 cell: str, mode: str, n_layers: int, n_gates: int,
                 n_tiles: int, with_head: bool):
    if with_head:
        ws_ref, bs_ref, h_out, c_out, lg_out, tok_out, *scratch = refs
    else:
        h_out, c_out, *scratch = refs
    xs_h, xs_x, hn_ref, ax_ref = scratch
    l, j = pl.program_id(0), pl.program_id(1)
    gemv = functools.partial(accumulate_planes, mode=mode)
    full_h = lambda: jnp.concatenate([hn_ref[t] for t in range(n_tiles)],
                                     axis=1)

    @pl.when(j == 0)
    def _load_inputs():
        _to_slot_major(xs_h, h_ref[0])
        if n_layers > 1:
            @pl.when(l > 0)
            def _():  # layer l-1's h_new, complete since its last tile
                _to_slot_major(xs_x, full_h())

    if n_layers > 1:
        @pl.when(l == 0)
        def _():
            ax_ref[...] = ax0_ref[...]

        @pl.when(l > 0)
        def _():
            # input-side preact, in-kernel: scale folds the x-side alpha,
            # shift folds BN shift + bias
            for i in range(n_gates):
                ax_ref[i] = (gemv(xs_x, cx_ref[0, i]) * sx_ref[0, i:i + 1, :]
                             + tx_ref[0, i:i + 1, :])
        ax = ax_ref
    else:
        ax = ax0_ref

    h_prev = ht_ref[0]           # (Bp, tn) this tile of h
    c_prev = c_ref[0]
    live = live_ref[...] > 0     # (Bp, tn)
    # accumulation-only h-side GEMV per gate; the BN scale below folds the
    # QTensor alpha, so the codes stay raw ±1/0 masks
    ah = [gemv(xs_h, ch_ref[0, i]) for i in range(n_gates)]
    if cell == "lstm":
        f, i_, o, g = [ah[i] * sh_ref[0, i:i + 1, :] + th_ref[0, i:i + 1, :]
                       + ax[i] for i in range(4)]
        c_new = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i_) * jnp.tanh(g)
        cn = c_new * sc_ref[0] + tc_ref[0]  # cell norm (1s/0s when off)
        # dead slots keep h/c bit-for-bit: a select, not a lerp —
        # dead-row garbage may be non-finite and 0*inf=NaN
        h_new = jnp.where(live, jax.nn.sigmoid(o) * jnp.tanh(cn), h_prev)
        c_sel = jnp.where(live, c_new, c_prev)
    else:
        # the h-side BN shift is NOT folded into ax: r gates the whole
        # normalized ah_g term (core/bnlstm._gru_step)
        ahn = [ah[i] * sh_ref[0, i:i + 1, :] + th_ref[0, i:i + 1, :]
               for i in range(3)]
        r = jax.nn.sigmoid(ax[0] + ahn[0])
        z = jax.nn.sigmoid(ax[1] + ahn[1])
        g = jnp.tanh(ax[2] + r * ahn[2])
        h_new = jnp.where(live, (1.0 - z) * h_prev + z * g, h_prev)
        c_sel = c_prev  # GRU carries no cell
    h_out[0] = h_new
    c_out[0] = c_sel
    hn_ref[j] = h_new

    if with_head:
        @pl.when((l == n_layers - 1) & (j == n_tiles - 1))
        def _head():
            # fp head: multiplies here consume the fused tick's OUTPUT
            # activations against the fp head weight — the mul-free claim
            # is about the packed weight path, which ended at h_new
            lg = jnp.dot(full_h(), ws_ref[...],
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32) + bs_ref[...]
            lg_out[...] = lg
            vp = lg.shape[-1]
            mx = jnp.max(lg, axis=-1, keepdims=True)
            col = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
            idx = jnp.min(jnp.where(lg == mx, col, vp), axis=-1,
                          keepdims=True)
            tok_out[...] = jnp.broadcast_to(idx, tok_out.shape)


def fused_tick(ax0: Array, h: Array, c: Array, live: Array, codes_h: Array,
               codes_x: Array, scale_h: Array, shift_h: Array,
               scale_x: Array, shift_x: Array, scale_c: Array,
               shift_c: Array, ws, bs, *, cell: str, mode: str,
               interpret: bool | None = None):
    """Padded-operand entry (see ops.fused_decode_tick for the public API).

    ax0: (g, Bp, Hp) layer-0 input preact (bias folded); h/c: (L, Bp, Hp);
    live: (Bp, Hp) fp32 0/1 row mask (all-ones when every slot is live — the
    mask is ALWAYS an operand, so masked and unmasked ticks share one launch
    signature and occupancy changes never relaunch a new shape);
    codes_h: (L, g, R, Hp) uint32, strided-packed with R*G = Kc >= Hp and
    R % 8 == 0; codes_x: (max(L-1,1), g, R, Hp); scale_h/shift_h: (L, g,
    Hp); scale_x/shift_x like codes_x's leading dim; scale_c/shift_c: (L,
    1, Hp); ws: (Hp, Vp) fp32 + bs: (1, Vp) enable the in-kernel head (pass
    None to skip it — wrapper applies the head outside when it would not
    fit VMEM).

    Returns (h', c') or (h', c', logits (Bp, Vp), greedy (Bp, TILE) int32).
    """
    group = group_of(mode)
    L, g, R, hp = codes_h.shape
    bp = h.shape[1]
    if hp % BN_TILE or R % 8 or R * group < hp:
        raise ValueError(f"codes {codes_h.shape} must be R x Hp with "
                         f"Hp % {BN_TILE} == 0, R % 8 == 0, R*{group} >= Hp")
    if h.shape != (L, bp, hp) or live.shape != (bp, hp) or bp % 8:
        raise ValueError(f"state {h.shape} / live {live.shape} must match "
                         f"padded ({L}, {bp}, {hp}) with Bp % 8 == 0")
    with_head = ws is not None
    interpret = dispatch.resolve_interpret(interpret)
    tn, nt = BN_TILE, hp // BN_TILE

    kernel = functools.partial(_tick_kernel, cell=cell, mode=mode,
                               n_layers=L, n_gates=g, n_tiles=nt,
                               with_head=with_head)
    lx = lambda l: jnp.maximum(l - 1, 0)  # x-side operands start at layer 1
    tile = pl.BlockSpec((1, bp, tn), lambda l, j: (l, 0, j))
    gate_tile = lambda lead: pl.BlockSpec((1, g, tn),
                                          lambda l, j: (lead(l), 0, j))
    codes_tile = lambda lead: pl.BlockSpec((1, g, R, tn),
                                           lambda l, j: (lead(l), 0, 0, j))
    same = lambda l: l
    in_specs = [
        pl.BlockSpec((g, bp, tn), lambda l, j: (0, 0, j)),     # ax0
        pl.BlockSpec((1, bp, hp), lambda l, j: (l, 0, 0)),     # h, whole
        tile, tile,                                            # h, c tiles
        pl.BlockSpec((bp, tn), lambda l, j: (0, j)),           # live
        codes_tile(same), codes_tile(lx),
        gate_tile(same), gate_tile(same), gate_tile(lx), gate_tile(lx),
        pl.BlockSpec((1, 1, tn), lambda l, j: (l, 0, j)),
        pl.BlockSpec((1, 1, tn), lambda l, j: (l, 0, j)),
    ]
    state_shape = jax.ShapeDtypeStruct((L, bp, hp), jnp.float32)
    out_shape = [state_shape, state_shape]
    out_specs = [tile, tile]
    args = [ax0, h, h, c, live, codes_h, codes_x, scale_h, shift_h, scale_x,
            shift_x, scale_c, shift_c]
    if with_head:
        vp = ws.shape[1]
        args += [ws, bs]
        whole = lambda shape: pl.BlockSpec(shape, lambda l, j: (0, 0))
        in_specs += [whole((hp, vp)), whole((1, vp))]
        out_shape += [jax.ShapeDtypeStruct((bp, vp), jnp.float32),
                      jax.ShapeDtypeStruct((bp, BN_TILE), jnp.int32)]
        out_specs += [whole((bp, vp)), whole((bp, BN_TILE))]
    scratch = [pltpu.VMEM((group, R, bp), jnp.float32),   # h, slot-major
               pltpu.VMEM((group, R, bp), jnp.float32),   # layer input, ditto
               pltpu.VMEM((nt, bp, tn), jnp.float32),     # h_new tiles
               pltpu.VMEM((g, bp, tn), jnp.float32)]      # input-side preact
    dispatch.count_launch(f"{mode}_{cell}_decode_tick")
    return pl.pallas_call(
        kernel,
        grid=(L, nt),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        # steps depend on earlier ones (layer l+1 reads layer l's h_new)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=f"{mode}_{cell}_decode_tick",
    )(*args)
