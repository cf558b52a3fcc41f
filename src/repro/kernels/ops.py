"""jit'd public wrappers around the Pallas kernels: padding to block
multiples, alpha scaling, dtype handling, and `qmatmul` — the single
dispatch entry every matmul call site in the model code goes through.

`qmatmul(x, w)` routes a `QTensor` operand (core/qtensor.py) to the Pallas
packed kernel and an fp operand to `jnp.matmul`, so `rnn_lm_apply`,
`T.prefill` and `T.decode_step` run unmodified against either a training
tree or an exported packed tree."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.qtensor import QTensor
from repro.kernels import dispatch
from repro.kernels import packed_matmul as PK

Array = jax.Array


def _pad_to(x: Array, m: int, axis: int) -> Array:
    r = x.shape[axis] % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - r)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("k", "mode", "interpret"))
def packed_matmul(x: Array, wp: Array, k: int, alpha=1.0, *, mode: str = "ternary",
                  interpret: Optional[bool] = None) -> Array:
    """y = alpha * (x @ unpack(wp)).  x: (..., K); wp: (K/G, N) uint32.

    Leading batch dims are flattened into M.  The kernel follows the row
    count M: M <= 8 (decode, and the one-row GEMV of BN-LSTM prefill)
    takes `packed_gemv`, with K padded to whole 8-word tiles of codes and
    N to 128 lanes; M > 8 takes the GEMM `packed_matmul`, the prefill
    path, which pads only the activation and reads the codes as they are.
    Both decode each code word once per call and contract it on the MXU
    against x's exact bfloat16 parts.  The paper's zero-multiply loop
    (`packed_matmul.accumulate_planes`) runs in the fused decode tick
    (`fused_decode_tick`).
    """
    group = PK.group_of(mode)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = wp.shape[1]
    xm = x.reshape(-1, K)
    M = xm.shape[0]

    if M <= 8:
        # K padded to whole sublane tiles of packed rows (8 words per slot
        # plane); pad activations are zero, so pad codes add nothing
        kp = -(-max(K, wp.shape[0] * group) // (8 * group)) * (8 * group)
        xm = jnp.pad(xm.astype(jnp.float32), ((0, 0), (0, kp - K)))
        wpp = jnp.pad(wp, ((0, kp // group - wp.shape[0]), (0, -N % 128)))
        y = PK.packed_gemv(xm, wpp, kp, mode=mode, interpret=interpret)
    else:
        y = PK.packed_matmul(xm, wp, K, mode=mode, interpret=interpret)
    y = y[:M, :N] * jnp.asarray(alpha, jnp.float32)
    return y.reshape(*lead, N)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def quantize_pack(w: Array, u: Array, alpha, *, mode: str = "ternary",
                  interpret: Optional[bool] = None) -> Array:
    """Fused stochastic quantize (paper Eq. 4-6) + bit-pack.  w: (K, N) with
    K % GROUP == 0 (weights in this framework are 128-aligned)."""
    group = PK.group_of(mode)
    K, N = w.shape
    bk = min(256, K) if K % 256 == 0 or K <= 256 else group * 8
    while K % bk:
        bk //= 2
    bk = max(bk, group)
    bn = min(256, N)
    while N % bn:
        bn //= 2
    return PK.quantize_pack(w.astype(jnp.float32), u.astype(jnp.float32),
                            alpha, mode=mode, block=(bk, bn),
                            interpret=interpret)


# ---------------------------------------------------------------------------
# qmatmul: the one matmul entry for fp AND packed weights
# ---------------------------------------------------------------------------


def qmatmul(x: Array, w, *, interpret: Optional[bool] = None) -> Array:
    """y = x @ w for fp `w`, or the Pallas packed matmul for `QTensor` w.

    x: (..., K).  A stacked QTensor (codes (L, ..., K/G, N)) is applied
    per-matrix: x's leading axes must start with the same L (expert / layer
    batch), and the L slices run as an unrolled loop (L is small and static —
    experts per layer — and this keeps us off pallas_call batching rules).

    Output dtype follows x (the activation compute dtype); the packed kernel
    accumulates in fp32 either way.

    Sharded codes (mesh serving): the dense-fallback branch accepts SPMD-
    sharded QTensors as-is.  Column-parallel codes (last axis on 'model')
    flow through `dequantize` untouched — its unpack reshapes only the
    packed-row axis, so the column sharding propagates to the dense weight
    and the dot computes each output shard locally (xW sharded exactly like
    a dense column-parallel matmul).  Row-parallel codes partition the
    contraction dim and the dot's psum does the rest; `serve_param_shardings`
    only emits that layout when the shard boundary cannot fall inside a pack
    word or the dequantize pad-slice (`qtensor_pspecs`).  The Pallas branch
    is a single-device launch and must NOT see sharded operands — mesh
    engines gate on `dispatch.packed_pallas_active` before construction.
    """
    if not isinstance(w, QTensor):
        # HIGHEST: an fp32 operand is multiplied in fp32 — the TPU default
        # rounds it to bf16 first (bf16 operands are exact either way)
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    if w.codes.ndim > 2:
        L = w.codes.shape[0]
        if x.shape[0] != L:
            raise ValueError(
                f"stacked QTensor with {L} matrices needs x batched the same "
                f"way, got x {x.shape}")
        sl = lambda i: jax.tree.map(lambda c: c[i], w)
        return jnp.stack([qmatmul(x[i], sl(i), interpret=interpret)
                          for i in range(L)])
    if x.shape[-1] != w.k:
        raise ValueError(f"qmatmul contraction mismatch: x {x.shape} vs "
                         f"QTensor k={w.k}")
    if not dispatch.use_pallas(interpret):
        # backend-honest CPU fallback (kernels/dispatch.py): dequantize and
        # run a dense matmul instead of emulating the Pallas kernel in
        # interpret mode.  Memory stays the packed codes; serving paths that
        # hit this every step cache the dense weight once per session
        # instead (rnn_decode_tables(dense=True)).  interpret=True is the
        # parity-test opt-in that still forces the emulated kernel here.
        y = jnp.dot(x.astype(jnp.float32), w.dequantize(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        return y.astype(x.dtype)
    # zero-pad activations to the codes' K coverage: pad lanes multiply
    # zeros, so pack-time pad codes contribute exactly nothing.
    kp = w.codes.shape[-2] * w.group
    if kp != w.k:
        x_in = _pad_to(x.reshape(-1, w.k), w.group, 1).reshape(
            x.shape[:-1] + (kp,))
    else:
        x_in = x
    y = packed_matmul(x_in, w.codes, kp, w.alpha, mode=w.mode,
                      interpret=interpret)
    if w.scale is not None:
        y = y * w.scale
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# fused whole-tick recurrent decode (kernels/decode_step.py)
# ---------------------------------------------------------------------------


def prepare_gate_codes(qt: QTensor, n_gates: int) -> Array:
    """Gate-align and strided-pack a packed recurrent weight for the fused
    decode kernel.

    `qt` packs wh (H, n_gates*H).  Each gate's N columns are sliced out and
    padded to the 128-lane tile Hp (so gate boundaries stay tile-aligned in
    the kernel), the K rows are padded to Kc = Hp rounded up to 8*GROUP,
    and the gate is re-packed STRIDED: slot j of word r holds row j*R + r
    (R = Kc/GROUP), so the kernel feeds each slot one aligned slice of h
    (kernels/decode_step.py).  Stacked: (n_gates, R, Hp) uint32.  Pad codes
    are harmless — the matching activation lanes are zero-padded.  Done
    ONCE per serving session (serve/recurrent.py caches the result in the
    decode tables)."""
    from repro.core import quantize as Q
    from repro.kernels.decode_step import BN_TILE

    if qt.scale is not None:
        # the fused kernel folds only alpha * BN-affine into its scale; a
        # per-channel QTensor scale would be silently dropped
        raise ValueError("fused decode does not support channel-scaled "
                         "QTensors (RNN export packs scale-free weights); "
                         "use the unfused path")
    kg, N = qt.codes.shape
    H = N // n_gates
    if H * n_gates != N or qt.k != H:
        raise ValueError(f"expected a square-per-gate (H, {n_gates}*H) "
                         f"recurrent weight, got k={qt.k}, N={N}")
    G = qt.group
    hp = -(-max(H, 1) // BN_TILE) * BN_TILE
    kc = -(-hp // (8 * G)) * (8 * G)
    unpack, pack = ((Q.unpack_ternary, Q.pack_ternary) if qt.mode == "ternary"
                    else (Q.unpack_binary, Q.pack_binary))
    vals = unpack(qt.codes, kg * G)[:H]            # exact ±1/0, (H, N)
    gates = []
    for i in range(n_gates):
        w = jnp.pad(vals[:, i * H:(i + 1) * H], ((0, kc - H), (0, hp - H)))
        # contiguous packing of the row permutation r*G + j <- j*R + r
        w = w.reshape(G, kc // G, hp).transpose(1, 0, 2).reshape(kc, hp)
        gates.append(pack(w))
    return jnp.stack(gates)


# padded head weight bytes the fused tick will keep in VMEM alongside the
# codes; beyond this the head runs as one XLA dot outside the launch.  Set
# from the v5e compiler, not guessed: with Hp in {1024, 2048} and B=8 a
# 12 MiB head still compiles and 16 MiB runs out of VMEM; 8 MiB leaves room
# for the larger code tiles of wider models (tests/test_tpu_compile.py
# compiles the tick at this edge)
HEAD_VMEM_BYTES = 8 * 1024 * 1024


def fused_decode_tick(tok: Array, h: Array, c: Array, tick: dict, *,
                      cell: str, mode: str, vocab: int,
                      live: Optional[Array] = None,
                      interpret: Optional[bool] = None):
    """One whole-model decode tick in a SINGLE Pallas launch.

    tok: (B,) int32; h/c: (L, B, H) carried state; `tick` is the stacked
    artifact `core.bnlstm.rnn_decode_tables` builds once per session
    (arrays only — it travels through jits as a pytree argument):

      rows0            (vocab, g*H)  layer-0 token rows, BN + bias folded
      codes_h          (L, g, R, Hp)   gate-aligned strided wh codes
      codes_x          (max(L-1,1), g, R, Hp)  same for wx, l >= 1
      scale_h/shift_h  (L, g, Hp)    h-side BN affine, alpha folded in scale
      scale_x/shift_x  (like codes_x's lead, g, Hp)  x-side BN + bias fold
      scale_c/shift_c  (L, 1, Hp)    cell-norm affine
      ws/bs            (Hp, Vp) / (1, Vp)  fp head, bias pads = finfo.min

    The layer-0 gather runs outside (an XLA gather is not a launch); the
    kernel scans the layers with h/c in VMEM, runs the accumulation-only
    GEMVs, and — when the padded head fits the VMEM budget — the logits
    head and greedy argmax too.  `live` (B,) bool freezes dead rows
    in-kernel, bit-for-bit.

    Returns (logits (B, vocab), h', c', greedy (B,) int32).
    """
    from repro.kernels import decode_step as DK

    L, B, H = h.shape
    codes_h = tick["codes_h"]
    g, hp = codes_h.shape[1], codes_h.shape[-1]
    bp = -(-max(B, 1) // 8) * 8
    f32 = jnp.float32

    rows = jnp.take(tick["rows0"], tok, axis=0).astype(f32)     # (B, g*H)
    ax0 = jnp.pad(rows.reshape(B, g, H).transpose(1, 0, 2),
                  ((0, 0), (0, bp - B), (0, hp - H)))          # (g, Bp, Hp)
    pad_state = lambda a: jnp.pad(a.astype(f32),
                                  ((0, 0), (0, bp - B), (0, hp - H)))
    if live is None:
        live_m = jnp.ones((bp, hp), f32)
    else:  # pad rows 0: they select their (zero) previous state
        live_m = jnp.pad(jnp.broadcast_to(live.astype(f32)[:, None], (B, hp)),
                         ((0, bp - B), (0, 0)))

    ws, bs = tick["ws"], tick["bs"]
    vp = ws.shape[1]
    with_head = (hp * vp + 2 * bp * vp) * 4 <= HEAD_VMEM_BYTES
    out = DK.fused_tick(ax0, pad_state(h), pad_state(c), live_m, codes_h,
                        tick["codes_x"], tick["scale_h"], tick["shift_h"],
                        tick["scale_x"], tick["shift_x"], tick["scale_c"],
                        tick["shift_c"], ws if with_head else None,
                        bs if with_head else None, cell=cell, mode=mode,
                        interpret=interpret)
    if with_head:
        hn, cn, lg, tk = out
        logits = lg[:B, :vocab]
        greedy = tk[:B, 0]
    else:  # head too big for VMEM: one XLA dot outside, still one launch
        hn, cn = out
        lg = jnp.dot(hn[-1], ws, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=f32) + bs
        logits = lg[:B, :vocab]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (logits.astype(h.dtype), hn[:, :B, :H].astype(h.dtype),
            cn[:, :B, :H].astype(h.dtype), greedy)
