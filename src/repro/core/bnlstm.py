"""BN-LSTM / BN-GRU with learned recurrent binary/ternary weights.

Faithful implementation of the paper's Algorithm 1 / Eq. (7):

  * master weights W_{*h}, W_{*x} are fp32; they are quantized ONCE per forward
    pass (before the time loop, Algorithm 1 lines 3-6),
  * every vector-matrix product is batch-normalized with a learned scale phi
    and additive term fixed to 0 (Eq. 7),
  * the cell state is optionally batch-normalized with learned (phi_c, gamma_c)
    (Algorithm 1 line 13),
  * biases, BN parameters and the softmax classifier stay full-precision.

The four gates (f, i, o, g) are fused into single (d, 4H) matmuls; BN is
per-column so the fused form is mathematically identical to eight separate
BN(W·) terms.  The time loop is a `jax.lax.scan`, so the HLO stays small and
the same code path scales from the CPU tests to the pod-level dry run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import quantize as Q
from repro.core.qtensor import export_packed, is_qtensor
from repro.core.recurrent_bn import BNParams, BNState, bn_apply, bn_init
from repro.kernels import dispatch
from repro.kernels import ops as OPS

Array = jax.Array

# The BN-LSTM keeps the paper's lowercase parameter names; this is the
# explicit QuantPolicy equivalent of Algorithm 1's split (quantize the
# recurrent/input matrices, keep the softmax classifier 'ws' and all
# biases/BN parameters fp).
RNN_POLICY = Q.QuantPolicy(include=("wx", "wh"))


@dataclasses.dataclass(frozen=True)
class RNNConfig:
    vocab: int
    d_hidden: int
    n_layers: int = 1
    cell: str = "lstm"  # 'lstm' | 'gru'
    quant: Q.QuantSpec = Q.QuantSpec(mode="ternary", norm="batch")
    cell_norm: bool = True  # BN on the cell state (Algorithm 1 line 13)
    eps: float = 1e-5
    momentum: float = 0.99
    dtype: Any = jnp.float32

    @property
    def n_gates(self) -> int:
        return 4 if self.cell == "lstm" else 3


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _layer_init(key, d_in: int, cfg: RNNConfig) -> dict:
    h, g = cfg.d_hidden, cfg.n_gates
    kx, kh = jax.random.split(key)
    ax = Q.glorot_alpha(d_in, g * h)
    ah = Q.glorot_alpha(h, g * h)
    wx = jax.random.uniform(kx, (d_in, g * h), cfg.dtype, -ax, ax)
    wh = jax.random.uniform(kh, (h, g * h), cfg.dtype, -ah, ah)
    bn_x, bn_x_s = bn_init(g * h, dtype=cfg.dtype)
    bn_h, bn_h_s = bn_init(g * h, dtype=cfg.dtype)
    bn_c, bn_c_s = bn_init(h, dtype=cfg.dtype)
    params = {
        "wx": wx, "wh": wh, "b": jnp.zeros((g * h,), cfg.dtype),
        "bn_x": bn_x, "bn_h": bn_h, "bn_c": bn_c,
    }
    state = {"bn_x": bn_x_s, "bn_h": bn_h_s, "bn_c": bn_c_s}
    return {"params": params, "state": state}


def rnn_lm_init(key, cfg: RNNConfig) -> dict:
    """Returns {'params': trainable, 'state': BN running stats}."""
    keys = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    d_in = cfg.vocab
    for l in range(cfg.n_layers):
        layers.append(_layer_init(keys[l], d_in, cfg))
        d_in = cfg.d_hidden
    ks = keys[-1]
    a = Q.glorot_alpha(cfg.d_hidden, cfg.vocab)
    head = {"ws": jax.random.uniform(ks, (cfg.d_hidden, cfg.vocab), cfg.dtype, -a, a),
            "bs": jnp.zeros((cfg.vocab,), cfg.dtype)}
    return {
        "params": {"layers": [l["params"] for l in layers], "head": head},
        "state": {"layers": [l["state"] for l in layers]},
    }


# ---------------------------------------------------------------------------
# quantize weights once per forward pass (Algorithm 1 lines 2-6)
# ---------------------------------------------------------------------------


def export_packed_rnn(params: dict, cfg: RNNConfig) -> dict:
    """Pack a trained BN-LSTM/GRU master tree for serving: every `wx`/`wh`
    becomes a QTensor; head + biases + BN parameters stay fp.  The result
    feeds `rnn_lm_apply` unchanged (training=False)."""
    return export_packed(params, cfg.quant, policy=RNN_POLICY)


def serving_variables(params: dict, bn_state: dict, cfg: RNNConfig) -> dict:
    """The train->serve handoff in one call (DESIGN.md §13): pack the
    trained fp masters and carry the training run's BN running statistics
    along as the FROZEN eval-time statistics.

    Serving always runs training=False, so `bn_apply` normalizes with these
    running (mean, var) — the per-timestep minibatch statistics of training
    never exist at decode time (batch of 1, step by step).  Handing the
    state over untouched is what makes the serving model the same function
    the validation BPC measured; `rnn_decode_tables` later folds these
    statistics into per-gate affines once per export."""
    return {"params": export_packed_rnn(params, cfg), "state": bn_state}


def _quantized_weights(params, cfg: RNNConfig, rng: Optional[Array],
                       training: bool = True):
    out = []
    stochastic = (cfg.quant.stochastic and training
                  and cfg.quant.mode in ("binary", "ternary"))
    for l, lp in enumerate(params["layers"]):
        wx, wh = lp["wx"], lp["wh"]
        if is_qtensor(wx) and is_qtensor(wh):
            # exported packed tree: weights are already the serving artifact
            out.append((wx, wh))
            continue
        if is_qtensor(wx) or is_qtensor(wh):
            raise ValueError(
                f"layer {l}: mixed packed/fp weights (wx packed={is_qtensor(wx)}, "
                f"wh packed={is_qtensor(wh)}); export both or neither — a raw "
                f"master here would silently serve unquantized")
        ax = Q.glorot_alpha(*wx.shape)
        ah = Q.glorot_alpha(*wh.shape)
        if cfg.quant.enabled and stochastic:
            if rng is None:
                raise ValueError("stochastic quantization needs an rng key in training mode")
            kx, kh = jax.random.split(jax.random.fold_in(rng, l))
            ux = jax.random.uniform(kx, wx.shape, wx.dtype)
            uh = jax.random.uniform(kh, wh.shape, wh.dtype)
        else:
            ux = uh = None
        if cfg.quant.mode in ("binary", "ternary") and not stochastic:
            # inference: deterministic expectation (paper Fig. 1b shows the
            # stochastic/deterministic gap is negligible)
            qx = Q.quantize(wx, cfg.quant.mode, ax, stochastic=False)
            qh = Q.quantize(wh, cfg.quant.mode, ah, stochastic=False)
        else:
            qx = Q.apply_quant(wx, cfg.quant, ax, ux)
            qh = Q.apply_quant(wh, cfg.quant, ah, uh)
        out.append((qx, qh))
    return out


# ---------------------------------------------------------------------------
# cells.  x_t arrives as int tokens for layer 0 (gather == one-hot matmul).
# ---------------------------------------------------------------------------


def _lstm_step(h, c, ax, ah, b, bn_c_p, bn_c_s, cfg: RNNConfig, training):
    pre = ax + ah + b
    f, i, o, g = jnp.split(pre, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    if cfg.cell_norm:
        cn, bn_c_s = bn_apply(c, bn_c_p, bn_c_s, training=training,
                              eps=cfg.eps, momentum=cfg.momentum)
    else:
        cn = c
    h = jax.nn.sigmoid(o) * jnp.tanh(cn)
    return h, c, bn_c_s


def _gru_step(h, ax_rz, ah_rz, ax_g, ah_g, b, training):
    # ax_*, ah_* are already batch-normalized slices; b = (3H,)
    b_r, b_z, b_g = jnp.split(b, 3, axis=-1)
    r = jax.nn.sigmoid(ax_rz[0] + ah_rz[0] + b_r)
    z = jax.nn.sigmoid(ax_rz[1] + ah_rz[1] + b_z)
    g = jnp.tanh(ax_g + r * ah_g + b_g)
    return (1.0 - z) * h + z * g


def rnn_lm_apply(variables: dict, tokens: Array, cfg: RNNConfig, *,
                 training: bool, rng: Optional[Array] = None,
                 return_state: bool = False, features_only: bool = False):
    """tokens: (B, T) int32.  Returns logits (B, T, vocab) and, when
    `return_state`, the updated BN running stats.  `features_only` skips the
    softmax head and returns the top layer's hidden states (B, T, H) —
    classification tasks (sequential MNIST, QA readouts) attach their own
    heads there."""
    params, state = variables["params"], variables["state"]
    B, T = tokens.shape
    qw = _quantized_weights(params, cfg, rng, training=training)

    x_seq = tokens  # layer 0 consumes token ids (gather == one-hot @ Wx)
    new_state = {"layers": []}
    for l in range(cfg.n_layers):
        lp, ls = params["layers"][l], state["layers"][l]
        qx, qh = qw[l]
        h0 = jnp.zeros((B, cfg.d_hidden), cfg.dtype)
        c0 = jnp.zeros((B, cfg.d_hidden), cfg.dtype)

        if l == 0:
            # (B,T) gather of quantized rows — identical to one-hot @ qx.
            # A packed qx decodes first: the gather itself is already
            # MAC-free, and layer 0's input projection is the one place the
            # serving path touches whole rows instead of a matmul.
            rows = qx.dequantize(cfg.dtype) if is_qtensor(qx) else qx
            x_proj_seq = jnp.take(rows, x_seq, axis=0)  # (B, T, gH)
        else:
            x_proj_seq = OPS.qmatmul(x_seq, qx)

        if cfg.cell == "lstm":
            def step(carry, x_proj_t):
                h, c, s_x, s_h, s_c = carry
                axn, s_x = bn_apply(x_proj_t, lp["bn_x"], s_x, training=training,
                                    trainable_gamma=False, eps=cfg.eps, momentum=cfg.momentum)
                ahn, s_h = bn_apply(OPS.qmatmul(h, qh), lp["bn_h"], s_h,
                                    training=training, trainable_gamma=False,
                                    eps=cfg.eps, momentum=cfg.momentum)
                h, c, s_c = _lstm_step(h, c, axn, ahn, lp["b"], lp["bn_c"], s_c, cfg, training)
                return (h, c, s_x, s_h, s_c), h

            carry0 = (h0, c0, ls["bn_x"], ls["bn_h"], ls["bn_c"])
            (hT, cT, s_x, s_h, s_c), hs = jax.lax.scan(
                step, carry0, jnp.swapaxes(x_proj_seq, 0, 1))
        else:  # gru
            def step(carry, x_proj_t):
                h, s_x, s_h = carry
                axn, s_x = bn_apply(x_proj_t, lp["bn_x"], s_x, training=training,
                                    trainable_gamma=False, eps=cfg.eps, momentum=cfg.momentum)
                ahn, s_h = bn_apply(OPS.qmatmul(h, qh), lp["bn_h"], s_h,
                                    training=training, trainable_gamma=False,
                                    eps=cfg.eps, momentum=cfg.momentum)
                H = cfg.d_hidden
                ax_r, ax_z, ax_g = axn[..., :H], axn[..., H:2 * H], axn[..., 2 * H:]
                ah_r, ah_z, ah_g = ahn[..., :H], ahn[..., H:2 * H], ahn[..., 2 * H:]
                h = _gru_step(h, (ax_r, ax_z), (ah_r, ah_z), ax_g, ah_g, lp["b"], training)
                return (h, s_x, s_h), h

            carry0 = (h0, ls["bn_x"], ls["bn_h"])
            (hT, s_x, s_h), hs = jax.lax.scan(step, carry0, jnp.swapaxes(x_proj_seq, 0, 1))
            s_c = ls["bn_c"]

        x_seq = jnp.swapaxes(hs, 0, 1)  # (B, T, H)
        new_state["layers"].append({"bn_x": s_x, "bn_h": s_h, "bn_c": s_c})

    if features_only:
        out = x_seq
    else:
        out = jnp.einsum("bth,hv->btv", x_seq, params["head"]["ws"]) \
            + params["head"]["bs"]
    if return_state:
        return out, new_state
    return out


# ---------------------------------------------------------------------------
# stateful serving: prefill / decode_step against frozen BN statistics
# (DESIGN.md §6).  At inference every BN is a per-column affine
#   y = x * (phi * rsqrt(var + eps)) + (gamma - phi * mean * rsqrt(var + eps))
# so the whole serving forward is gathers, (packed) matmuls, affines and gate
# nonlinearities — exactly the shape the fused Pallas decode kernel consumes.
# ---------------------------------------------------------------------------


class RNNState(NamedTuple):
    """Per-session recurrent state: stacked per-layer hidden/cell vectors.

    `c` is carried but unused for GRU cells (kept zeros) so LSTM and GRU share
    one state layout and the serving runtime never branches on cell type."""

    h: Array    # (n_layers, B, H)
    c: Array    # (n_layers, B, H)
    pos: Array  # () int32 — tokens consumed; (B,) in a per-slot pool


def rnn_state_init(cfg: RNNConfig, batch: int, dtype=None, *,
                   per_slot: bool = False) -> RNNState:
    """`per_slot` gives each batch row its own token counter (B,) — the
    continuous-batching pool layout, where slots sit at different depths.
    `pos` is bookkeeping (the recurrence itself is position-free), so both
    layouts run the identical prefill/decode compute."""
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.d_hidden)
    pos = jnp.zeros((batch,) if per_slot else (), jnp.int32)
    # two buffers, not one: the engine donates the pool, and XLA refuses
    # to donate one buffer twice
    return RNNState(h=jnp.zeros(shape, dtype), c=jnp.zeros(shape, dtype),
                    pos=pos)


def rnn_write_slots(state: RNNState, sub: RNNState, slots) -> RNNState:
    """Insert a k-sequence state into rows `slots` of a per-slot pool.

    The O(1) recurrent state is the whole trick: admission is two (L, H)
    row copies per slot, no KV bytes move.  `slots`: scalar or (k,) int;
    `sub`: batch-k state (its pos may be scalar — a freshly prefilled
    single request — or (k,))."""
    slots = jnp.atleast_1d(jnp.asarray(slots, jnp.int32))
    sub_pos = jnp.broadcast_to(jnp.asarray(sub.pos), slots.shape)
    return RNNState(h=state.h.at[:, slots].set(sub.h),
                    c=state.c.at[:, slots].set(sub.c),
                    pos=state.pos.at[slots].set(sub_pos))


def _bn_affine(p: BNParams, s: BNState, eps: float) -> tuple[Array, Array]:
    """Frozen inference BN as (scale, shift): y = x * scale + shift."""
    inv = jax.lax.rsqrt(s.var + eps)
    return p.phi * inv, p.gamma - p.phi * s.mean * inv


def rnn_decode_tables(variables: dict, cfg: RNNConfig, *,
                      dense: Optional[bool] = None) -> list:
    """Per-session serving artifacts, computed ONCE and reused every step.

    Per layer: deterministic/packed weights, the h-side and x-side BN affines,
    the cell-norm affine, and — for layer 0 — the token gather table with the
    x-side BN already folded in (`rows_bn`), so serving never dequantizes the
    embedding rows per call.  When the whole tree serves packed, the tables
    additionally carry the stacked whole-tick artifact (`tables[0]["tick"]`,
    see `_tick_tables`) that `rnn_decode_step` feeds the single-launch fused
    Pallas decode kernel.

    `dense` expands packed weights into DENSE fp tables at session setup,
    the same once-per-session dequantize layer 0's `rows_bn` already gets:
    the serving tree stays the packed QTensor export (memory is still the
    2-bit codes), but every step runs plain dense matmuls.  `dense=None`
    asks `kernels/dispatch.py` for the backend-honest answer — True on CPU
    (where packed Pallas kernels would only run emulated), False on real
    accelerators.  Parity tests opt into the packed tables on CPU with an
    explicit `dense=False`."""
    dense = dispatch.prefer_dense(dense)
    params, bn_state = variables["params"], variables["state"]
    qw = _quantized_weights(params, cfg, None, training=False)
    tables = []
    for l in range(cfg.n_layers):
        lp, ls = params["layers"][l], bn_state["layers"][l]
        qx, qh = qw[l]
        if dense and is_qtensor(qh):
            qh = qh.dequantize(cfg.dtype)
        if dense and is_qtensor(qx):
            qx = qx.dequantize(cfg.dtype)
        sx, tx = _bn_affine(lp["bn_x"], ls["bn_x"], cfg.eps)
        sh, th = _bn_affine(lp["bn_h"], ls["bn_h"], cfg.eps)
        if cfg.cell == "lstm" and cfg.cell_norm:
            sc, tc = _bn_affine(lp["bn_c"], ls["bn_c"], cfg.eps)
        else:
            sc = jnp.ones((cfg.d_hidden,), cfg.dtype)
            tc = jnp.zeros((cfg.d_hidden,), cfg.dtype)
        t = {"qh": qh, "b": lp["b"], "scale_h": sh, "shift_h": th,
             "scale_c": sc, "shift_c": tc}
        if l == 0:
            rows = qx.dequantize(cfg.dtype) if is_qtensor(qx) else qx
            t["rows_bn"] = rows * sx + tx  # gather -> already-BN'd preact
        else:
            t["qx"] = qx
            t["scale_x"], t["shift_x"] = sx, tx
        tables.append(t)
    packed = (all(is_qtensor(t["qh"]) and t["qh"].scale is None
                  for t in tables)
              and all(is_qtensor(t["qx"]) and t["qx"].scale is None
                      for t in tables[1:]))
    if packed:
        tables[0]["tick"] = _tick_tables(params, tables, cfg)
    return tables


def _tick_tables(params: dict, tables: list, cfg: RNNConfig) -> dict:
    """Stacked, padded, fold-complete operands for the whole-tick fused
    kernel (`ops.fused_decode_tick`) — built once per serving session.

    Everything a tick needs beyond the token ids and the carried h/c,
    pre-stacked over layers so the kernel scans them with a static index:
    gate-aligned packed codes for the h-side (all layers) and x-side
    (layers >= 1), the frozen-BN affines with the QTensor alpha folded into
    the scales and the bias folded into the input-side shifts (layer 0's
    bias folds into the `rows0` gather table), the cell-norm affine, and
    the padded fp head with finfo.min bias pads so pad logit columns can
    never win the in-kernel argmax.  ARRAYS ONLY: the dict rides through
    the engine's jits as part of the tables pytree argument."""
    from repro.kernels.decode_step import BN_TILE

    g, H = cfg.n_gates, cfg.d_hidden
    hp = -(-H // BN_TILE) * BN_TILE
    f32 = jnp.float32
    pad_g = lambda a: jnp.pad(a.astype(f32).reshape(g, H),
                              ((0, 0), (0, hp - H)))
    pad_1 = lambda a: jnp.pad(a.astype(f32).reshape(1, H),
                              ((0, 0), (0, hp - H)))
    codes_h, sh, th, sc, tc = [], [], [], [], []
    codes_x, sx, tx = [], [], []
    rows0 = None
    for l, t in enumerate(tables):
        codes_h.append(OPS.prepare_gate_codes(t["qh"], g))
        sh.append(pad_g(t["scale_h"] * t["qh"].alpha))
        th.append(pad_g(t["shift_h"]))
        sc.append(pad_1(t["scale_c"]))
        tc.append(pad_1(t["shift_c"]))
        if l == 0:
            rows0 = (t["rows_bn"] + t["b"]).astype(f32)
        else:
            codes_x.append(OPS.prepare_gate_codes(t["qx"], g))
            sx.append(pad_g(t["scale_x"] * t["qx"].alpha))
            tx.append(pad_g(t["shift_x"] + t["b"]))
    if not codes_x:  # single layer: dummy operand the kernel never reads
        codes_x = [jnp.zeros_like(codes_h[0])]
        sx = [jnp.zeros((g, hp), f32)]
        tx = [jnp.zeros((g, hp), f32)]
    head = params["head"]
    V = cfg.vocab
    vp = -(-V // BN_TILE) * BN_TILE
    ws = jnp.pad(head["ws"].astype(f32), ((0, hp - H), (0, vp - V)))
    bs = jnp.full((1, vp), jnp.finfo(f32).min, f32)
    bs = bs.at[0, :V].set(head["bs"].astype(f32))
    return {"rows0": rows0, "codes_h": jnp.stack(codes_h),
            "codes_x": jnp.stack(codes_x), "scale_h": jnp.stack(sh),
            "shift_h": jnp.stack(th), "scale_x": jnp.stack(sx),
            "shift_x": jnp.stack(tx), "scale_c": jnp.stack(sc),
            "shift_c": jnp.stack(tc), "ws": ws, "bs": bs}


def _serve_lstm_step(t: dict, ax: Array, h: Array, c: Array):
    """ax: (B, 4H) BN'd input-side preact (no bias).  Returns (h', c')."""
    ah = OPS.qmatmul(h, t["qh"]) * t["scale_h"] + t["shift_h"]
    f, i, o, g = jnp.split(ax + ah + t["b"], 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    cn = c * t["scale_c"] + t["shift_c"]
    h = jax.nn.sigmoid(o) * jnp.tanh(cn)
    return h, c


def _serve_gru_step(t: dict, ax: Array, h: Array):
    """ax: (B, 3H) BN'd input-side preact (no bias).  Returns h'."""
    ah = OPS.qmatmul(h, t["qh"]) * t["scale_h"] + t["shift_h"]
    axb = ax + t["b"]
    H = h.shape[-1]
    r = jax.nn.sigmoid(axb[..., :H] + ah[..., :H])
    z = jax.nn.sigmoid(axb[..., H:2 * H] + ah[..., H:2 * H])
    g = jnp.tanh(axb[..., 2 * H:] + r * ah[..., 2 * H:])
    return (1.0 - z) * h + z * g


def _serve_x_preact(t: dict, l: int, x, dtype):
    """Input-side BN'd pre-activation: layer 0 gathers the folded row table
    (token ids in, no matmul); deeper layers project the layer below."""
    if l == 0:
        return jnp.take(t["rows_bn"], x, axis=0).astype(dtype)
    return OPS.qmatmul(x, t["qx"]) * t["scale_x"] + t["shift_x"]


def _serve_scan_layer(t: dict, ax_seq: Array, h0: Array, c0: Array,
                      cell: str):
    """One layer's serving scan — THE shared body of `rnn_prefill` and
    `rnn_prefill_chunk`.  Both must compile this exact step with these
    exact emitted outputs: XLA fuses (and therefore rounds) a scan body
    differently if its outputs differ, and whole-vs-chunked prefill being
    bit-identical depends on the shared body.  Returns (hs, cs, hl, cl):
    the per-step h/c stacked over time (cs = None for GRU) and the final
    carry."""
    if cell == "lstm":
        def step(carry, ax_t):
            h, c = _serve_lstm_step(t, ax_t, *carry)
            return (h, c), (h, c)
        (hl, cl), (hs, cs) = jax.lax.scan(step, (h0, c0),
                                          jnp.swapaxes(ax_seq, 0, 1))
        return hs, cs, hl, cl

    def step(h, ax_t):
        h = _serve_gru_step(t, ax_t, h)
        return h, h
    hl, hs = jax.lax.scan(step, h0, jnp.swapaxes(ax_seq, 0, 1))
    return hs, None, hl, c0


def rnn_logits_last(variables: dict, state: RNNState, cfg: RNNConfig) -> Array:
    """Next-token logits (B, vocab) from a carried state's top-layer h.

    Both prefill flavours (full `rnn_prefill` and the engine's bucket-padded
    `rnn_prefill_chunk`) sample the request's first token through THIS
    helper, at the same (B, 1, H) matmul shape — matmul rounding depends on
    the row count, so sharing the shape is what makes the chunked engine's
    first token bit-identical to the sequential loop's."""
    head = variables["params"]["head"]
    x = state.h[-1].astype(cfg.dtype)[:, None]  # (B, 1, H)
    return (OPS.qmatmul(x, head["ws"]) + head["bs"])[:, 0]


def rnn_prefill(variables: dict, tokens: Array, cfg: RNNConfig,
                state: Optional[RNNState] = None, *,
                tables: Optional[list] = None):
    """Run the prompt through the RNN, carrying state across calls.

    tokens: (B, T) int32.  Returns (logits (B, T, vocab), new RNNState) —
    full-sequence logits so callers can score the prompt; the serving loop
    samples from `rnn_logits_last` on the returned state.

    Runs `_serve_scan_layer` per layer — the body shared with
    `rnn_prefill_chunk` — so the carried state after T tokens is
    bit-identical whether the prompt ran whole or in chunks."""
    params = variables["params"]
    B, T = tokens.shape
    if state is None:
        state = rnn_state_init(cfg, B)
    if tables is None:
        tables = rnn_decode_tables(variables, cfg)

    x_seq = tokens
    hT, cT = [], []
    for l, t in enumerate(tables):
        ax_seq = _serve_x_preact(t, l, x_seq, cfg.dtype)  # (B, T, gH)
        hs, _, hl, cl = _serve_scan_layer(
            t, ax_seq, state.h[l].astype(cfg.dtype),
            state.c[l].astype(cfg.dtype), cfg.cell)
        x_seq = jnp.swapaxes(hs, 0, 1)
        hT.append(hl)
        cT.append(cl)

    logits = OPS.qmatmul(x_seq, params["head"]["ws"]) + params["head"]["bs"]
    new_state = RNNState(h=jnp.stack(hT), c=jnp.stack(cT),
                         pos=state.pos + jnp.int32(T))
    return logits, new_state


def rnn_prefill_chunk(variables: dict, tokens: Array, cfg: RNNConfig,
                      state: RNNState, *, n: Array,
                      tables: Optional[list] = None):
    """One bucket-padded prompt chunk: consume the first `n` of T tokens.

    tokens: (B, T) int32 where T is a BUCKET length (static — one jit trace
    per bucket) and `n` (traced int32) is the real token count, 1 <= n <= T.
    The scan body is `_serve_scan_layer` — EXACTLY `rnn_prefill`'s, so XLA
    fuses and rounds identically; the pad tokens simply run past the end
    and the state at token n-1 is picked out of the per-step outputs.
    Pad steps feed on real outputs but their own outputs are discarded, so
    the returned state and logits are bit-identical to running the unpadded
    slice through `rnn_prefill`, with a trace count that depends on the
    bucket set, not on prompt lengths.  The continuous-batching engine
    resumes a prompt across chunks with this; the carried `state` makes it
    O(1) per chunk regardless of how much prompt came before."""
    B, T = tokens.shape
    if tables is None:
        tables = rnn_decode_tables(variables, cfg)
    n = jnp.asarray(n, jnp.int32)

    x_seq = tokens
    hT, cT = [], []
    for l, t in enumerate(tables):
        ax_seq = _serve_x_preact(t, l, x_seq, cfg.dtype)  # (B, T, gH)
        hs, cs, _, carry_c = _serve_scan_layer(
            t, ax_seq, state.h[l].astype(cfg.dtype),
            state.c[l].astype(cfg.dtype), cfg.cell)
        hl = jnp.take(hs, n - 1, axis=0)
        # GRU carries no cell: _serve_scan_layer returns the c0 it was given
        cl = jnp.take(cs, n - 1, axis=0) if cs is not None else carry_c
        x_seq = jnp.swapaxes(hs, 0, 1)
        hT.append(hl)
        cT.append(cl)

    new_state = RNNState(h=jnp.stack(hT), c=jnp.stack(cT), pos=state.pos + n)
    # first-token logits through the SAME (B, 1, H) head shape the
    # sequential loop samples from (rnn_logits_last) — bit-for-bit equal
    return rnn_logits_last(variables, new_state, cfg), new_state


def rnn_decode_step(variables: dict, tok: Array, cfg: RNNConfig,
                    state: RNNState, *, tables: Optional[list] = None,
                    fused: Optional[bool] = None,
                    live: Optional[Array] = None,
                    interpret: Optional[bool] = None):
    """One serving step.  tok: (B,) or (B, 1) int32.

    Returns (logits (B, vocab), new RNNState).  With packed tables the WHOLE
    tick — every layer's accumulation-only h-side GEMV + BN affine + bias +
    gate nonlinearities, plus the logits head when it fits VMEM — runs as
    ONE fused Pallas launch (kernels/decode_step.py); `fused=False` forces
    the unfused qmatmul path (the parity oracle), `fused=True` requires the
    packed whole-tick tables.

    `live` (B,) bool freezes dead continuous-batching slots: masked rows
    keep their h/c (and pos) bit-for-bit while live rows step normally, so
    the engine runs ONE batched step per tick at fixed shape regardless of
    occupancy.  The fused kernel applies the mask in-launch; the unfused
    path selects after the step.  Dead rows' logits are garbage — the
    engine never samples from them."""
    params = variables["params"]
    if tok.ndim == 2:
        tok = tok[:, 0]
    if tables is None:
        tables = rnn_decode_tables(variables, cfg)

    tick = tables[0].get("tick")
    use_tick = (tick is not None) if fused is None else fused
    if use_tick:
        if tick is None:
            raise ValueError("fused decode needs packed (QTensor) weights; "
                             "export the tree (dense=False tables) or pass "
                             "fused=False")
        logits, hT, cT, _greedy = OPS.fused_decode_tick(
            tok, state.h.astype(cfg.dtype), state.c.astype(cfg.dtype), tick,
            cell=cfg.cell, mode=tables[0]["qh"].mode, vocab=cfg.vocab,
            live=live, interpret=interpret)
        step = 1 if live is None else live.astype(state.pos.dtype)
        return logits, RNNState(h=hT, c=cT, pos=state.pos + step)

    x = tok
    hT, cT = [], []
    for l, t in enumerate(tables):
        ax = _serve_x_preact(t, l, x, cfg.dtype)
        h = state.h[l].astype(cfg.dtype)
        c = state.c[l].astype(cfg.dtype)
        if cfg.cell == "lstm":
            hn, cn = _serve_lstm_step(t, ax, h, c)
        else:
            hn, cn = _serve_gru_step(t, ax, h), c
        if live is not None:
            hn = jnp.where(live[:, None], hn, h)
            cn = jnp.where(live[:, None], cn, c)
        hT.append(hn)
        cT.append(cn)
        x = hn

    logits = OPS.qmatmul(x, params["head"]["ws"]) + params["head"]["bs"]
    step = 1 if live is None else live.astype(state.pos.dtype)
    new_state = RNNState(h=jnp.stack(hT), c=jnp.stack(cT), pos=state.pos + step)
    return logits, new_state


def rnn_verify(variables: dict, tokens: Array, cfg: RNNConfig,
               state: RNNState, *, tables: Optional[list] = None,
               live: Optional[Array] = None,
               interpret: Optional[bool] = None):
    """Speculative-decoding target verify: T tokens through the EXACT
    decode-step body, one `lax.scan` (DESIGN.md §9).

    tokens: (B, T) int32.  Returns (logits (B, T, vocab), end RNNState,
    (hs, cs)) where hs/cs are the per-step carried states stacked over time
    ((T, L, B, H)) — the rollback material `rnn_spec_commit` selects from.

    The scan body IS `rnn_decode_step` (fused Pallas kernel and all), so
    position i's logits and state are bit-identical to i+1 sequential
    decode steps — at temperature 0 a verified stream is byte-identical to
    plain decoding, which is the whole speculative contract.  `live` (B,)
    freezes dead continuous-batching rows exactly as in the tick."""
    if tables is None:
        tables = rnn_decode_tables(variables, cfg)

    def body(carry, tok_t):
        lg, ns = rnn_decode_step(variables, tok_t, cfg, carry, tables=tables,
                                 live=live, interpret=interpret)
        return ns, (lg, ns.h, ns.c)

    end, (lgs, hs, cs) = jax.lax.scan(body, state,
                                      jnp.swapaxes(tokens, 0, 1))
    return jnp.swapaxes(lgs, 0, 1), end, (hs, cs)


def rnn_spec_commit(state0: RNNState, emits, n: Array) -> RNNState:
    """Roll a verified/drafted span back to `n` committed tokens per slot.

    emits: (hs, cs) stacked per-step states from `rnn_verify` (or the
    engine's draft loop), shape (T, L, B, H); n: (B,) int32 in [0, T].
    Slot b gets the state after its first n[b] tokens — n = 0 restores
    `state0`'s row bit-for-bit (the reject-everything rollback; also the
    dead-slot no-op), because the O(1) recurrent state needs no byte
    surgery: rollback is a SELECT, not a rewind."""
    hs, cs = emits
    idx = jnp.maximum(n - 1, 0)[:, None, None, None]

    def pick(stack, base):
        sb = jnp.moveaxis(stack, 2, 0)                  # (B, T, L, H)
        sel = jnp.take_along_axis(sb, idx, axis=1)[:, 0]
        sel = jnp.moveaxis(sel, 0, 1)                   # (L, B, H)
        return jnp.where((n > 0)[None, :, None], sel, base)

    return RNNState(h=pick(hs, state0.h), c=pick(cs, state0.c),
                    pos=state0.pos + n)


def lm_loss(variables, tokens, targets, cfg: RNNConfig, *, training, rng=None):
    """Mean next-token cross entropy (nats).  BPC = loss / ln(2)."""
    logits, new_state = rnn_lm_apply(variables, tokens, cfg, training=training,
                                     rng=rng, return_state=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), new_state


def clip_masters(params, cfg: RNNConfig):
    """Post-update clip of master weights to [-alpha, alpha] (keeps Bernoulli
    probabilities valid).  No-op for unquantized configs."""
    if not cfg.quant.enabled:
        return params
    params = dict(params)
    layers = []
    for lp in params["layers"]:
        lp = dict(lp)
        lp["wx"] = Q.clip_master(lp["wx"], Q.glorot_alpha(*lp["wx"].shape))
        lp["wh"] = Q.clip_master(lp["wh"], Q.glorot_alpha(*lp["wh"].shape))
        layers.append(lp)
    params["layers"] = layers
    return params
