"""Mamba2 (SSD) mixer — chunked selective-state-space recurrence.

Used by the zamba2 hybrids.  TPU-native formulation:

  * the sequence is processed in chunks of `cfg.ssm_chunk`; within a chunk the
    recurrence is a dense (Q x Q) causally-masked matmul (MXU work), across
    chunks a `lax.scan` carries the (H, N, P) state — this is the standard
    SSD block-decomposition and maps the "recurrence" onto matmuls instead of
    a length-S scalar scan (length-S scans are VPU-serial on TPU).
  * in/out projections are 'W*' quantizable leaves (the paper's technique);
    the SSM dynamics parameters (A, dt bias, conv, D) are O(d) and stay fp,
    mirroring the paper keeping biases/BN parameters full-precision.

Shapes: d_inner = expand * d_model, H = d_inner / headdim ssm heads,
N = ssm_state, G = ssm_groups B/C groups (head h reads group h // (H/G)).
The in-projection packs [z, x, B, C, dt]; x, B and C pass through the
causal conv; the output is RMS-normalized per group of d_inner / G after
the silu(z) gate, and the time step is floored at 0.001 (transformers'
`Zamba2MambaMixer`).

Bucket-padded chunks (chunked in-slot prefill, DESIGN.md §8): `n` marks
the first n tokens as real.  A pad token gets dt = 0 and x = 0, so it
leaves the state h exactly as it was, and the conv tail is taken at the
last real token, so a padded chunk carries the same state as its real
prefix alone.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import qmatmul
from repro.core.qlinear import maybe_scale, scaled, winit
from repro.runtime import constrain

Array = jax.Array
# every product here multiplies float32 in float32 (the TPU default rounds
# float32 operands to bfloat16 first)
HIGHEST = jax.lax.Precision.HIGHEST
DT_MIN = 1e-3    # floor of the time step (Zamba2's time_step_min)
NORM_EPS = 1e-5  # of the gated RMSNorm (Zamba2's mixer)


class SSMState(NamedTuple):
    h: Array        # (B, H, N, P) inter-chunk state
    conv: Array     # (B, K-1, conv_dim) causal-conv tail
    pos: Array      # () int32 — tokens seen


def _dims(cfg):
    di = cfg.d_inner
    H = cfg.ssm_heads
    P = cfg.ssm_headdim
    N = cfg.ssm_state
    GN = cfg.ssm_groups * N
    conv_dim = di + 2 * GN  # x, B, C pass through the causal conv
    return di, H, P, N, conv_dim


def mamba2_init(key, cfg) -> dict:
    d = cfg.d_model
    di, H, P, N, conv_dim = _dims(cfg)
    d_proj = di + conv_dim + H  # z, x, B, C, dt
    ki, ko, kc, kd = jax.random.split(key, 4)
    p = {
        "Win": winit(ki, (d, d_proj)),
        "Wout": winit(ko, (di, d)),
        "conv_w": jax.random.normal(kc, (cfg.ssm_conv, conv_dim)) * 0.1,
        "conv_b": jnp.zeros((conv_dim,)),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),  # A = -exp
        "D": jnp.ones((H,)),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(kd, (H,)) * (jnp.log(0.1) - jnp.log(1e-3))
                    + jnp.log(1e-3)))),
        "norm": jnp.ones((di,)),
    }
    maybe_scale(p, "Win", cfg.quant, d_proj, jnp.float32)
    maybe_scale(p, "Wout", cfg.quant, d, jnp.float32)
    return p


def _causal_conv(x: Array, w: Array, b: Array, tail: Optional[Array] = None,
                 n=None):
    """x: (B, S, C) depthwise causal conv with kernel (K, C).  `tail` is the
    last K-1 inputs from the previous call (decode); returns (y, new_tail).
    With `n` (traced) only the first n inputs are real: the new tail is the
    last K-1 inputs up to and including the n-th."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    if K == 1:
        new_tail = tail
    elif n is None:
        new_tail = xp[:, -(K - 1):, :]
    else:
        new_tail = jax.lax.dynamic_slice_in_dim(xp, n, K - 1, axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype) for i in range(K))
    return jax.nn.silu(y + b.astype(x.dtype)), new_tail


def _heads(m: Array, H: int, grouped: bool) -> Array:
    if not grouped:
        return jnp.broadcast_to(m[..., None, :], m.shape[:-1] + (H, m.shape[-1]))
    G = m.shape[-2]
    return jnp.repeat(m, H // G, axis=-2)


def ssd_chunked(x: Array, dt: Array, A: Array, Bm: Array, Cm: Array,
                chunk: int, h0: Optional[Array] = None) -> Tuple[Array, Array]:
    """Chunked SSD scan.

    x:  (B, S, H, P)   dt: (B, S, H)   A: (H,) (negative)
    Bm, Cm: (B, S, N) (one group, shared by every head) or (B, S, G, N)
    h0: optional (B, H, N, P) initial state.
    Returns (y (B, S, H, P), h_final (B, H, N, P)).
    """
    Bsz, S, H, P = x.shape
    grouped = Bm.ndim == 4
    Bm, Cm = _heads(Bm, H, grouped), _heads(Cm, H, grouped)  # (B, S, H, N)
    N = Bm.shape[-1]
    Q = min(chunk, S)
    S0_len = S
    if S % Q:
        # zero-pad to a chunk multiple: dt=0 gives decay exp(0)=1 and zero
        # state increment, so the final state is exact.
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q

    r = lambda t: t.reshape(Bsz, nc, Q, *t.shape[2:])
    x, dt, Bm, Cm = r(x), r(dt), r(Bm), r(Cm)

    dA = dt * A  # (B, nc, Q, H) — negative
    cum = jnp.cumsum(dA, axis=2)
    seg_end = cum[:, :, -1, :]                     # total chunk decay (log)

    # intra-chunk: att[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j, i >= j
    idx = jnp.arange(Q)
    causal = idx[:, None] >= idx[None, :]
    logdec = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,Qi,Qj,H)
    dec = jnp.where(causal[None, None, :, :, None], jnp.exp(logdec), 0.0)
    cb = jnp.einsum("bcihn,bcjhn->bcijh", Cm, Bm,
                    precision=HIGHEST)                       # (B,nc,Qi,Qj,H)
    att = cb * dec * dt[:, :, None, :, :]                    # (B,nc,Qi,Qj,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att, x, precision=HIGHEST)

    # chunk states: S_c = sum_j exp(seg_end - cum_j) * dt_j * B_j x_j^T
    w_state = jnp.exp(seg_end[:, :, None, :] - cum) * dt     # (B,nc,Q,H)
    states = jnp.einsum("bcjh,bcjhn,bcjhp->bchnp", w_state, Bm, x,
                        precision=HIGHEST)

    # inter-chunk scan over nc (tiny: S/Q iterations of an (H,N,P) op).
    # The recurrent state is ALWAYS fp32 (decay products compound; bf16
    # states drift over long contexts and break the scan carry dtype).
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    h0 = h0.astype(jnp.float32)
    states = states.astype(jnp.float32)

    def step(h, inp):
        st, dec_tot = inp  # (B,H,N,P), (B,H)
        h_out = h  # state entering this chunk
        h = h * jnp.exp(dec_tot)[:, :, None, None] + st
        return h, h_out

    hT, h_in = jax.lax.scan(step, h0,
                            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(seg_end, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                          # (B,nc,H,N,P)

    # inter-chunk contribution: y_i += C_i . (exp(cum_i) * h_in)
    y_inter = jnp.einsum("bcihn,bchnp,bcih->bcihp", Cm, h_in, jnp.exp(cum),
                         precision=HIGHEST)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y[:, :S0_len], hT


def ssd_step(x: Array, dt: Array, A: Array, Bm: Array, Cm: Array, h: Array):
    """Single-token recurrence (decode).  x: (B, H, P), dt: (B, H),
    Bm/Cm: (B, N) or (B, G, N), h: (B, H, N, P) -> (y, h').

    This IS the serving decode_step body (serve/recurrent.py): decay, rank-1
    state update, readout — no sequence axis."""
    H = x.shape[1]
    grouped = Bm.ndim == 3
    Bm, Cm = _heads(Bm, H, grouped), _heads(Cm, H, grouped)  # (B, H, N)
    dA = jnp.exp(dt * A)                                     # (B, H)
    inc = jnp.einsum("bh,bhn,bhp->bhnp", dt, Bm, x, precision=HIGHEST)
    h = h * dA[:, :, None, None] + inc
    y = jnp.einsum("bhn,bhnp->bhp", Cm, h, precision=HIGHEST)
    return y, h


def gated_group_norm(y: Array, z: Array, w: Array, groups: int,
                     eps: float) -> Array:
    """norm(y * silu(z)) with the RMS taken over each of `groups` equal
    slices of the last axis, then scaled by `w` (fp32 inside)."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    gs = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    gs = gs * jax.lax.rsqrt(jnp.mean(gs * gs, axis=-1, keepdims=True) + eps)
    return gs.reshape(g.shape) * w.astype(jnp.float32)


def mamba2_apply(p: dict, x: Array, cfg, *, state: Optional[SSMState] = None,
                 decode: Optional[bool] = None,
                 n=None) -> Tuple[Array, Optional[SSMState]]:
    """x: (B, S, d_model). decode=True expects S == 1 and a state;
    decode=None auto-selects the `ssd_step` path for a single carried-state
    token (direct mixer callers; the transformer block driver passes the
    flag explicitly).  `n` (traced int32, prefill only) marks the first n
    tokens as real and the rest as bucket padding (module docstring)."""
    Bsz, S, d = x.shape
    di, H, P, N, conv_dim = _dims(cfg)
    G = cfg.ssm_groups
    if decode is None:
        decode = state is not None and S == 1

    proj = scaled(qmatmul(x, p["Win"]), p, "Win", cfg.quant)
    z, xBC, dt = jnp.split(proj, [di, di + conv_dim], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    dt = jnp.maximum(dt, DT_MIN)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    tail = state.conv if state is not None else None
    conv_out, new_tail = _causal_conv(xBC, p["conv_w"], p["conv_b"], tail,
                                      None if n is None
                                      else jnp.asarray(n, jnp.int32))
    xin, Bc, Cc = jnp.split(conv_out, [di, di + G * N], axis=-1)
    if n is not None:
        real = (jnp.arange(S) < n)[None, :, None]
        dt = jnp.where(real, dt, 0.0)
        xin = jnp.where(real, xin, 0.0)

    xh = xin.reshape(Bsz, S, H, P)
    xh = constrain(xh, ("pod", "data"), None, "model", None)
    grp = (lambda m: m.reshape(m.shape[:-1] + (G, N))) if G > 1 else \
        (lambda m: m)
    h0 = state.h if state is not None else None

    if decode:
        h0 = (h0 if h0 is not None
              else jnp.zeros((Bsz, H, N, P), jnp.float32)).astype(jnp.float32)
        y1, hT = ssd_step(xh[:, 0].astype(jnp.float32), dt[:, 0], A,
                          grp(Bc[:, 0].astype(jnp.float32)),
                          grp(Cc[:, 0].astype(jnp.float32)), h0)
        y = y1[:, None]
    else:
        y, hT = ssd_chunked(xh, dt, A, grp(Bc), grp(Cc), cfg.ssm_chunk, h0)

    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh.astype(y.dtype)
    y = y.reshape(Bsz, S, di)
    g = gated_group_norm(y, z, p["norm"], G, NORM_EPS).astype(x.dtype)

    out = scaled(qmatmul(g, p["Wout"]), p, "Wout", cfg.quant)
    new_state = None
    if state is not None or decode:
        pos = (state.pos if state is not None else jnp.zeros((), jnp.int32))
        new_state = SSMState(h=hT, conv=new_tail,
                             pos=pos + (S if n is None else n))
    return out, new_state


def state_init(cfg, batch: int, dtype=jnp.float32, *,
               per_slot: bool = False) -> SSMState:
    """Zero per-session recurrent state — the unified serving-state entry
    point (one signature with `rwkv6.state_init` / `bnlstm.rnn_state_init`;
    serve/recurrent.py and the transformer cache builder both use it).
    `per_slot` makes the token counter (B,) so every continuous-batching
    slot tracks its own depth; `pos` is bookkeeping, not compute, so the
    SSD recurrence is unchanged either way."""
    di, H, P, N, conv_dim = _dims(cfg)
    return SSMState(
        h=jnp.zeros((batch, H, N, P), jnp.float32),  # fp32 recurrent core
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        pos=jnp.zeros((batch,) if per_slot else (), jnp.int32),
    )


ssm_state_init = state_init  # historical name
