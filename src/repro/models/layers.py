"""Shared transformer building blocks: RMSNorm, RoPE, chunked GQA attention
(full / sliding-window / cross), SwiGLU & GeLU MLPs.

All matmul weights follow the default 'W*' pattern of the QuantPolicy in
`repro.core.quantize`; by the time these functions run, a weight may be a
plain fp array, binary/ternary values produced by `quantize_tree` (the
paper's technique), or an exported packed `QTensor` — every weight matmul
goes through `kernels.ops.qmatmul`, which dispatches on the operand, so the
layer code is agnostic.

Attention is query-chunked (a scan over query blocks) so peak logits memory is
O(chunk x S) instead of O(S x S); sliding-window layers additionally slice the
KV stream to `window + chunk`, making local attention O(S x window) — both
matter for the pod-scale memory analysis and keep the HLO small.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.qlinear import maybe_scale, scaled, winit
from repro.kernels.ops import qmatmul
from repro.runtime import constrain

Array = jax.Array
NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale)).astype(dt)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd); positions: (S,) or broadcastable to x's S axis."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., :, None, :]  # broadcast over heads
    sin = sin[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return pe


# ---------------------------------------------------------------------------
# core scaled-dot-product with flexible masking, fp32 softmax
# ---------------------------------------------------------------------------


def _sdpa(q: Array, k: Array, v: Array, q_pos: Array, kv_pos: Array,
          *, causal: bool, window: int, softcap: float = 0.0,
          scale: float = 0.0) -> Array:
    """q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd) with Hq % Hkv == 0;
    *_pos: (Sq,), (Skv,) absolute positions (kv_pos < 0 marks invalid /
    unwritten cache slots) — or per-sequence (B, Sq) / (B, Skv) when slots
    of a continuous-batching pool sit at different depths; the mask then
    varies over batch instead of broadcasting.

    GQA is computed by grouping q heads (einsum over (Hkv, G)) instead of
    materializing repeated K/V — repeating would (a) multiply decode-time KV
    bytes by G and (b) force a cache reshard when the cache is length-sharded
    (SPMD 'involuntary full rematerialization', EXPERIMENTS.md §Perf)."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    scale = scale or hd ** -0.5
    # HIGHEST: float32 operands are multiplied in float32 (the TPU default
    # rounds them to bfloat16 first; bfloat16 operands are exact either way)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32, precision=HIGHEST)
    logits = logits * scale
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    qp = q_pos if q_pos.ndim == 2 else q_pos[None]    # (B|1, Sq)
    kp = kv_pos if kv_pos.ndim == 2 else kv_pos[None]  # (B|1, Skv)
    mask = (kp[:, None, :] >= 0)
    if causal:
        mask = mask & (kp[:, None, :] <= qp[:, :, None])
    if window > 0:
        mask = mask & (kp[:, None, :] > qp[:, :, None] - window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v, precision=HIGHEST)
    return o.reshape(B, Sq, Hq, hd)


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: int = 0, q_offset=0, kv_pos: Optional[Array] = None,
              chunk: int = 1024, softcap: float = 0.0,
              scale: float = 0.0) -> Array:
    """Grouped-query attention with query chunking.

    q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd) with Hq % Hkv == 0.
    `q_offset` is the absolute position of q[0] (decode: cache length) —
    scalar, or (B,) when a per-slot cache puts every sequence at its own
    depth.  `kv_pos` gives absolute positions of cache slots (ring buffers);
    (Skv,) or per-slot (B, Skv); defaults to arange(Skv).  `scale` of the
    logits, 0 for hd ** -0.5.
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    if kv_pos is None:
        kv_pos = jnp.arange(Skv, dtype=jnp.int32)
    off = jnp.asarray(q_offset, jnp.int32)
    ar = jnp.arange(Sq, dtype=jnp.int32)
    q_pos = off[:, None] + ar if off.ndim == 1 else off + ar
    batched = q_pos.ndim == 2 or kv_pos.ndim == 2

    if Sq <= chunk or Sq % chunk != 0:
        return _sdpa(q, k, v, q_pos, kv_pos, causal=causal, window=window,
                     softcap=softcap, scale=scale)

    n_chunks = Sq // chunk
    # the sliding-window KV slice needs one scalar start per chunk, so it
    # stays off when positions are per-row; query chunking itself is
    # row-independent and still bounds logits memory for a long
    # prefill-into-slot (admission prefills are B=1 but Sq can be the
    # whole prompt)
    use_slice = window > 0 and Skv > window + chunk and causal and not batched
    kv_span = window + chunk if use_slice else Skv

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=1)
        if q_pos.ndim == 2:
            qp = jax.lax.dynamic_slice_in_dim(q_pos, i * chunk, chunk, axis=1)
        else:
            qp = q_pos[0] + i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        if use_slice:
            start = jnp.clip(q_offset + i * chunk - window + 1, 0, Skv - kv_span)
            ki = jax.lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
            kp = start + jnp.arange(kv_span, dtype=jnp.int32)
        else:
            ki, vi, kp = k, v, kv_pos
        return _sdpa(qi, ki, vi, qp, kp, causal=causal, window=window,
                     softcap=softcap, scale=scale)

    out = jax.lax.map(one, jnp.arange(n_chunks))  # (n_chunks, B, chunk, Hq, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, Hq, hd)


# ---------------------------------------------------------------------------
# attention layer (params + apply)
# ---------------------------------------------------------------------------


def attn_init(key, cfg, *, cross: bool = False, kv_d: Optional[int] = None) -> dict:
    d, hd = cfg.d_model, cfg.hd
    kq, kk, kv_, ko, kg = jax.random.split(key, 5)
    kvd = kv_d or d
    p = {
        "Wq": winit(kq, (d, cfg.n_heads * hd)),
        "Wk": winit(kk, (kvd, cfg.n_kv * hd)),
        "Wv": winit(kv_, (kvd, cfg.n_kv * hd)),
        "Wo": winit(ko, (cfg.n_heads * hd, d)),
    }
    for n, dout in (("Wq", cfg.n_heads * hd), ("Wk", cfg.n_kv * hd),
                    ("Wv", cfg.n_kv * hd), ("Wo", d)):
        maybe_scale(p, n, cfg.quant, dout, jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,))
        p["k_norm"] = jnp.zeros((hd,))
    if cross:
        p["xgate"] = jnp.zeros(())  # tanh-gated cross-attn (llama-vision style)
    return p


def attn_q(p: dict, x: Array, cfg) -> Array:
    """Query projection only (decode-time cross attention)."""
    hd = cfg.hd
    B, S, _ = x.shape
    q = scaled(qmatmul(x, p["Wq"]), p, "Wq", cfg.quant).reshape(B, S, cfg.n_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
    return q


def attn_kv(p: dict, src: Array, cfg):
    """Key/value projections (cache fill / cross-source encode)."""
    hd = cfg.hd
    B, S, _ = src.shape
    k = scaled(qmatmul(src, p["Wk"]), p, "Wk", cfg.quant).reshape(B, S, cfg.n_kv, hd)
    v = scaled(qmatmul(src, p["Wv"]), p, "Wv", cfg.quant).reshape(B, S, cfg.n_kv, hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"])
    return k, v


def attn_qkv(p: dict, x: Array, cfg, kv_src: Optional[Array] = None):
    """Project to q (from x) and k,v (from kv_src or x); returns (q, k, v)."""
    src = x if kv_src is None else kv_src
    q = attn_q(p, x, cfg)
    k, v = attn_kv(p, src, cfg)
    return q, k, v


def attn_out(p: dict, o: Array, cfg, *, cross: bool = False) -> Array:
    B, S = o.shape[:2]
    o = o.reshape(B, S, cfg.n_heads * cfg.hd)
    y = scaled(qmatmul(o, p["Wo"]), p, "Wo", cfg.quant)
    if cross and "xgate" in p:
        y = jnp.tanh(p["xgate"]).astype(y.dtype) * y
    return y


def attn_apply(p: dict, x: Array, cfg, *, kind: str = "full",
               positions: Optional[Array] = None,
               kv_src: Optional[Array] = None, chunk: int = 1024,
               causal: Optional[bool] = None) -> Array:
    """Self- or cross-attention over a full sequence (training / prefill)."""
    B, S, d = x.shape
    cross = kind == "cross"
    q, k, v = attn_qkv(p, x, cfg, kv_src=kv_src if cross else None)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if not cross:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("pod", "data"), None, "model", None)
    window = cfg.window if kind == "local" or (kind == "full" and cfg.window and cfg.swa_all) else 0
    if causal is None:
        causal = cfg.causal and not cross
    o = attention(q, k, v, causal=causal, window=window, chunk=chunk,
                  softcap=cfg.attn_softcap)
    return attn_out(p, o, cfg, cross=cross)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, cfg, *, kind: Optional[str] = None) -> dict:
    kind = kind or cfg.mlp
    d, ff = cfg.d_model, cfg.d_ff
    if kind == "swiglu":
        kg, ku, kd = jax.random.split(key, 3)
        p = {"Wgate": winit(kg, (d, ff)), "Wup": winit(ku, (d, ff)),
             "Wdown": winit(kd, (ff, d))}
        for n, dout in (("Wgate", ff), ("Wup", ff), ("Wdown", d)):
            maybe_scale(p, n, cfg.quant, dout, jnp.float32)
    else:  # gelu
        k1, k2 = jax.random.split(key)
        p = {"Wfc1": winit(k1, (d, ff)), "Wfc2": winit(k2, (ff, d)),
             "bfc1": jnp.zeros((ff,)), "bfc2": jnp.zeros((d,))}
        for n, dout in (("Wfc1", ff), ("Wfc2", d)):
            maybe_scale(p, n, cfg.quant, dout, jnp.float32)
    return p


def mlp_apply(p: dict, x: Array, cfg) -> Array:
    if "Wgate" in p:
        g = scaled(qmatmul(x, p["Wgate"]), p, "Wgate", cfg.quant)
        u = scaled(qmatmul(x, p["Wup"]), p, "Wup", cfg.quant)
        h = jax.nn.silu(g) * u
        h = constrain(h, ("pod", "data"), None, "model")
        return scaled(qmatmul(h, p["Wdown"]), p, "Wdown", cfg.quant)
    h = jax.nn.gelu(scaled(qmatmul(x, p["Wfc1"]), p, "Wfc1", cfg.quant)
                    + p["bfc1"].astype(x.dtype))
    h = constrain(h, ("pod", "data"), None, "model")
    return scaled(qmatmul(h, p["Wfc2"]), p, "Wfc2", cfg.quant) + p["bfc2"].astype(x.dtype)
