"""Pattern-driven model covering the whole assigned pool.

One code path builds dense (llama3/qwen3/gemma3), MoE (mixtral/qwen3-moe),
SSM (rwkv6), enc-dec audio (whisper backbone) and VLM (llama-3.2-vision:
interleaved cross-attn) models from a `ModelConfig`; the zamba2 hybrids
(mamba2 layers with shared attention blocks called between them) have the
one path of their own below (`_hybrid_layers`).

HLO-size discipline (critical for the 512-device dry-run): layers are grouped
by the repeating `block_pattern`; parameters of repeat r, pattern position i
are STACKED over r and the model runs as `lax.scan` over repeats with the
pattern unrolled inside the body.  A 100-layer model lowers to ~5 layer bodies
+ a scan, not 100 inlined layers.  KV caches / SSM states are stacked the same
way and streamed through the scan as xs/ys.

The paper's technique enters exactly once per step: `quantize_tree` maps
every QuantPolicy-matching leaf (stacked or not) through the stochastic
binary/ternary quantizer with straight-through gradients (core/qlinear.py).
At serving time the same forward functions accept an `export_packed` tree
whose weight leaves are packed `QTensor`s — `quantize_tree` passes them
through and every weight matmul dispatches via `kernels.ops.qmatmul`.
Everything else here is quantization-agnostic.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qlinear import maybe_scale, quantize_tree, scaled, winit
from repro.core.qtensor import QTensor
from repro.kernels.ops import qmatmul
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models import rwkv6 as R
from repro.runtime import constrain
from repro.serve.kvcache import (AttnCache, CrossCache, cache_init,
                                 cache_positions, cache_update)

Array = jax.Array

ATTN_KINDS = ("full", "global", "self", "local", "enc")
DECODE_MARGIN = 128  # extra cache slots beyond the spec'd context length


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def _remat_policy(cfg):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


# ---------------------------------------------------------------------------
# pattern expansion
# ---------------------------------------------------------------------------


def expand_pattern(cfg) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    """-> (pattern, repeats, tail_kinds).  A hybrid is n_layers mamba
    layers; its shared-block calls (`cfg.hybrid_ids`) are extra."""
    if cfg.family == "hybrid":
        return ("mamba",), cfg.n_layers, ()
    pat = cfg.block_pattern
    rep = cfg.n_layers // len(pat)
    tail = pat[: cfg.n_layers % len(pat)]
    return pat, rep, tail


# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------


def block_init(key, cfg, kind: str) -> dict:
    d = cfg.d_model
    if kind == "mamba":
        k1, k2 = jax.random.split(key)
        return {"norm": jnp.zeros((d,)), "mixer": M.mamba2_init(k2, cfg)}
    if kind == "rwkv":
        k1, = jax.random.split(key, 1)
        return {"ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,)),
                "mix": R.rwkv6_init(k1, cfg)}
    ka, km = jax.random.split(key)
    p: dict = {"norm1": jnp.zeros((d,)), "norm2": jnp.zeros((d,))}
    if kind == "cross":
        p["attn"] = L.attn_init(ka, cfg, cross=True, kv_d=d)
    elif kind == "selfcross":
        kx, ka = jax.random.split(ka)
        p["attn"] = L.attn_init(ka, cfg)
        p["normc"] = jnp.zeros((d,))
        p["xattn"] = L.attn_init(kx, cfg, cross=True, kv_d=d)
    else:
        p["attn"] = L.attn_init(ka, cfg)
    if cfg.n_experts > 0 and kind not in ("enc",):
        p["moe"] = MOE.moe_init(km, cfg)
    else:
        p["mlp"] = L.mlp_init(km, cfg, kind="gelu" if cfg.family == "audio" else None)
    return p


def _stacked_init(key, cfg, kind: str, n: int) -> dict:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: block_init(k, cfg, kind))(keys)


def model_init(key, cfg) -> dict:
    d, Vp = cfg.d_model, cfg.padded_vocab
    pat, rep, tail = expand_pattern(cfg)
    keys = jax.random.split(key, len(pat) + len(tail) + 8)
    ki = iter(range(len(keys)))

    params: dict = {
        "embed": jax.random.normal(keys[next(ki)], (Vp, d)) * (d ** -0.5),
        "final_norm": jnp.zeros((d,)),
    }
    if not cfg.tie_embeddings:
        params["head"] = winit(keys[next(ki)], (d, Vp))
    if cfg.family == "hybrid":
        params["mamba"] = _stacked_init(keys[next(ki)], cfg, "mamba", rep)
        params["shared"] = jax.vmap(lambda k: shared_block_init(k, cfg))(
            jax.random.split(keys[next(ki)], cfg.n_shared))
        params["calls"] = jax.vmap(lambda k: call_init(k, cfg))(
            jax.random.split(keys[next(ki)], len(cfg.hybrid_ids)))
        return params
    params["stack"] = tuple(_stacked_init(keys[next(ki)], cfg, k, rep)
                            for k in pat)
    params["tail"] = tuple(block_init(keys[next(ki)], cfg, k) for k in tail)
    if cfg.family == "audio":
        ek = jax.random.split(keys[next(ki)], cfg.n_enc_layers + 1)
        params["enc"] = {
            "stack": _stacked_init(ek[0], cfg, "enc", cfg.n_enc_layers),
            "final_norm": jnp.zeros((d,)),
        }
    return params


# ---------------------------------------------------------------------------
# block apply — one function per (kind, cached?) path
# ---------------------------------------------------------------------------


def _mlp_or_moe(p: dict, x: Array, cfg, *, no_drop: bool = False) -> Tuple[Array, Array]:
    if "moe" in p:
        y, aux = MOE.moe_apply(p["moe"], x, cfg, no_drop=no_drop)
        return y, aux
    return L.mlp_apply(p["mlp"], x, cfg), jnp.zeros((), jnp.float32)


def _attn_full(p, x, cfg, kind, positions, xsrc):
    """Training/uncached attention block."""
    if cfg.parallel_block and kind in ("full", "global", "self", "local"):
        h = L.rms_norm(x, p["norm1"])
        o = L.attn_apply(p["attn"], h, cfg, kind=kind, positions=positions)
        y, aux = _mlp_or_moe(p, h, cfg)
        return x + o + y, aux
    h = L.rms_norm(x, p["norm1"])
    if kind == "cross":
        o = L.attn_apply(p["attn"], h, cfg, kind="cross", kv_src=xsrc)
    elif kind == "selfcross":
        o = L.attn_apply(p["attn"], h, cfg, kind="full", positions=positions)
        x = x + o
        hc = L.rms_norm(x, p["normc"])
        o = L.attn_apply(p["xattn"], hc, cfg, kind="cross", kv_src=xsrc)
    else:
        o = L.attn_apply(p["attn"], h, cfg, kind=kind, positions=positions,
                         causal=False if kind == "enc" else None)
    x = x + o
    h = L.rms_norm(x, p["norm2"])
    y, aux = _mlp_or_moe(p, h, cfg)
    return x + y, aux


def _self_attn_cached(p_attn, h, cfg, cache: AttnCache, *, window: int,
                      live=None):
    """h: (B, S, d) new tokens; attends over cache+new.  Returns (o, cache).
    `live` (B,) freezes dead continuous-batching rows' cache bytes/pos."""
    q = L.attn_q(p_attn, h, cfg)
    k_new, v_new = L.attn_kv(p_attn, h, cfg)
    S = h.shape[1]
    # scalar pos -> (S,); per-slot pos (B,) -> (B, S) (every slot of a
    # continuous-batching pool RoPEs/masks at its own sequence depth)
    ar = jnp.arange(S, dtype=jnp.int32)
    positions = cache.pos[:, None] + ar if cache.pos.ndim else cache.pos + ar
    q = L.rope(q, positions, cfg.rope_theta)
    k_new = L.rope(k_new, positions, cfg.rope_theta)
    cache = cache_update(cache, k_new, v_new, live)
    kv_pos = cache_positions(cache)
    # Match q's sharding to the cache policy: heads over 'model' only when
    # the KV heads themselves are head-sharded; with a LENGTH-sharded cache
    # (GQA kv-heads < TP degree) q stays replicated over 'model' so the
    # attention runs where the cache lives (partial logits + small gather)
    # instead of resharding gigabytes of cache every step.
    from repro.serve.kvcache import kv_pspec
    spec = kv_pspec(cache.k.shape[0], cache.k.shape[1], cache.k.shape[2])
    if len(spec) > 2 and spec[2] == "model":
        q = constrain(q, ("pod", "data"), None, "model", None)
    o = L.attention(q, cache.k, cache.v, causal=True, window=window,
                    q_offset=cache.pos - S, kv_pos=kv_pos,
                    chunk=cfg.attn_chunk, softcap=cfg.attn_softcap)
    return o, cache


def _attn_cached(p, x, cfg, kind, cache, xcache: Optional[CrossCache],
                 live=None):
    """Prefill/decode attention block; returns (x, new_cache, new_xcache)."""
    window = cfg.window if (kind == "local" or cfg.swa_all) else 0
    if cfg.parallel_block and kind in ("full", "global", "self", "local"):
        h = L.rms_norm(x, p["norm1"])
        o, cache = _self_attn_cached(p["attn"], h, cfg, cache, window=window,
                                     live=live)
        o = L.attn_out(p["attn"], o, cfg)
        y, aux = _mlp_or_moe(p, h, cfg, no_drop=x.shape[1] == 1)
        return x + o + y, cache, xcache, aux
    h = L.rms_norm(x, p["norm1"])
    if kind == "cross":
        q = L.attn_q(p["attn"], h, cfg)
        o = L.attention(q, xcache.k, xcache.v, causal=False)
        x = x + L.attn_out(p["attn"], o, cfg, cross=True)
    elif kind == "selfcross":
        o, cache = _self_attn_cached(p["attn"], h, cfg, cache, window=0,
                                     live=live)
        x = x + L.attn_out(p["attn"], o, cfg)
        hc = L.rms_norm(x, p["normc"])
        q = L.attn_q(p["xattn"], hc, cfg)
        o = L.attention(q, xcache.k, xcache.v, causal=False)
        x = x + L.attn_out(p["xattn"], o, cfg, cross=True)
    else:
        o, cache = _self_attn_cached(p["attn"], h, cfg, cache, window=window,
                                     live=live)
        x = x + L.attn_out(p["attn"], o, cfg)
    h = L.rms_norm(x, p["norm2"])
    y, aux = _mlp_or_moe(p, h, cfg, no_drop=x.shape[1] == 1)
    return x + y, cache, xcache, aux


def _rwkv_block(p, x, cfg, state: Optional[R.RWKVState], decode):
    h = L.rms_norm(x, p["ln1"])
    y, S, tm_last = R.rwkv6_time_mix(p["mix"], h, cfg, state=state, decode=decode)
    x = x + y.astype(x.dtype)
    h = L.rms_norm(x, p["ln2"])
    y, cm_last = R.rwkv6_channel_mix(
        p["mix"], h, cfg, prev=state.cm_shift if state is not None else None)
    x = x + y.astype(x.dtype)
    new_state = None
    if state is not None:
        new_state = R.RWKVState(S=S, tm_shift=tm_last, cm_shift=cm_last,
                                pos=state.pos + h.shape[1])
    return x, new_state


# ---------------------------------------------------------------------------
# zamba2 hybrid (transformers' modeling_zamba2): n_layers mamba2 layers; before
# the layers in cfg.hybrid_ids a shared attention+MLP block runs on
# concat(hidden, embeddings), the n_shared blocks called in turn
# ---------------------------------------------------------------------------


HYBRID_EPS = 1e-5   # every RMSNorm of a zamba2 hybrid (rms_norm_eps)


def hybrid_calls(cfg) -> np.ndarray:
    """(n_layers,) int32: the shared-block call made before mamba layer i,
    -1 where there is none."""
    calls = np.full(cfg.n_layers, -1, np.int32)
    calls[list(cfg.hybrid_ids)] = np.arange(len(cfg.hybrid_ids))
    return calls


def shared_block_init(key, cfg) -> dict:
    """One shared block: attention over the 2*d_model concat (head_dim is
    2*d_model/n_heads as published), then the gated-GELU MLP."""
    d, hd, ff = cfg.d_model, cfg.hd, cfg.d_ff
    kq, kk, kv, ko, kg, kd = jax.random.split(key, 6)
    p = {"norm1": jnp.zeros((2 * d,)), "norm2": jnp.zeros((d,)),
         "Wq": winit(kq, (2 * d, cfg.n_heads * hd)),
         "Wk": winit(kk, (2 * d, cfg.n_kv * hd)),
         "Wv": winit(kv, (2 * d, cfg.n_kv * hd)),
         "Wo": winit(ko, (cfg.n_heads * hd, d)),
         "Wgate_up": winit(kg, (d, 2 * ff)),
         "Wdown": winit(kd, (ff, d))}
    for n in ("Wq", "Wk", "Wv", "Wo", "Wgate_up", "Wdown"):
        maybe_scale(p, n, cfg.quant, p[n].shape[1], jnp.float32)
    return p


def call_init(key, cfg) -> dict:
    """What each call owns: the rank-r gate_up adapter and the d x d linear
    that projects the block's output into the next mamba layer's input."""
    d, r = cfg.d_model, cfg.adapter_rank
    ka, kb, kl = jax.random.split(key, 3)
    p = {"Wlinear": winit(kl, (d, d))}
    if r:
        p["Wadapt_in"] = winit(ka, (d, r))
        p["Wadapt_out"] = winit(kb, (r, 2 * cfg.d_ff))
    for n in list(p):
        maybe_scale(p, n, cfg.quant, p[n].shape[1], jnp.float32)
    return p


def _mm(x, p, name, cfg):
    return scaled(qmatmul(x, p[name]), p, name, cfg.quant)


def hybrid_kv_width(cfg) -> int:
    """Lanes of one head in a hybrid's K/V cache: hd rounded up to 128.
    Zamba2-7B's hd = 224 is no multiple of the 128 lanes; padded with
    zeros to 256, the cache keeps its plain layout and the attention dots
    read it in place."""
    return -(-cfg.hd // 128) * 128


def _call_qkv(qp, x, emb, pos, c, cfg):
    """Call `c` of the shared blocks, up to its roped queries, keys and
    values over the 2 d-wide concat of x and the embeddings, (B, S, heads,
    hybrid_kv_width) each, zero past hd.  `pos` is the position of x's
    first token: () or per row (B,)."""
    B, S, _ = x.shape
    p = jax.tree.map(lambda a: a[c % cfg.n_shared], qp["shared"])
    h = L.rms_norm(jnp.concatenate([x, emb], axis=-1), p["norm1"],
                   HYBRID_EPS)
    positions = (pos[:, None] if pos.ndim else pos) + jnp.arange(
        S, dtype=jnp.int32)
    pad = [(0, 0)] * 3 + [(0, hybrid_kv_width(cfg) - cfg.hd)]

    def heads(name, n, roped):
        y = _mm(h, p, name, cfg).reshape(B, S, n, cfg.hd)
        if roped:
            y = L.rope(y, positions, cfg.rope_theta)
        return jnp.pad(y, pad)

    return (heads("Wq", cfg.n_heads, True), heads("Wk", cfg.n_kv, True),
            heads("Wv", cfg.n_kv, False))


def _call_out(qp, q, k, v, kv_pos, pos, c, cfg):
    """The rest of call `c`: attention of q over keys and values (B, Skv,
    heads, hybrid_kv_width) at positions kv_pos (-1 unwritten), o_proj,
    the gated-GELU MLP with the call's gate_up adapter, and the call's
    linear.  -> (B, S, d), what the call adds to the next mamba layer's
    input."""
    B, S = q.shape[:2]
    p = jax.tree.map(lambda a: a[c % cfg.n_shared], qp["shared"])
    pc = jax.tree.map(lambda a: a[c], qp["calls"])
    # Zamba2: hd is 2 d / heads, over the concat, and the scale (hd/2)^-0.5
    o = L.attention(q, k, v, causal=True, q_offset=pos, kv_pos=kv_pos,
                    chunk=cfg.attn_chunk, scale=(cfg.hd / 2) ** -0.5)
    o = _mm(o[..., :cfg.hd].reshape(B, S, cfg.n_heads * cfg.hd), p, "Wo",
            cfg)
    h = L.rms_norm(o, p["norm2"], HYBRID_EPS)
    gu = _mm(h, p, "Wgate_up", cfg)
    if cfg.adapter_rank:
        gu = gu + _mm(_mm(h, pc, "Wadapt_in", cfg), pc, "Wadapt_out", cfg)
    gate, up = jnp.split(gu, 2, axis=-1)
    h = jax.nn.gelu(gate, approximate=False) * up
    return _mm(_mm(h, p, "Wdown", cfg), pc, "Wlinear", cfg)


def _hybrid_layers(qp, x, caches: Optional[dict], cfg, *, decode=False,
                   n=None, live=None):
    """All layers of a zamba2 hybrid over x (B, S, d): one `lax.scan` over
    the n_layers mamba layers, the shared-block call before a layer of
    `hybrid_ids` in two `lax.cond`s.  The call's output is added to that
    mamba layer's input only; the residual stream carries x.

    With `caches` ({"ssm": per-layer SSMState, "kv": per-call AttnCache},
    `init_caches`) every state advances in place in the scan's carry.  The
    first cond makes the call's q, k and v; they are written into the
    call's cache between the conds, by a scatter of a few rows (at a layer
    without a call it writes back the rows it read); the second cond only
    reads the caches.  So no copy of the caches is made.  `n` marks bucket
    padding (prefill) and `live` freezes dead rows (decode).
    -> (x, caches)."""
    emb = x
    B, S, _ = x.shape
    calls = jnp.asarray(hybrid_calls(cfg))
    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    ssm = kv = None
    if caches is not None:
        ssm, kv = caches["ssm"], caches["kv"]
    w = hybrid_kv_width(cfg)
    qkv_shapes = [(B, S, h, w) for h in (cfg.n_heads, cfg.n_kv, cfg.n_kv)]

    def body(carry, xs):
        x, ssm, kv = carry
        p, i, c = xs
        ci = jnp.maximum(c, 0)
        on = c >= 0
        pos = jnp.zeros((), jnp.int32) if kv is None else kv.pos[ci]
        q, k, v = jax.lax.cond(
            on, lambda x: _call_qkv(qp, x, emb, pos, ci, cfg),
            lambda x: tuple(jnp.zeros(s, x.dtype) for s in qkv_shapes), x)
        if kv is None:
            t = jax.lax.cond(
                on, lambda: _call_out(qp, q, k, v, None, pos, ci, cfg),
                lambda: jnp.zeros_like(x))
        else:
            kv = _write_call(kv, ci, k, v, jnp.logical_and(
                on, True if live is None else live))

            def attend(kv):
                # the call's cache as (B, cap, heads, w): a transpose the
                # dots absorb
                ck, cv = (jnp.swapaxes(a[ci], 1, 2) for a in (kv.k, kv.v))
                cap = ck.shape[1]
                slots = jnp.arange(cap, dtype=jnp.int32)
                top = kv.pos[ci]
                top = top[:, None] if top.ndim else top
                kv_pos = jnp.where(slots < top, slots, -1)
                return _call_out(qp, q, ck, cv, kv_pos, pos, ci, cfg)

            t = jax.lax.cond(on, attend, lambda kv: jnp.zeros_like(x), kv)
        h = L.rms_norm(x + t, p["norm"], HYBRID_EPS)
        if ssm is None:
            y, _ = M.mamba2_apply(p["mixer"], h, cfg, decode=False)
        else:
            st = jax.tree.map(lambda a: a[i], ssm)
            y, st2 = M.mamba2_apply(p["mixer"], h, cfg, state=st,
                                    decode=decode, n=n)
            if live is not None:
                st2 = _freeze_dead(st2, st, live)
            ssm = jax.tree.map(lambda a, b: a.at[i].set(b), ssm, st2)
        x = constrain(x + y.astype(x.dtype), ("pod", "data"), None, None)
        return (x, ssm, kv), None

    if caches is None and cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False,
                              policy=_remat_policy(cfg))
    (x, ssm, kv), _ = jax.lax.scan(body, (x, ssm, kv),
                                   (qp["mamba"], layers, calls))
    return x, (None if caches is None else {"ssm": ssm, "kv": kv})


def _write_call(kv: AttnCache, c, k, v, write) -> AttnCache:
    """Append call c's new keys and values (B, S, heads, w) to its cache
    (calls, B, heads, cap, w) at each slot's own depth, in the rows where
    `write` ((B,) or scalar) holds; elsewhere the rows keep their bytes
    and pos.  One scatter of (heads, S, w) windows, a row's window
    starting at its depth (at most cap - S), a row not written pointed
    past the calls and dropped: a window that leaves the cache's layout
    as it is (an index per token would make XLA copy the cache into
    another).  Bucket padding is rewound off pos by `prefill`, as for
    every attention cache."""
    B, S = k.shape[:2]
    n, cap = kv.k.shape[0], kv.k.shape[3]
    pos = kv.pos[c]                                       # (B,) or ()
    pos = pos if pos.ndim else jnp.broadcast_to(pos, (B,))
    w = jnp.broadcast_to(write, (B,))
    idx = jnp.stack([jnp.where(w, c, n), jnp.arange(B),
                     jnp.clip(pos, 0, cap - S)], axis=1)
    dn = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, 3))

    def put(cache, new):
        return jax.lax.scatter(cache, idx, jnp.swapaxes(new, 1, 2), dn,
                               mode=jax.lax.GatherScatterMode.FILL_OR_DROP)

    step = S * w.astype(jnp.int32)
    pos_c = kv.pos[c] + (step if kv.pos.ndim == 2 else step[0])
    return kv._replace(k=put(kv.k, k), v=put(kv.v, v),
                       pos=kv.pos.at[c].set(pos_c))


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------


def _kind_cache(cfg, kind: str, batch: int, cap: int, src_len: int, dtype,
                per_slot: bool = False):
    """Cache pytree for one layer of `kind` (python structure, zero arrays)."""
    Hkv, hd = cfg.n_kv, cfg.hd
    if kind in ("full", "global", "self"):
        c = cap if not cfg.swa_all else min(cfg.window + DECODE_MARGIN, cap)
        return {"attn": cache_init(batch, c, Hkv, hd, dtype, ring=cfg.swa_all,
                                   per_slot=per_slot)}
    if kind == "local":
        w = min(cfg.window + DECODE_MARGIN, cap)
        return {"attn": cache_init(batch, w, Hkv, hd, dtype, ring=True,
                                   per_slot=per_slot)}
    if kind == "cross":
        return {"cross": CrossCache(k=jnp.zeros((batch, src_len, Hkv, hd), dtype),
                                    v=jnp.zeros((batch, src_len, Hkv, hd), dtype))}
    if kind == "selfcross":
        return {"attn": cache_init(batch, cap, Hkv, hd, dtype,
                                   per_slot=per_slot),
                "cross": CrossCache(k=jnp.zeros((batch, src_len, Hkv, hd), dtype),
                                    v=jnp.zeros((batch, src_len, Hkv, hd), dtype))}
    if kind == "mamba":
        return {"ssm": M.state_init(cfg, batch, dtype, per_slot=per_slot)}
    if kind == "rwkv":
        return {"rwkv": R.state_init(cfg, batch, dtype, per_slot=per_slot)}
    raise ValueError(kind)


def init_caches(cfg, batch: int, context: int, *, src_len: int = 0,
                dtype=None, per_slot: bool = False) -> dict:
    """Stacked cache pytree matching the scan structure.  With `per_slot`
    every position counter is per-sequence (B,) so batch rows can sit at
    different depths — the continuous-batching pool layout (DESIGN.md §7)."""
    dtype = dtype or _dt(cfg)
    cap = context + DECODE_MARGIN
    pat, rep, tail = expand_pattern(cfg)

    def stack(kind):
        one = _kind_cache(cfg, kind, batch, cap, src_len, dtype, per_slot)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (rep,) + a.shape), one)

    if cfg.family == "hybrid":
        # one SSM state per mamba layer, one K/V cache per shared-block
        # call (the calls of one block share its weights, not its cache):
        # (calls, B, Hkv, cap, hybrid_kv_width), the layout the attention
        # dots read without a copy
        n = len(cfg.hybrid_ids)
        shape = (n, batch, cfg.n_kv, cap, hybrid_kv_width(cfg))
        pos = jnp.zeros((n, batch) if per_slot else (n,), jnp.int32)
        return {"ssm": stack("mamba")["ssm"],
                "kv": AttnCache(k=jnp.zeros(shape, dtype),
                                v=jnp.zeros(shape, dtype), pos=pos)}
    return {
        "stack": tuple(stack(k) for k in pat),
        "tail": tuple(_kind_cache(cfg, k, batch, cap, src_len, dtype, per_slot)
                      for k in tail),
    }


# ---------------------------------------------------------------------------
# forward (training / eval — no caches)
# ---------------------------------------------------------------------------


def _embed(params, tokens: Array, cfg) -> Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(_dt(cfg))
    return constrain(x, ("pod", "data"), None, None)


def _final_norm(params, x: Array, cfg) -> Array:
    eps = HYBRID_EPS if cfg.family == "hybrid" else 1e-6
    return L.rms_norm(x, params["final_norm"], eps)


def _head(params, x: Array, cfg) -> Array:
    if cfg.tie_embeddings:
        w = params["embed"].T.astype(x.dtype)  # embed stays fp (gather path)
    else:
        w = params["head"]
        w = w if isinstance(w, QTensor) else w.astype(x.dtype)
    logits = qmatmul(x, w).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab:
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    return constrain(logits, ("pod", "data"), None, "model")


def _run_encoder(params, frames: Array, cfg) -> Array:
    """Whisper-style encoder over precomputed frame embeddings (stub frontend)."""
    x = frames.astype(_dt(cfg))
    x = x + L.sinusoidal_positions(frames.shape[1], cfg.d_model).astype(x.dtype)
    positions = jnp.arange(frames.shape[1], dtype=jnp.int32)

    def body(x, p_slice):
        y, _ = _attn_full(p_slice, x, cfg, "enc", positions, None)
        return y, None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False, policy=_remat_policy(cfg))
    if cfg.n_enc_layers > 0 and cfg.unroll:
        for r in range(cfg.n_enc_layers):
            x, _ = body(x, jax.tree.map(lambda l: l[r], params["enc"]["stack"]))
    elif cfg.n_enc_layers > 0:
        x, _ = jax.lax.scan(body, x, params["enc"]["stack"])
    return L.rms_norm(x, params["enc"]["final_norm"])


def forward(params, tokens: Array, cfg, *, training: bool = False,
            rng: Optional[Array] = None, img: Optional[Array] = None,
            enc_frames: Optional[Array] = None,
            last_only: bool = False) -> Tuple[Array, Array]:
    """Full-sequence forward.  Returns (logits, moe_aux_loss).

    tokens: (B, S) int32.  img: (B, N_img, d) VLM patch embeddings (stub).
    enc_frames: (B, S_audio, d) whisper frame embeddings (stub).
    """
    spec = cfg.quant if training else dataclasses.replace(
        cfg.quant, stochastic=False)
    qparams = quantize_tree(params, spec, rng, compute_dtype=_dt(cfg))

    xsrc = None
    if cfg.family == "audio":
        xsrc = _run_encoder(qparams, enc_frames, cfg)
    elif cfg.family == "vlm":
        xsrc = img.astype(_dt(cfg))

    x = _embed(qparams, tokens, cfg)
    if cfg.family == "hybrid":
        x, _ = _hybrid_layers(qparams, x, None, cfg)
        x = _final_norm(qparams, x, cfg)
        if last_only:
            x = x[:, -1:]
        return _head(qparams, x, cfg), jnp.zeros((), jnp.float32)
    S = tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    pat, rep, tail = expand_pattern(cfg)

    def apply_kind(p, x, kind):
        if kind == "rwkv":
            y, _ = _rwkv_block(p, x, cfg, None, False)
            return y, jnp.zeros((), jnp.float32)
        return _attn_full(p, x, cfg, kind, positions, xsrc)

    def body(carry, p_slices):
        x, aux = carry
        for kind, p in zip(pat, p_slices):
            x, a = apply_kind(p, x, kind)
            aux = aux + a
        x = constrain(x, ("pod", "data"), None, None)
        return (x, aux), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False, policy=_remat_policy(cfg))

    aux0 = jnp.zeros((), jnp.float32)
    if rep > 0 and cfg.unroll:
        carry = (x, aux0)
        for r in range(rep):
            carry, _ = body(carry, jax.tree.map(lambda l: l[r], qparams["stack"]))
        x, aux = carry
    elif rep > 0:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), qparams["stack"])
    else:
        aux = aux0
    for kind, p in zip(tail, qparams["tail"]):
        x, a = apply_kind(p, x, kind)
        aux = aux + a

    x = L.rms_norm(x, qparams["final_norm"])
    if last_only:
        x = x[:, -1:]
    return _head(qparams, x, cfg), aux


# ---------------------------------------------------------------------------
# serving: prefill + decode share one cached-step implementation
# ---------------------------------------------------------------------------


def _freeze_dead(new, old, live):
    """Select per-row between a recurrent state update and the previous
    state: dead continuous-batching rows (live=False) keep every leaf —
    S-matrices, conv tails, shift buffers, pos — bit-for-bit.  The leaf's
    batch axis is axis 0 (RWKVState/SSMState are built per layer)."""
    def sel(n, o):
        m = live.reshape(live.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def _step_cached(qparams, x, caches, cfg, *, decode: bool,
                 xsrc: Optional[Array], live=None) -> Tuple[Array, dict, Array]:
    """Run all layers over new tokens x (B,S,d) against caches.  `live`
    (B,) bool (decode tick of the continuous-batching engine) freezes dead
    rows' cache writes and recurrent states — a dead row may be a slot
    MID-PREFILL, whose state the zombie decode must not touch."""
    pat, rep, tail = expand_pattern(cfg)

    def apply_kind(p, x, kind, cache):
        aux0 = jnp.zeros((), jnp.float32)
        if kind == "rwkv":
            y, st = _rwkv_block(p, x, cfg, cache["rwkv"], decode)
            if live is not None:
                st = _freeze_dead(st, cache["rwkv"], live)
            return y, {"rwkv": st}, aux0
        xc = cache.get("cross")
        if xc is not None and not decode and xsrc is not None:
            # prefill: encode the cross source into the cache once
            name = "xattn" if kind == "selfcross" else "attn"
            k, v = L.attn_kv(p[name], xsrc, cfg)
            xc = CrossCache(k=k, v=v)
        y, ac, xc, aux = _attn_cached(p, x, cfg, kind, cache.get("attn"), xc,
                                      live)
        out = {}
        if ac is not None:
            out["attn"] = ac
        if xc is not None:
            out["cross"] = xc
        return y, out, aux

    def body(carry, xs):
        x, aux = carry
        p_slices, cache_slices = xs
        new_caches = []
        for kind, p, c in zip(pat, p_slices, cache_slices):
            x, nc, a = apply_kind(p, x, kind, c)
            new_caches.append(nc)
            aux = aux + a
        x = constrain(x, ("pod", "data"), None, None)
        return (x, aux), tuple(new_caches)

    aux0 = jnp.zeros((), jnp.float32)
    if rep > 0 and cfg.unroll:
        carry = (x, aux0)
        outs = []
        for r in range(rep):
            sl = lambda t: jax.tree.map(lambda l: l[r], t)
            carry, nc = body(carry, (sl(qparams["stack"]), sl(caches["stack"])))
            outs.append(nc)
        (x, aux) = carry
        new_stack = jax.tree.map(lambda *ls: jnp.stack(ls), *outs) if outs else \
            caches["stack"]
    elif rep > 0:
        (x, aux), new_stack = jax.lax.scan(
            body, (x, aux0), (qparams["stack"], caches["stack"]))
    else:
        aux, new_stack = aux0, caches["stack"]
    new_tail = []
    for kind, p, c in zip(tail, qparams["tail"], caches["tail"]):
        x, nc, a = apply_kind(p, x, kind, c)
        new_tail.append(nc)
        aux = aux + a
    return x, {"stack": new_stack, "tail": tuple(new_tail)}, aux


def _serve_quant(params, cfg):
    spec = dataclasses.replace(cfg.quant, stochastic=False)
    return quantize_tree(params, spec, None, compute_dtype=_dt(cfg))


def _rewind_pad(caches: dict, pad) -> dict:
    """Drop `pad` bucket-padding tokens back off every attention cache's
    per-slot pos.  The pad tokens' k/v bytes stay where they were written,
    but `cache_positions` derives validity from pos alone, so they read as
    unwritten and the next chunk / decode step overwrites them.  Only
    meaningful for runtimes whose caches are pure attention (the engine
    gates bucket padding on that)."""
    is_c = lambda c: isinstance(c, AttnCache)
    return jax.tree.map(lambda c: c._replace(pos=c.pos - pad) if is_c(c) else c,
                        caches, is_leaf=is_c)


def prefill(params, tokens: Array, caches: dict, cfg, *,
            img: Optional[Array] = None,
            enc_frames: Optional[Array] = None,
            n: Optional[Array] = None) -> Tuple[Array, dict]:
    """Process the prompt, fill caches.  Returns (last-token logits, caches).

    `n` (traced int32) marks the first n of tokens as real and the tail as
    bucket padding: the returned logits are taken at position n-1 and the
    attention caches' pos is rewound by the pad count (a hybrid's mamba
    layers leave their state as the real tokens left it), so a fixed bucket
    length serves every real chunk length with one jit trace (chunked
    in-slot prefill, DESIGN.md §8)."""
    qparams = _serve_quant(params, cfg)
    xsrc = None
    if cfg.family == "audio":
        xsrc = _run_encoder(qparams, enc_frames, cfg)
    elif cfg.family == "vlm":
        xsrc = img.astype(_dt(cfg))
    x = _embed(qparams, tokens, cfg)
    if cfg.family == "hybrid":
        x, caches = _hybrid_layers(qparams, x, caches, cfg, n=n)
    else:
        x, caches, _ = _step_cached(qparams, x, caches, cfg, decode=False,
                                    xsrc=xsrc)
    if n is None:
        x = x[:, -1:]
    else:
        n = jnp.asarray(n, jnp.int32)
        x = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
        caches = _rewind_pad(caches, tokens.shape[1] - n)
    x = _final_norm(qparams, x, cfg)
    return _head(qparams, x, cfg)[:, 0], caches


def verify_step(params, tokens: Array, caches: dict, cfg,
                live: Optional[Array] = None) -> Tuple[Array, dict]:
    """Speculative-decoding verify: one multi-token decode over the
    candidate span.  tokens: (B, S) int32 with S = spec_k + 1 (static) ->
    (logits (B, S, Vp), caches).

    Unlike `prefill` this returns logits at EVERY position — the
    acceptance rule needs the target distribution at each candidate — and
    each position's head runs at the decode step's (B, 1, d) shape
    (unrolled: S is a small static constant), because matmul rounding
    depends on the row count and the verified stream must be bit-identical
    to plain decoding at temperature 0.  `live` (B,) freezes dead rows'
    cache bytes/pos exactly as in the decode tick; rollback of rejected
    suffixes is the caller's job (kvcache.cache_spec_commit)."""
    qparams = _serve_quant(params, cfg)
    x = _embed(qparams, tokens, cfg)
    x, caches, _ = _step_cached(qparams, x, caches, cfg, decode=True,
                                xsrc=None, live=live)
    x = L.rms_norm(x, qparams["final_norm"])
    logits = [_head(qparams, x[:, i:i + 1], cfg)[:, 0]
              for i in range(tokens.shape[1])]
    return jnp.stack(logits, axis=1), caches


def decode_step(params, token: Array, caches: dict, cfg,
                live: Optional[Array] = None) -> Tuple[Array, dict]:
    """One decode step.  token: (B,) or (B,1) int32 -> (logits (B, Vp), caches).

    `live` (B,) bool is the continuous-batching engine's occupancy mask:
    dead rows' caches and recurrent states stay bit-for-bit frozen (their
    logits are garbage and never sampled)."""
    if token.ndim == 1:
        token = token[:, None]
    qparams = _serve_quant(params, cfg)
    x = _embed(qparams, token, cfg)
    if cfg.family == "hybrid":
        x, caches = _hybrid_layers(qparams, x, caches, cfg, decode=True,
                                   live=live)
    else:
        x, caches, _ = _step_cached(qparams, x, caches, cfg, decode=True,
                                    xsrc=None, live=live)
    x = _final_norm(qparams, x, cfg)
    return _head(qparams, x, cfg)[:, 0], caches


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def lm_loss(params, batch: dict, cfg, *, training: bool = True,
            rng: Optional[Array] = None, aux_weight: float = 0.01,
            z_weight: float = 1e-4):
    """batch: {'tokens': (B,S), 'targets': (B,S), optional 'img'/'enc_frames'}."""
    logits, aux = forward(params, batch["tokens"], cfg, training=training,
                          rng=rng, img=batch.get("img"),
                          enc_frames=batch.get("enc_frames"))
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - tgt)
    loss = nll + aux_weight * aux + z_weight * jnp.mean(jnp.square(logz))
    return loss, {"nll": nll, "moe_aux": aux}
