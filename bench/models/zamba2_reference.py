"""Plain float32 reference of a Zamba2 language model (transformers'
`modeling_zamba2.py`, the published Zamba2-7B-Instruct layout).

Straight jax.numpy, imports nothing of the program, weights from
`zamba2_data`.  Run layer-major: `Stream.mamba(i, t)` and
`Stream.shared(c)` apply one layer to every row of the batch at once, so a
caller makes each layer's weights, applies them and drops them, and the
whole model never sits on the device.

  * mamba layer: RMSNorm(x + t) where t is the last shared call's output
    (zero elsewhere); in_proj -> [z, xBC, dt]; causal depthwise conv + silu
    over xBC -> [x, B, C]; dt = max(softplus(dt + dt_bias), time_step_min);
    the SSM as a sequential recurrence, one token at a time (not SSD
    chunks): h = exp(dt A) h + dt x B^T, y = h C + D x, for each head with
    its group's B and C; y * silu(z) RMS-normalized per group of d_inner /
    G; out_proj; added to x (the residual is x, not x + t).
  * shared call c (block c % num_mem_blocks): RMSNorm over concat(x,
    embeddings), q/k/v, RoPE (theta, half rotation), causal attention at
    scale (head_dim / 2)^-0.5, o_proj, RMSNorm, gate_up + the call's
    adapter, gelu(gate) * up (exact gelu), down, the call's linear.
  * head: final RMSNorm, logits = x @ embedding^T (tied).

`dot` is the one place precision enters: `dot_highest` is the reference;
the control is `dot_bfloat16`, one bfloat16 pass, the step below float32
activations against ternary weights.  Elementwise products (the
recurrence, the gates) are float32 in both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.models import zamba2_data as DATA
from bench.models.bnlstm_reference import dot_bfloat16, dot_highest

CONTROLS = {"bfloat16": dot_bfloat16}


def rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w


def matmul(dot, x, wv):
    """x (..., K) against a (values, scale) weight of zamba2_data."""
    vals, scale = wv
    lead = x.shape[:-1]
    return dot(x.reshape(-1, x.shape[-1]), vals * scale).reshape(*lead, -1)


def rope(x, theta):
    """x (R, T, heads, hd) at positions 0..T-1, rotate_half convention."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def mamba(D: dict, w: dict, x, t, capture_at, dot):
    """One mamba layer over x (R, T, d) with the shared call's output t
    added to its input.  Returns (x', h (R, H, P, N) after step
    capture_at[r] of row r, zero where capture_at is -1)."""
    R, T, _ = x.shape
    di, H, P, G, N, K = D["di"], D["H"], D["P"], D["G"], D["N"], D["K"]
    h_in = rms(x + t, w["norm"], D["eps"])
    proj = matmul(dot, h_in, w["in_proj"])
    z, xbc, dt = jnp.split(proj, [di, di + D["conv"]], axis=-1)
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, k:k + T] * w["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs, Bs, Cs = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jnp.maximum(jax.nn.softplus(dt + w["dt_bias"]), D["dt_min"])
    A = -jnp.exp(w["A_log"])
    rep = H // G
    xs = xs.reshape(R, T, H, P)
    Bs = jnp.repeat(Bs.reshape(R, T, G, N), rep, axis=2)      # (R, T, H, N)
    Cs = jnp.repeat(Cs.reshape(R, T, G, N), rep, axis=2)

    def step(carry, inp):
        h, cap = carry
        x_t, dt_t, B_t, C_t, s = inp
        h = (h * jnp.exp(dt_t * A)[:, :, None, None]
             + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, :, None, :])
        y = jnp.sum(h * C_t[:, :, None, :], axis=-1) + w["D"][:, None] * x_t
        hit = (capture_at == s)[:, None, None, None]
        return (h, jnp.where(hit, h, cap)), y

    z0 = jnp.zeros((R, H, P, N), jnp.float32)
    mv = lambda a: jnp.moveaxis(a, 1, 0)
    (_, cap), ys = jax.lax.scan(
        step, (z0, z0), (mv(xs), mv(dt), mv(Bs), mv(Cs),
                         jnp.arange(T, dtype=jnp.int32)))
    y = mv(ys).reshape(R, T, di)
    g = y * jax.nn.silu(z)
    g = rms(g.reshape(R, T, G, di // G), 1.0, D["eps"]).reshape(R, T, di)
    out = matmul(dot, g * w["gnorm"], w["out_proj"])
    return x + out, cap


def shared(D: dict, ws: dict, wc: dict, x, emb, dot):
    """Shared call over x (R, T, d).  Returns (its output after the call's
    linear (R, T, d), roped keys and values (R, T, kv_heads * hd))."""
    R, T, d = x.shape
    hd, nh, nkv = D["hd"], D["heads"], D["kv_heads"]
    h = rms(jnp.concatenate([x, emb], axis=-1), ws["norm1"], D["eps"])
    q = rope(matmul(dot, h, ws["q"]).reshape(R, T, nh, hd), D["theta"])
    k = rope(matmul(dot, h, ws["k"]).reshape(R, T, nkv, hd), D["theta"])
    v = matmul(dot, h, ws["v"]).reshape(R, T, nkv, hd)
    rep = nh // nkv
    kh, vh = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = (hd / 2) ** -0.5

    def row(qkv):
        qr, kr, vr = qkv                                    # (T, nh, hd)
        s = jnp.stack([dot(qr[:, j], kr[:, j].T) for j in range(nh)]) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.stack([dot(p[j], vr[:, j]) for j in range(nh)], axis=1)

    o = jax.lax.map(row, (q, kh, vh)).reshape(R, T, nh * hd)
    h = rms(matmul(dot, o, ws["o"]), ws["norm2"], D["eps"])
    gu = matmul(dot, h, ws["gate_up"]) + matmul(
        dot, matmul(dot, h, wc["adapter_in"]), wc["adapter_out"])
    gate, up = jnp.split(gu, 2, axis=-1)
    out = matmul(dot, jax.nn.gelu(gate, approximate=False) * up, ws["down"])
    return (matmul(dot, out, wc["linear"]), k.reshape(R, T, nkv * hd),
            v.reshape(R, T, nkv * hd))


def logits(D: dict, we: dict, x, dot):
    """x (..., d) -> (..., vocab) through the final norm and the tied head."""
    xf = rms(x, we["final_norm"], D["eps"])
    lead = xf.shape[:-1]
    return dot(xf.reshape(-1, xf.shape[-1]), we["embed"].T).reshape(
        *lead, -1)


class Stream:
    """One pass of the reference over a batch of token rows, a layer at a
    time: `step(i)` applies mamba layer i, and before it the shared call
    when layer i has one, making their weights from the seed and dropping
    them after.  Each step returns what the check compares."""

    def __init__(self, cfg: dict, seed: int, tokens, capture_at,
                 dot=dot_highest):
        self.cfg, self.D = cfg, DATA.dims(cfg)
        self.key = DATA.jax_key(seed)
        self.dot, self.capture_at = dot, capture_at
        self.we = _embed_fn(_items(cfg))(self.key)
        self.x = jnp.take(self.we["embed"], tokens, axis=0)
        self.emb = self.x

    def step(self, i: int):
        """-> (h of layer i at the captures (R, H, P, N), the call's (k, v)
        (R, T, kv_heads * hd) or None)."""
        D, key, items = self.D, self.key, _items(self.cfg)
        kv = None
        t = jnp.zeros_like(self.x)
        if i in D["ids"]:
            c = D["ids"].index(i)
            ws = _shared_fn(items)(key, c % D["blocks"])
            wc = _call_fn(items)(key, c)
            t, k, v = _apply_shared(items, self.dot)(ws, wc, self.x, self.emb)
            kv = (k, v)
            del ws, wc
        wm = _mamba_fn(items)(key, i)
        self.x, cap = _apply_mamba(items, self.dot)(wm, self.x, t,
                                                    self.capture_at)
        return cap, kv

    def logits(self, rows=None):
        x = self.x if rows is None else self.x[rows]
        return _apply_logits(_items(self.cfg), self.dot)(self.we, x)


def _items(cfg: dict):
    """The configuration's published keys, hashable (jit caches by them)."""
    keep = ("hidden_size", "mamba_expand", "n_mamba_heads", "mamba_headdim",
            "mamba_ngroups", "mamba_d_state", "mamba_d_conv",
            "num_attention_heads", "num_key_value_heads",
            "attention_head_dim", "intermediate_size", "adapter_rank",
            "vocab_size", "num_hidden_layers", "hybrid_layer_ids",
            "num_mem_blocks", "rms_norm_eps", "rope_theta", "time_step_min",
            "time_step_max")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in keep)


@functools.lru_cache(maxsize=None)
def _embed_fn(items):
    return jax.jit(lambda key: DATA.embed(dict(items), key))


@functools.lru_cache(maxsize=None)
def _mamba_fn(items):
    return jax.jit(lambda key, i: DATA.mamba_layer(dict(items), key, i))


@functools.lru_cache(maxsize=None)
def _shared_fn(items):
    return jax.jit(lambda key, j: DATA.shared_block(dict(items), key, j))


@functools.lru_cache(maxsize=None)
def _call_fn(items):
    return jax.jit(lambda key, c: DATA.call(dict(items), key, c))


@functools.lru_cache(maxsize=None)
def _apply_mamba(items, dot):
    D = DATA.dims(dict(items))
    return jax.jit(lambda w, x, t, cap: mamba(D, w, x, t, cap, dot))


@functools.lru_cache(maxsize=None)
def _apply_shared(items, dot):
    D = DATA.dims(dict(items))
    return jax.jit(lambda ws, wc, x, emb: shared(D, ws, wc, x, emb, dot))


@functools.lru_cache(maxsize=None)
def _apply_logits(items, dot):
    D = DATA.dims(dict(items))
    return jax.jit(lambda we, x: logits(D, we, x, dot))
