"""Plain float32 reference of the paper's BN-LSTM language model.

Ardakani et al., ICLR 2019, Eq. (7) and Algorithm 1 at inference: every
vector-matrix product is batch-normalized with frozen running statistics
(y = gamma + phi * (x - mean) / sqrt(var + eps)), the gates are
f, i, o, g in that column order, the cell state is batch-normalized before
the output tanh, and a full-precision softmax classifier reads the top
layer.  Layer 0 takes token ids, so its input product is a row gather.

Straight jax.numpy and a `lax.scan` over time: no kernels, no packing, no
cache, no slots.  It imports nothing of the program and takes its weights
from `bnlstm_data.generate`.  `dot` is the one place precision enters:
`dot_highest` is the reference; the control is the step below the
precision the configuration states: `dot_bfloat16` (one bfloat16 pass) for
float32 activations against ternary or binary weights, `dot_high` (three
bfloat16 passes) for float32 weights at `highest`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


def dot_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def dot_bfloat16(a, b):
    """float32 product in one bfloat16 pass: operands rounded to bfloat16,
    products accumulated in float32."""
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def dot_high(a, b):
    """float32 product as three bfloat16 passes (hi*hi + hi*lo + lo*hi),
    written out so that it computes the same on every backend."""
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return dot_highest(ah, bh) + (dot_highest(ah, bl) + dot_highest(al, bh))


def _bn(x, p):
    return p["gamma"] + p["phi"] * (x - p["mean"]) * jax.lax.rsqrt(
        p["var"] + EPS)


def forward(w: dict, tokens, capture_at, dot=dot_highest):
    """tokens (R, T) int32.  Returns (top (T, R, H): the top layer's h after
    each step, h_at (L, R, H) and c_at (L, R, H): every layer's state after
    step capture_at[r] of row r)."""
    R, T = tokens.shape
    x = None
    h_at, c_at = [], []
    for l, lw in enumerate(w["layers"]):
        H = lw["wh"].shape[0]
        if l == 0:
            xp = jnp.take(lw["wx"], tokens.T, axis=0)        # (T, R, 4H)
        else:
            xp = dot(x.reshape(T * R, -1), lw["wx"]).reshape(T, R, -1)
        ax = _bn(xp, lw["bn_x"])

        def step(carry, inp):
            h, c, hc, cc = carry
            ax_t, t = inp
            pre = ax_t + _bn(dot(h, lw["wh"]), lw["bn_h"]) + lw["b"]
            f, i, o, g = jnp.split(pre, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(_bn(c, lw["bn_c"]))
            hit = (capture_at == t)[:, None]
            return (h, c, jnp.where(hit, h, hc), jnp.where(hit, c, cc)), h

        z = jnp.zeros((R, H), jnp.float32)
        (_, _, hc, cc), hs = jax.lax.scan(
            step, (z, z, z, z), (ax, jnp.arange(T, dtype=jnp.int32)))
        h_at.append(hc)
        c_at.append(cc)
        x = hs
    return x, jnp.stack(h_at), jnp.stack(c_at)


CONTROLS = {"bfloat16": dot_bfloat16, "high": dot_high}


def logits(w: dict, hs, dot=dot_highest):
    """hs (..., H) -> (..., V)."""
    lead = hs.shape[:-1]
    out = dot(hs.reshape(-1, hs.shape[-1]), w["ws"]) + w["bs"]
    return out.reshape(*lead, -1)
