"""Weights of a Zamba2 language model, made on the device from a seed, one
layer at a time.

Shared by the system under test (`zamba2.py` packs them for the program,
layer by layer) and the plain reference (`zamba2_reference.py` makes each
layer again when it reaches it), so both compute with the same numbers and
neither ever holds the whole float tree (29 GB at the published widths).
Nothing here imports the program.

Every key comes from the seed and a layer's own tag (`layer_key`), so a
layer is the same whichever order it is made in.  The names and widths are
those of transformers' `modeling_zamba2.py`, read from a configuration
file's published keys (`dims`).

Distributions (the `assumed` entry of the configuration file):
  * every matmul weight (in/out projections of the mamba layers; q, k, v,
    o, gate_up and down of the shared blocks; each call's adapter and
    linear) is drawn as its ternary value alpha * t, t in {-1, 0, +1} with
    P(0) = 1/2, alpha the paper's Glorot scale sqrt(6 / (fan_in +
    fan_out)), times a per-output-channel scale ~ U(0.75, 1.25).  A master
    that already sits on its ternary value packs to itself exactly.
  * embeddings ~ N(0, 0.02^2) (the published initializer range); RMSNorm
    weights ~ U(0.8, 1.2); conv weights ~ U(-0.5, 0.5) (1/sqrt(d_conv)),
    conv biases ~ U(-0.1, 0.1); A = -U(1, 16) (Mamba2's A_init_range);
    dt_bias = softplus^-1 of dt ~ logU(0.001, 0.1) (the published
    time_step_min/max); D ~ U(0.5, 1.5).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

TAG_EMBED, TAG_MAMBA, TAG_SHARED, TAG_CALL = 1, 2, 3, 4
SCALE = (0.75, 1.25)
NORM = (0.8, 1.2)


def dims(cfg: dict) -> dict:
    """The widths the weights and both implementations need, from the
    configuration file's published keys."""
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    H, P = cfg["n_mamba_heads"], cfg["mamba_headdim"]
    G, N = cfg["mamba_ngroups"], cfg["mamba_d_state"]
    if H * P != di:
        raise ValueError(f"n_mamba_heads * mamba_headdim = {H * P} != {di}")
    return {"d": d, "di": di, "H": H, "P": P, "G": G, "N": N,
            "K": cfg["mamba_d_conv"], "conv": di + 2 * G * N,
            "proj": 2 * di + 2 * G * N + H,
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "hd": cfg["attention_head_dim"], "ff": cfg["intermediate_size"],
            "rank": cfg["adapter_rank"], "vocab": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "ids": tuple(cfg["hybrid_layer_ids"]),
            "blocks": cfg["num_mem_blocks"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"]),
            "dt_min": cfg["time_step_min"], "dt_max": cfg["time_step_max"]}


def jax_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def layer_key(key, tag: int, i):
    return jax.random.fold_in(jax.random.fold_in(key, tag), i)


def alpha(fan_in: int, fan_out: int) -> float:
    return math.sqrt(6.0 / float(fan_in + fan_out))


def ternary(key, shape):
    """(alpha * t, per-output-channel scale): the weight is their product."""
    kt, ks = jax.random.split(key)
    u = jax.random.uniform(kt, shape)
    t = jnp.where(u < 0.25, -1.0, jnp.where(u < 0.5, 1.0, 0.0))
    return (jnp.float32(alpha(*shape)) * t,
            jax.random.uniform(ks, shape[-1:], jnp.float32, *SCALE))


def _u(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def embed(cfg: dict, key) -> dict:
    D = dims(cfg)
    ke, kn = jax.random.split(layer_key(key, TAG_EMBED, 0))
    return {"embed": 0.02 * jax.random.normal(ke, (D["vocab"], D["d"])),
            "final_norm": _u(kn, (D["d"],), *NORM)}


def mamba_layer(cfg: dict, key, i) -> dict:
    """Mamba layer i: `norm` (its input RMSNorm), `in_proj` (d, 2 di +
    2 G N + H) packing [z, x, B, C, dt], the depthwise `conv_w` (K, conv)
    and `conv_b`, `A_log`, `dt_bias`, `D`, the gated norm's `gnorm` and
    `out_proj` (di, d); each projection as (values, scale)."""
    D = dims(cfg)
    ks = jax.random.split(layer_key(key, TAG_MAMBA, i), 9)
    dt = jnp.exp(_u(ks[6], (D["H"],), math.log(D["dt_min"]),
                    math.log(D["dt_max"])))
    return {"norm": _u(ks[0], (D["d"],), *NORM),
            "in_proj": ternary(ks[1], (D["d"], D["proj"])),
            "conv_w": _u(ks[2], (D["K"], D["conv"]), -0.5, 0.5),
            "conv_b": _u(ks[3], (D["conv"],), -0.1, 0.1),
            "A_log": jnp.log(_u(ks[4], (D["H"],), 1.0, 16.0)),
            "D": _u(ks[5], (D["H"],), 0.5, 1.5),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "gnorm": _u(ks[7], (D["di"],), *NORM),
            "out_proj": ternary(ks[8], (D["di"], D["d"]))}


def shared_block(cfg: dict, key, j) -> dict:
    """Shared block j: `norm1` over the 2 d concat, q/k/v (2 d, heads x
    hd), o (heads x hd, d), `norm2`, gate_up (d, 2 ff), down (ff, d)."""
    D = dims(cfg)
    d, hd = D["d"], D["hd"]
    ks = jax.random.split(layer_key(key, TAG_SHARED, j), 8)
    return {"norm1": _u(ks[0], (2 * d,), *NORM),
            "q": ternary(ks[1], (2 * d, D["heads"] * hd)),
            "k": ternary(ks[2], (2 * d, D["kv_heads"] * hd)),
            "v": ternary(ks[3], (2 * d, D["kv_heads"] * hd)),
            "o": ternary(ks[4], (D["heads"] * hd, d)),
            "norm2": _u(ks[5], (d,), *NORM),
            "gate_up": ternary(ks[6], (d, 2 * D["ff"])),
            "down": ternary(ks[7], (D["ff"], d))}


def call(cfg: dict, key, c) -> dict:
    """Call c: its gate_up adapter (d, rank) then (rank, 2 ff), and its
    linear (d, d)."""
    D = dims(cfg)
    ks = jax.random.split(layer_key(key, TAG_CALL, c), 3)
    return {"adapter_in": ternary(ks[0], (D["d"], D["rank"])),
            "adapter_out": ternary(ks[1], (D["rank"], 2 * D["ff"])),
            "linear": ternary(ks[2], (D["d"], D["d"]))}
