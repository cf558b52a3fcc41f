"""Weights of a BN-LSTM language model, made on the device from a seed.

Shared by the system under test (`bnlstm.py` hands them to the program) and
the plain reference (`bnlstm_reference.py`), so both compute with the same
numbers.  Nothing here imports the program.

The recurrent and input matrices are drawn directly as their quantized
values, alpha * t with t in {-1, 0, +1} (ternary, P(0) = 1/2 as the
deterministic quantizer gives for uniform Glorot masters), t in {-1, +1}
(binary), or uniform in [-alpha, alpha] (full precision).  A ternary or
binary master that already sits on its quantized value quantizes to itself
exactly, so the program's packed export and the reference hold identical
weights.  alpha is the paper's fixed Glorot scale sqrt(6 / (fan_in +
fan_out)).

The batch-norm parameters and running statistics are random too (the
`assumed` entry of each configuration file): phi ~ U(0.5, 1.5), running
variances near the variance each pre-activation has under these weights,
running means a tenth of a standard deviation off zero, and small random
biases.  With the initial values (phi = 0.1, mean 0, var 1) the logits
would hardly depend on the input, and a check of the served tokens would
see almost nothing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MODEL_KEYS = ("vocab", "d_hidden", "n_layers", "cell", "quant")
N_GATES = 4           # LSTM: gates f, i, o, g, fused in this column order
H_SQUARE_MEAN = 0.1   # assumed E[h^2] of a hidden unit, for the BN variances
# phi of the h-side batch norm: small enough that the recurrence contracts.
# With phi ~ U(0.5, 1.5) there too, some seeds give a chaotic network in
# which float32 rounding alone grows to a different state within a few
# thousand steps, and no comparison with a reference could hold.
PHI_H = (0.1, 0.4)


def alpha(fan_in: int, fan_out: int) -> float:
    return math.sqrt(6.0 / float(fan_in + fan_out))


def nonzero_share(quant: str) -> float:
    """E[t^2] of a weight value t in units of alpha."""
    return {"ternary": 0.5, "binary": 1.0, "none": 1.0 / 3.0}[quant]


def jax_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _matrix(key, shape, quant: str):
    a = jnp.float32(alpha(*shape))
    if quant == "ternary":
        t = jax.random.choice(key, jnp.array([-1.0, 0.0, 1.0], jnp.float32),
                              shape, p=jnp.array([0.25, 0.5, 0.25]))
        return a * t
    if quant == "binary":
        return a * jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a)


def _bn(key, n: int, var_nominal: float, with_gamma: bool, phi=(0.5, 1.5)):
    kp, km, kv, kg = jax.random.split(key, 4)
    sd = math.sqrt(var_nominal)
    return {
        "phi": jax.random.uniform(kp, (n,), jnp.float32, *phi),
        "gamma": (jax.random.uniform(kg, (n,), jnp.float32, -0.1, 0.1)
                  if with_gamma else jnp.zeros((n,), jnp.float32)),
        "mean": 0.1 * sd * jax.random.normal(km, (n,), jnp.float32),
        "var": var_nominal * jax.random.uniform(kv, (n,), jnp.float32,
                                                0.5, 2.0),
    }


def generate(cfg: dict, key) -> dict:
    """All weights of the model in `cfg` (a configuration file's dict):
    {"layers": [{wx, wh, b, bn_x, bn_h, bn_c}], "ws", "bs"}.  Jit it with
    `cfg` static: one call on the device."""
    V, H, L = cfg["vocab"], cfg["d_hidden"], cfg["n_layers"]
    quant, g = cfg["quant"], N_GATES
    nz = nonzero_share(quant)
    keys = jax.random.split(key, L + 1)
    layers = []
    for l in range(L):
        d_in = V if l == 0 else H
        kx, kh, kb, k1, k2, k3 = jax.random.split(keys[l], 6)
        ax, ah = alpha(d_in, g * H), alpha(H, g * H)
        # layer 0 gathers one row per token; deeper layers sum d_in terms
        var_x = ax * ax * nz * (1.0 if l == 0 else d_in * H_SQUARE_MEAN)
        var_h = ah * ah * nz * H * H_SQUARE_MEAN
        layers.append({
            "wx": _matrix(kx, (d_in, g * H), quant),
            "wh": _matrix(kh, (H, g * H), quant),
            "b": jax.random.uniform(kb, (g * H,), jnp.float32, -0.1, 0.1),
            "bn_x": _bn(k1, g * H, var_x, with_gamma=False),
            "bn_h": _bn(k2, g * H, var_h, with_gamma=False, phi=PHI_H),
            "bn_c": _bn(k3, H, 0.5, with_gamma=True),
        })
    ks, kb = jax.random.split(keys[-1])
    a = alpha(H, V)
    return {"layers": layers,
            "ws": jax.random.uniform(ks, (H, V), jnp.float32, -a, a),
            "bs": jax.random.uniform(kb, (V,), jnp.float32, -0.1, 0.1)}
