"""The system under test for a `"model": "zamba2"` configuration: the
program's `get_config(<program>)` at the configuration file's published
widths, its `TransformerRuntime` behind its `ServeEngine`, packed ternary.

The weights are made on the device from the seed one layer at a time
(`zamba2_data`) and packed by the program's own `QTensor.from_master`
inside the same call, so the float tree (29 GB at the published widths)
never exists: at most one layer of it does.

The comparison that decides `correct` lives in `zamba2_check.py`, which
does not import the program."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.models import zamba2_data as DATA


def program_config(cfg: dict):
    """The program's configuration for `cfg["program"]`, with every width
    taken from the configuration file's published keys."""
    from repro.configs import get_config

    D = DATA.dims(cfg)
    return dataclasses.replace(
        get_config(cfg["program"]), n_layers=D["layers"], d_model=D["d"],
        n_heads=D["heads"], n_kv=D["kv_heads"], head_dim=D["hd"],
        d_ff=D["ff"], vocab=D["vocab"], ssm_state=D["N"],
        ssm_expand=cfg["mamba_expand"], ssm_headdim=D["P"],
        ssm_conv=D["K"], ssm_groups=D["G"],
        hybrid_ids=D["ids"], n_shared=D["blocks"], adapter_rank=D["rank"],
        rope_theta=D["theta"], dtype="float32")


def _packed(w, scale_name, tree, name):
    from repro.core.qtensor import QTensor

    vals, scale = w
    tree[name] = QTensor.from_master(vals, "ternary",
                                     DATA.alpha(*vals.shape))
    tree[scale_name] = scale


def _mamba(cfg, key, i):
    w = DATA.mamba_layer(cfg, key, i)
    mixer = {k: w[k] for k in ("conv_w", "conv_b", "A_log", "D", "dt_bias")}
    mixer["norm"] = w["gnorm"]
    _packed(w["in_proj"], "sin", mixer, "Win")
    _packed(w["out_proj"], "sout", mixer, "Wout")
    return {"norm": w["norm"] - 1.0, "mixer": mixer}


def _shared(cfg, key, j):
    w = DATA.shared_block(cfg, key, j)
    p = {"norm1": w["norm1"] - 1.0, "norm2": w["norm2"] - 1.0}
    for n in ("q", "k", "v", "o", "gate_up", "down"):
        _packed(w[n], "s" + n, p, "W" + n)
    return p


def _call(cfg, key, c):
    w = DATA.call(cfg, key, c)
    p = {}
    for n, prog in (("adapter_in", "adapt_in"), ("adapter_out", "adapt_out"),
                    ("linear", "linear")):
        _packed(w[n], "s" + prog, p, "W" + prog)
    return p


def program_params(cfg: dict, seed: int):
    """The packed serving tree, in the layout of the program's
    `model_init` + `export_packed`, made and packed layer by layer."""
    import jax
    import jax.numpy as jnp

    D = DATA.dims(cfg)
    key = DATA.jax_key(seed)
    stack = lambda fn, n: jax.jit(lambda k: jax.lax.map(
        lambda i: fn(cfg, k, i), jnp.arange(n)))(key)
    we = jax.jit(lambda k: DATA.embed(cfg, k))(key)
    return {"embed": we["embed"], "final_norm": we["final_norm"] - 1.0,
            "mamba": stack(_mamba, D["layers"]),
            "shared": stack(_shared, D["blocks"]),
            "calls": stack(_call, len(D["ids"]))}


def build(cfg: dict, seed: int, *, slots: int, max_context: int,
          prefill_chunk: int, mesh_spec: str | None):
    """Weights from the seed, the runtime, and an engine over them."""
    import jax
    from repro.core.qtensor import export_packed
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine
    from repro.serve.recurrent import serving_runtime

    if mesh_spec:
        raise ValueError("the zamba2 cells run on one chip")
    pcfg = program_config(cfg)
    params = program_params(cfg, seed)
    want = jax.eval_shape(lambda k: export_packed(T.model_init(k, pcfg),
                                                  pcfg.quant),
                          jax.random.PRNGKey(0))
    got, expect = (jax.tree.map(lambda a: (a.shape, a.dtype), t)
                   for t in (params, want))
    if got != expect:
        raise ValueError("the packed weights do not have the layout of the "
                         "program's model_init + export_packed")
    rt = serving_runtime(pcfg, params)
    return ServeEngine(rt, cfg["vocab"], slots=slots, max_context=max_context,
                       prefill_chunk=prefill_chunk)


def read_state(eng) -> dict:
    """The slot pool as the window left it: every mamba layer's h (L,
    slots, H, N, P), every call's keys and values (calls, slots, up to
    the deepest slot, kv_heads * hd), and the tokens each slot has
    consumed (slots,).  The program keeps K/V as (calls, slots, kv_heads,
    cap, width), heads zero-padded to a whole number of lanes; they come
    to the host a call at a time."""
    pool = eng.pool
    pos = np.asarray(pool["ssm"].pos[0], np.int64)
    kv = pool["kv"]
    calls, slots, heads, cap, _ = kv.k.shape
    hd = eng.rt.cfg.hd
    # the keys and values up to the deepest slot, rounded up to 256
    n = min(cap, -(-max(int(pos.max()), 1) // 256) * 256)

    def flat(a):
        out = np.empty((calls, slots, n, heads * hd), np.float32)
        for c in range(calls):
            out[c] = np.asarray(a[c, :, :, :n, :hd], np.float32).transpose(
                0, 2, 1, 3).reshape(slots, n, heads * hd)
        return out

    return {"h": np.asarray(pool["ssm"].h, np.float32),
            "k": flat(kv.k), "v": flat(kv.v), "pos": pos}


def check(cfg: dict, seed: int, **kw) -> dict:
    from bench.models import zamba2_check
    return zamba2_check.check(cfg, seed, **kw)
