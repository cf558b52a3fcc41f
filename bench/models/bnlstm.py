"""The system under test for a `"model": "bnlstm"` configuration: the
repo's `RNNRuntime` behind its `ServeEngine`, with weights from
`bnlstm_data.generate` made on the device in one jitted call (packed by the
program's own `export_packed_rnn` inside the same call).

The comparison that decides `correct` lives in `bnlstm_check.py`, which
does not import the program."""
from __future__ import annotations

import numpy as np

from bench.models import bnlstm_data


def program_config(cfg: dict):
    from repro.core import bnlstm as BL
    from repro.core.quantize import QuantSpec

    if cfg["cell"] != "lstm":
        raise ValueError("the benchmark's BN-LSTM reference covers LSTM cells")
    return BL.RNNConfig(vocab=cfg["vocab"], d_hidden=cfg["d_hidden"],
                        n_layers=cfg["n_layers"], cell="lstm",
                        quant=QuantSpec(mode=cfg["quant"], norm="batch"),
                        cell_norm=True, eps=1e-5)


def _program_variables(cfg: dict, rcfg, w: dict) -> dict:
    import jax.numpy as jnp
    from repro.core import bnlstm as BL
    from repro.core.recurrent_bn import BNParams, BNState

    params, state = [], []
    for lw in w["layers"]:
        bn_p = {k: BNParams(phi=lw[k]["phi"], gamma=lw[k]["gamma"])
                for k in ("bn_x", "bn_h", "bn_c")}
        bn_s = {k: BNState(mean=lw[k]["mean"], var=lw[k]["var"],
                           count=jnp.zeros((), jnp.float32))
                for k in ("bn_x", "bn_h", "bn_c")}
        params.append({"wx": lw["wx"], "wh": lw["wh"], "b": lw["b"], **bn_p})
        state.append(bn_s)
    tree = {"layers": params, "head": {"ws": w["ws"], "bs": w["bs"]}}
    if cfg["quant"] != "none":
        tree = BL.export_packed_rnn(tree, rcfg)
    return {"params": tree, "state": {"layers": state}}


def build(cfg: dict, seed: int, *, slots: int, max_context: int,
          prefill_chunk: int, mesh_spec: str | None):
    """Weights from the seed, the runtime, and an engine over them."""
    import jax
    from repro.serve.engine import ServeEngine
    from repro.serve.recurrent import RNNRuntime

    rcfg = program_config(cfg)
    make = jax.jit(lambda key: _program_variables(
        cfg, rcfg, bnlstm_data.generate(cfg, key)))
    variables = make(bnlstm_data.jax_key(seed))
    rt = RNNRuntime(rcfg, variables)
    mesh = None
    if mesh_spec:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(mesh_spec)
    return ServeEngine(rt, cfg["vocab"], slots=slots, max_context=max_context,
                       prefill_chunk=prefill_chunk, mesh=mesh)


def read_state(eng) -> dict:
    """The slot pool as the window left it: h, c (L, slots, H) and the
    tokens each slot has consumed (slots,)."""
    pool = eng.pool
    return {"h": np.asarray(pool.h, np.float32),
            "c": np.asarray(pool.c, np.float32),
            "pos": np.asarray(pool.pos, np.int64)}


def check(cfg: dict, seed: int, **kw) -> dict:
    from bench.models import bnlstm_check
    return bnlstm_check.check(cfg, seed, **kw)
