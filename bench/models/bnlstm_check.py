"""The comparison that decides `correct` for a BN-LSTM cell.  Imports
nothing of the program.

Two numbers, each against the plain float32 reference
(`bnlstm_reference`, products at `highest`) run over the same prompts and
served tokens, once the window has closed:

  greedy_gap  over a sample of finished greedy requests (the longest among
              them): at every served position, how far the reference's
              logit of the served token lies below the reference's best
              logit, the widest such gap as a share of the largest
              |reference logit| in the sample.  It covers the first-token
              head after chunked prefill, every decode tick and the head,
              and catches a token altered where it is produced.
  state_err   over every request still in a slot when the window closed:
              the slot's h and c (every layer) against the reference's
              state after the same tokens, the root-mean-square difference
              as a share of the reference's root-mean-square, the worse of
              h and c (`state_err_max`, the same with largest values, is
              reported beside it).  It sees rounding that the greedy tokens hide, in prefill
              and in decode, in slots that were reused after a scrub.

With `control=True` the same numbers are also read for the control: the
reference put in the program's place, its products computed one step below
the precision the configuration states (the configuration's `control`:
"bfloat16", one pass, for float32 activations against ternary weights;
"high", three bfloat16 passes, for float32 weights at `highest`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import bnlstm_data
from bench.models import bnlstm_reference as REF


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_items):
    cfg = dict(cfg_items)
    return jax.jit(lambda key: bnlstm_data.generate(cfg, key))


@functools.partial(jax.jit, static_argnames=("n_greedy", "control"))
def _readings(w, tokens, capture_at, lo, hi, n_greedy, control):
    """`control` is None or the name of the control's precision."""
    """tokens (R, T); rows [0, n_greedy) are greedy rows whose served
    positions are t in [lo[r], hi[r]) (logits at t predict tokens[r, t+1]);
    the other rows are slot rows whose state after step capture_at[r] is
    wanted."""
    out = {}
    ctl_dot = REF.CONTROLS[control] if control else None
    dots = [("ref", REF.dot_highest)] + ([("ctl", ctl_dot)]
                                         if control else [])
    tops = {}
    for name, dot in dots:
        top, h_at, c_at = REF.forward(w, tokens, capture_at, dot)
        tops[name] = top
        out[f"h_{name}"] = h_at[:, n_greedy:]
        out[f"c_{name}"] = c_at[:, n_greedy:]
    T = tokens.shape[1]
    t = jnp.arange(T)

    def row(r):
        lg = REF.logits(w, tops["ref"][:, r])                      # (T, V)
        nxt = jnp.concatenate([tokens[r, 1:], tokens[r, :1]])
        inside = (t >= lo[r]) & (t < hi[r])
        best = jnp.max(lg, axis=-1)
        gap = best - jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
        res = [jnp.max(jnp.where(inside, gap, 0.0)),
               jnp.max(jnp.where(inside[:, None], jnp.abs(lg), 0.0)),
               jnp.sum(inside & (gap == 0.0)), jnp.sum(inside)]
        if control:
            lc = REF.logits(w, tops["ctl"][:, r], ctl_dot)
            pick = jnp.argmax(lc, axis=-1)
            gc = best - jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
            res.append(jnp.max(jnp.where(inside, gc, 0.0)))
        return jnp.stack([jnp.asarray(x, jnp.float32) for x in res])

    out["greedy"] = jax.lax.map(row, jnp.arange(n_greedy))
    return out


def check(cfg: dict, seed: int, *, greedy_rows, slot_rows, state,
          t_max: int, n_greedy: int, control: bool = False) -> dict:
    """greedy_rows: [(prompt, served tokens)]; slot_rows: [(prompt, tokens
    so far, slot)]; state: the pool read after the window (`h`, `c`,
    `pos`); t_max: the longest prompt + output the traffic can make (the
    reference's fixed length, so it compiles once); n_greedy: the number of
    greedy rows it is compiled for."""
    n_slots = state["h"].shape[1]
    R = n_greedy + n_slots
    tokens = np.zeros((R, t_max), np.int32)
    lo = np.zeros(R, np.int32)
    hi = np.zeros(R, np.int32)
    capture = np.full(R, -1, np.int32)
    if len(greedy_rows) > n_greedy:
        raise ValueError(f"{len(greedy_rows)} greedy rows, room for {n_greedy}")
    for r, (prompt, toks) in enumerate(greedy_rows):
        seq = np.concatenate([np.asarray(prompt), np.asarray(toks)])
        tokens[r, :seq.size] = seq
        lo[r], hi[r] = len(prompt) - 1, len(prompt) - 1 + len(toks)
    bookkeeping_ok = True
    want = np.zeros(n_slots, bool)
    for prompt, toks, slot in slot_rows:
        r = n_greedy + slot
        seq = np.concatenate([np.asarray(prompt), np.asarray(toks)])
        pos = int(state["pos"][slot])
        if toks and pos != len(prompt) + len(toks) - 1:
            bookkeeping_ok = False  # the slot consumed other tokens
        if pos > seq.size:
            bookkeeping_ok = False
            continue
        tokens[r, :seq.size] = seq
        capture[r] = pos - 1
        want[slot] = True

    w = _weights_fn(tuple((k, cfg[k]) for k in bnlstm_data.MODEL_KEYS))(
        bnlstm_data.jax_key(seed))
    out = jax.device_get(_readings(w, jnp.asarray(tokens), jnp.asarray(capture),
                                   jnp.asarray(lo), jnp.asarray(hi),
                                   n_greedy, cfg["control"] if control
                                   else None))
    g = out["greedy"][:len(greedy_rows)]
    scale = float(g[:, 1].max()) if len(g) else 0.0

    def rel_state(hk, ck, norm):
        if not want.any():
            return 0.0
        errs = []
        for prog, ref in ((state["h"], out[hk]), (state["c"], out[ck])):
            p = prog[:, want].astype(np.float64)
            q = np.asarray(ref)[:, want].astype(np.float64)
            errs.append(norm(p - q) / max(norm(q), 1e-30))
        return max(errs)

    rms = lambda x: float(np.sqrt(np.mean(x * x)))
    peak = lambda x: float(np.abs(x).max())

    res = {
        "greedy_gap": (float(g[:, 0].max()) / scale) if scale > 0 else
        (0.0 if not len(g) else float("inf")),
        "state_err": rel_state("h_ref", "c_ref", rms) if bookkeeping_ok
        else float("inf"),
        "state_err_max": rel_state("h_ref", "c_ref", peak)
        if bookkeeping_ok else float("inf"),
        "greedy_rows": len(greedy_rows),
        "greedy_tokens": int(g[:, 3].sum()) if len(g) else 0,
        "greedy_tokens_agree": int(g[:, 2].sum()) if len(g) else 0,
        "slot_rows": int(want.sum()),
        "logit_scale": scale,
    }
    if control:
        res["control"] = {
            "greedy_gap": (float(g[:, 4].max()) / scale) if scale > 0
            else 0.0,
            "state_err": rel_state("h_ctl", "c_ctl", rms),
            "state_err_max": rel_state("h_ctl", "c_ctl", peak),
        }
    return res
